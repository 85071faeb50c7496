"""Training loop: metrics, periodic async checkpointing, budget schedules,
auto-resume, elastic restart.

The loop is deliberately thin — all heavy lifting is in the jitted step — but
production-shaped: it survives SIGTERM-style interruption (atomic checkpoints),
resumes from the newest checkpoint (possibly onto a different mesh), and
switches between the pre-compiled budget buckets of the runtime's
:class:`~repro.api.BudgetSchedule` per step (paper App. B.1 straggler
mitigation and §4 warmup/anneal schedules).

:func:`train_loop` is the Runtime-native loop (``Runtime.train`` delegates
here); :func:`train` is the legacy kwarg spelling kept as a thin shim that
constructs a Runtime internally and warns once.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Iterable, Optional

import jax
import numpy as np

from repro import compat
from repro.api import Runtime
from repro.obs import observability
from repro.configs.base import ArchConfig
from repro.core import SketchPolicy
from repro.optim import Optimizer
from repro.train import checkpoint as ckptlib
from repro.train.checkpoint import CheckpointManager
from repro.train.train_step import TrainState, init_state

__all__ = ["TrainerConfig", "train", "train_loop"]


@dataclasses.dataclass
class TrainerConfig:
    """Loop mechanics (steps, logging, checkpointing) — everything about the
    *model and estimator* lives on the Runtime instead.

    ``straggler_budgets`` is the legacy spelling of a reactive
    :class:`~repro.api.BudgetSchedule` and is honoured only through the
    legacy :func:`train` shim.
    """

    steps: int = 100
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    seed: int = 0
    straggler_budgets: tuple = ()  # legacy; use Runtime.schedule


def _host_metrics(metrics, *, scalars_only: bool = False) -> dict:
    """Device-get a metrics tree to plain python (floats; nested dicts — the
    per-site probe vectors — become lists, or are dropped with
    ``scalars_only`` for the cheap per-step controller fetch). One batched
    ``device_get`` per call, not one transfer per key."""
    tree = {k: v for k, v in metrics.items()
            if not (scalars_only and isinstance(v, dict))}
    fetched = jax.device_get(tree)
    out = {}
    for k, v in fetched.items():
        if isinstance(v, dict):
            out[k] = {kk: np.asarray(vv).astype(float).tolist()
                      for kk, vv in v.items()}
        else:
            out[k] = float(np.asarray(v))
    return out


def _policy_can_probe(policy, execution=None) -> bool:
    """Does any site of ``policy`` emit telemetry probes? (column-family
    method + an estimator implementing the probe hook, or — under
    ``tp_sketch`` — a TP-shardable estimator whose shard_map plans probe
    in-body; see repro/telemetry/probes.py and core/site.py)."""
    from repro.core.site import tp_estimator
    from repro.telemetry.probes import probe_capable

    if policy is None or policy.location != "all":
        return False
    tp = execution is not None and execution.tp_sketch

    def can(cfg):
        if probe_capable(cfg):
            return True
        # TP plans probe from the in-body plan marginals even when the
        # estimator has no apply_with_probe hook
        return tp and tp_estimator(cfg) is not None

    return can(policy.base) or any(can(cfg) for _, cfg in policy.overrides)


def train_loop(runtime: Runtime, cfg: ArchConfig, opt: Optimizer,
               data: Iterable, tcfg: Optional[TrainerConfig] = None, *,
               state: Optional[TrainState] = None,
               on_metrics: Optional[Callable] = None,
               faults=None, seed_salt: int = 0,
               on_event: Optional[Callable] = None):
    """Run the loop under ``runtime``; returns (final_state, history).

    One train step is compiled per distinct budget in
    ``runtime.schedule.buckets()`` — before the loop starts — and each step
    dispatches to the bucket the schedule (or, in controller mode, the
    straggler/adaptive controller) selects. Unbiasedness means bucket
    switches never bias the gradient, only its variance (paper §2.2).

    Telemetry: with ``runtime.execution.telemetry`` set, each step's metrics
    carry the probe summary and the configured sinks receive one record per
    ``telemetry.interval`` steps. An adaptive schedule
    (``BudgetSchedule.adaptive``) implies probes — they are enabled here
    automatically when the execution config has no telemetry — and its
    controller consumes the host-fetched ``probe_snr`` between steps to pick
    the next (pre-compiled) bucket: no recompiles, ever.

    Resilience (``runtime.execution.resilience`` set; docs/resilience.md):
    the compiled steps take a traced ``fault_scale`` operand, a
    :class:`~repro.resilience.GradSentinel` digests the per-step scalars —
    skipped updates surface as ``sentinel_trip``, trips force the exact
    bucket for K steps, and M consecutive trips raise
    :class:`~repro.resilience.RollbackRequired` for the supervisor.
    ``faults`` is a :class:`~repro.resilience.FaultPlan` (or a supervisor's
    :class:`~repro.resilience.FaultInjector`); ``seed_salt`` folds an extra
    term into every step key so a retried trajectory resamples its sketches;
    ``on_event`` receives every fault/trip/recovery record (the records also
    go to the telemetry sinks). A failed async checkpoint write surfaces as
    :class:`~repro.train.checkpoint.CheckpointError` here — with resilience
    enabled it is recorded and retried synchronously instead of raising.
    """
    tcfg = tcfg or TrainerConfig()
    schedule = runtime.schedule
    tel = runtime.execution.telemetry
    if schedule.is_adaptive and runtime.execution.accum != 1:
        raise ValueError(
            "adaptive BudgetSchedule requires accum == 1: the SNR probes "
            "cannot ride accumulated microbatches, so the controller would "
            "have no signal — use a fixed/warmup/reactive schedule with "
            "accumulation")
    if schedule.is_adaptive and (tel is None or not tel.probes):
        from repro.telemetry import TelemetryConfig

        # per_site=False: the controller only consumes the probe_snr scalar,
        # so the implicit config skips the per-site vectors (a user-supplied
        # TelemetryConfig keeps its own per_site choice)
        tel = (TelemetryConfig(per_site=False) if tel is None
               else dataclasses.replace(tel, probes=True))
        runtime = runtime.replace(execution=runtime.execution.replace(telemetry=tel))
    if schedule.is_adaptive and not _policy_can_probe(runtime.policy,
                                                      runtime.execution):
        warnings.warn(
            "adaptive BudgetSchedule cannot measure gradient SNR here "
            "(exact/location-restricted policy, or no probe-capable site: "
            "column-family method + an estimator with the probe hook or a "
            "TP-shardable plan) — the controller will hold its first "
            "bucket; see docs/telemetry.md", stacklevel=2)
    rcfg = runtime.execution.resilience
    if faults is not None and rcfg is None:
        raise ValueError(
            "faults= requires runtime.execution.resilience (the compiled "
            "step needs its traced fault_scale operand) — set "
            "ExecutionConfig(resilience=ResilienceConfig())")
    injector = sentinel = None
    if rcfg is not None:
        from repro.resilience.faults import DeviceLossFault, FaultInjector
        from repro.resilience.sentinel import GradSentinel, RollbackRequired

        injector = FaultInjector.wrap(faults)
        if rcfg.sentinel:
            sentinel = GradSentinel(rcfg)
    ob = observability(runtime.execution.obs)
    tracer = ob.tracer
    traced = tracer.enabled
    key = compat.prng_key(tcfg.seed)
    if state is None:
        state = init_state(jax.random.fold_in(key, 0), cfg, opt,
                           runtime.policy, execution=runtime.execution)

    ckpt = (CheckpointManager(tcfg.ckpt_dir, tcfg.ckpt_every, tracer=tracer)
            if tcfg.ckpt_dir else None)
    if ckpt is not None:
        # restore() yields host numpy leaves; commit them to device arrays
        # *before* the loop. The compiled step donates its state argument,
        # and donating an auto-converted numpy operand hands XLA a
        # conversion temporary to alias in place — the whole donation chain
        # then rides memory whose keep-alive drops with this call frame
        # (observed as the resumed run's final state.step reading recycled
        # bytes once the allocator is under churn).
        mesh = runtime.execution.mesh
        if mesh is not None:
            from repro.train import elastic
            restored = ckpt.restore_or_none(
                state, shardings=elastic.state_shardings(state, mesh))
        else:
            restored = ckpt.restore_or_none(state)
            if restored is not None:
                # an explicit target device forces owned copies; deviceless
                # device_put (like the jit-call conversion) may zero-copy
                # aligned numpy buffers, which the donating step then aliases
                dev = jax.local_devices()[0]
                restored = (compat.tree_map(
                    lambda x: jax.device_put(x, dev), restored[0]),
                    restored[1])
        if restored is not None:
            state, step0 = restored
            print(f"[trainer] resumed from step {step0}")

    # pre-built budget buckets: one compiled step per distinct budget; the
    # sentinel's escalation target (exact, i.e. None) is added when the
    # schedule alone would never compile it
    buckets = schedule.buckets()
    if sentinel is not None and None not in buckets:
        buckets = buckets + (None,)
    with tracer.span("build_buckets", n_buckets=len(buckets)):
        steps_by_budget = {b: runtime.train_step(cfg, opt, budget=b)
                           for b in buckets}
    controller = schedule.make_controller(policy=runtime.policy)
    fetch_each_step = bool(controller is not None
                           and getattr(controller, "wants_metrics", False))
    from repro.telemetry import sinks as tsinks

    sink = tsinks.build_sinks(tel)

    def sync(metrics, scalars_only, wait_only):
        if wait_only:
            jax.block_until_ready(metrics["loss"])
            return None
        return _host_metrics(metrics, scalars_only=scalars_only)

    def fetch(metrics, step, *, scalars_only=False, wait_only=False):
        # every device->host fetch (or bare wait) of the loop: a train_fetch
        # span when traced, no clock read when not
        if traced:
            with tracer.span("train_fetch", step=step):
                return sync(metrics, scalars_only, wait_only)
        return sync(metrics, scalars_only, wait_only)

    def emit(rec: dict):
        if sink is not None:
            sink.write(dict(rec))
        if on_event is not None:
            on_event(dict(rec))
        if ob.flight is not None:
            ob.flight.note(rec)

    def ckpt_wait_safe():
        # a pending async write may carry a CheckpointError; before raising a
        # recovery fault we drain it so the supervisor sees a settled
        # directory (with resilience on, the write error is recorded — the
        # rollback target is the newest *verified* checkpoint anyway)
        if ckpt is None:
            return
        try:
            with tracer.span("ckpt_wait"):
                ckpt.wait()
        except ckptlib.CheckpointError as e:
            emit({"event": "ckpt_io_error", "step": step, "error": str(e)})
            ob.dump_crash("ckpt_io", {"step": step, "error": str(e)})

    reg = ob.metrics
    steps_counter = reg.counter("train.steps") if reg is not None else None
    budget_gauge = reg.gauge("train.budget") if reg is not None else None
    history = []
    data_it = iter(data)
    start_step = int(jax.device_get(state.step))
    loop_span = tracer.span("train_loop", start_step=start_step,
                            steps=tcfg.steps)
    try:
      with loop_span:
        for step in range(start_step, tcfg.steps):
            if traced:
                with tracer.span("train_data", step=step):
                    batch = next(data_it)
            else:
                batch = next(data_it)
            fscale = 1.0
            if injector is not None:
                fault = injector.take(step)
                if fault is not None:
                    emit({"event": "fault_injected", "step": step,
                          "kind": fault.kind})
                    with tracer.span("fault_injected", step=step,
                                     kind=fault.kind):
                        if fault.kind == "device_loss":
                            ckpt_wait_safe()
                            raise DeviceLossFault(step, fault.mesh_shape,
                                                  history=history, state=state)
                        if fault.kind == "slow":
                            time.sleep(fault.sleep_s)
                        elif fault.kind == "ckpt_io":
                            if ckpt is not None:
                                ckptlib.inject_fault_once()
                        elif fault.kind == "nonfinite":
                            fscale = float("nan")
                        elif fault.kind == "spike":
                            fscale = fault.scale
            step_key = jax.random.fold_in(key, step + 1)
            if seed_salt:
                # retried trajectories resample their sketches; salt 0 is
                # skipped entirely so the first attempt stays bit-identical
                # to a resilience-off run
                step_key = jax.random.fold_in(step_key, seed_salt)
            budget = controller.budget if controller else schedule.budget_at(step)
            if sentinel is not None:
                budget = sentinel.override(budget)
            fn = steps_by_budget[budget]
            if controller:
                controller.step_begin()
            if traced:
                # span attrs built only on the traced path — tracing-off
                # stays allocation-free here
                with tracer.span("train_step", step=step,
                                 budget=-1.0 if budget is None else budget):
                    if rcfg is not None:
                        state, metrics = fn(state, batch, step_key, fscale)
                    else:
                        state, metrics = fn(state, batch, step_key)
            elif rcfg is not None:
                state, metrics = fn(state, batch, step_key, fscale)
            else:
                state, metrics = fn(state, batch, step_key)
            if steps_counter is not None:
                steps_counter.inc()
            host_m = None  # full fetch (sink/log cadence only)
            host_scalars = None
            if controller or sentinel is not None:
                # per-step fetch stays scalars-only: the controller consumes
                # one scalar (probe_snr), the sentinel a handful; per-site
                # vectors are fetched on sink/log steps below
                host_scalars = fetch(
                    metrics, step, scalars_only=True,
                    wait_only=not (fetch_each_step or sentinel is not None))
            if controller:
                controller.step_end(host_scalars if fetch_each_step else None)
            if sentinel is not None:
                cause = sentinel.observe(step, host_scalars)
                if cause is not None:
                    emit(tsinks.recovery_record(
                        "sentinel_trip", step=step, cause=cause,
                        escalate_steps=rcfg.escalate_steps,
                        consecutive=sentinel.consecutive))
                if sentinel.should_rollback:
                    # raise BEFORE maybe_save: a state the sentinel cannot
                    # stabilise must never reach a checkpoint
                    ckpt_wait_safe()
                    raise RollbackRequired(step, sentinel.last_cause,
                                           history=history)
            if sink is not None and step % tel.interval == 0:
                host_m = fetch(metrics, step)
                sink.write(dict(host_m, step=step, budget=budget))
            if budget_gauge is not None and (
                    step % tcfg.log_every == 0 or step == tcfg.steps - 1):
                budget_gauge.set(-1.0 if budget is None else budget)
                if ob.flight is not None:
                    ob.flight.snapshot(step)
            if step % tcfg.log_every == 0 or step == tcfg.steps - 1:
                m = host_m if host_m is not None else fetch(metrics, step)
                m = dict(m, step=step, budget=budget)
                history.append(m)
                if on_metrics:
                    on_metrics(m)
                else:
                    b = "exact" if budget is None else f"{budget:.2f}"
                    print(f"[trainer] step {step:6d} loss {m['loss']:.4f} "
                          f"budget {b}")
            if ckpt is not None:
                try:
                    ckpt.maybe_save(step + 1, state)
                except ckptlib.CheckpointError as e:
                    if rcfg is None:
                        raise
                    # the failed async write is retried synchronously: one
                    # recorded hiccup, no lost checkpoint cadence
                    emit({"event": "ckpt_io_recovered", "step": step,
                          "error": str(e)})
                    ob.dump_crash("ckpt_io", {"step": step, "error": str(e)})
                    with tracer.span("ckpt_save_sync", step=step + 1):
                        ckptlib.save(ckpt.dir, step + 1, state, keep=ckpt.keep)
        if ckpt is not None:
            try:
                with tracer.span("ckpt_wait"):
                    ckpt.wait()
            except ckptlib.CheckpointError as e:
                if rcfg is None:
                    raise
                emit({"event": "ckpt_io_recovered", "step": tcfg.steps,
                      "error": str(e)})
                ob.dump_crash("ckpt_io", {"step": tcfg.steps, "error": str(e)})
                with tracer.span("ckpt_save_sync", step=tcfg.steps):
                    ckptlib.save(ckpt.dir, tcfg.steps, state, keep=ckpt.keep)
    finally:
        if sink is not None:
            sink.close()
        ob.export()
    return state, history


_warned_legacy = False


def train(cfg: ArchConfig, opt: Optimizer, data: Iterable, tcfg: TrainerConfig,
          policy: Optional[SketchPolicy] = None, *, mesh=None,
          act_sharding=None, data_axes=("data",), model_axes=("model",),
          tp_sketch: bool = False, compact_grads: bool = False,
          state: Optional[TrainState] = None,
          on_metrics: Optional[Callable] = None):
    """Legacy entry point — prefer ``repro.api.Runtime(...).train(...)``.

    Thin deprecation shim: the loose kwargs are bundled into a
    :class:`~repro.api.Runtime` (``tcfg.straggler_budgets`` becomes a
    reactive :class:`~repro.api.BudgetSchedule`) and the call is forwarded to
    :func:`train_loop`, so old calls produce bit-identical steps to the
    equivalent Runtime. Warns ``DeprecationWarning`` once per process.
    """
    global _warned_legacy
    if not _warned_legacy:
        warnings.warn(
            "repro.train.trainer.train(...) with loose kwargs is deprecated; "
            "build a repro.api.Runtime and call Runtime.train(...) "
            "(see docs/api.md for the migration table)",
            DeprecationWarning, stacklevel=2)
        _warned_legacy = True
    straggler = tuple(tcfg.straggler_budgets) if (tcfg.straggler_budgets
                                                 and policy is not None) else ()
    runtime = Runtime.from_legacy_kwargs(
        policy, mesh=mesh, act_sharding=act_sharding, data_axes=data_axes,
        model_axes=model_axes, tp_sketch=tp_sketch, compact_grads=compact_grads,
        straggler_budgets=straggler)
    return train_loop(runtime, cfg, opt, data, tcfg, state=state,
                      on_metrics=on_metrics)
