"""Jitted training step factory: sketched backprop + sharded optimizer update.

``make_train_step`` closes over static config (arch, sketch policy, optimizer)
and returns a function of pure pytrees — ready for ``jax.jit`` with the param
/ batch shardings from ``repro.launch.sharding``. The same factory builds the
dry-run ``train_step`` (lowered against ShapeDtypeStructs).

Distributed-optimization knobs:
  * gradient accumulation (``accum``) — microbatch scan, gradients averaged;
    with FSDP-sharded params XLA lowers the per-microbatch gradient sums to
    reduce-scatters that overlap the next microbatch's backward.
  * compressed DP all-reduce — when the policy uses a *compact/pallas* sketch,
    the dW of sketched layers is column-sparse with an index set shared across
    DP replicas (shared step key), so the all-reduce moves ~budget × bytes.
    Under SPMD/pjit this happens structurally: the backward scatter-add of the
    compact dW is sharded over the data axis, and XLA reduce-scatters only the
    written rows' values. EXPERIMENTS.md §Perf measures the effect.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.api.execution import ExecutionConfig
from repro.configs.base import ArchConfig
from repro.core import SketchPolicy
from repro.core import compact_grad as cgrad
from repro.core import plan_state as pstate
from repro.models import lm
from repro.obs import scopes
from repro.optim import Optimizer, global_grad_norm

__all__ = ["TrainState", "make_train_step", "init_state"]


@compat.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: dict
    opt_state: dict
    step: jax.Array


def init_state(key, cfg: ArchConfig, opt: Optimizer,
               policy: Optional[SketchPolicy] = None, *,
               execution: Optional[ExecutionConfig] = None) -> TrainState:
    """Fresh train state. ``policy``/``execution`` (optional, backwards
    compatible) let plan-carry estimators ("onepass"/"stale") merge their
    permanent per-site score leaves into the params tree — without them a
    carry policy still runs, every step just re-seeds from the uniform
    prior (see core/plan_state.py)."""
    params = lm.init_params(key, cfg)
    if pstate.policy_uses_carry(policy):
        ex = execution
        params = pstate.with_plan_state(
            params, policy, n_layers=cfg.n_layers,
            mesh=ex.mesh if ex else None,
            data_axes=ex.data_axes if ex else ("data",),
            model_axes=ex.model_axes if ex else ("model",),
            tp_sketch=ex.tp_sketch if ex else False)
    return TrainState(params=params, opt_state=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(cfg: ArchConfig, opt: Optimizer, policy: Optional[SketchPolicy] = None,
                    *, execution: Optional[ExecutionConfig] = None,
                    mesh=None, act_sharding=None, accum: int = 1,
                    cost_mode: bool = False, data_axes=("data",), model_axes=("model",),
                    tp_sketch: bool = False, compact_grads: bool = False):
    """Returns ``step_fn(state, batch, key) -> (state, metrics)``.

    ``execution`` is the one-object spelling (the :class:`Runtime` front door
    passes it); the loose kwargs are the legacy spelling and are ignored when
    ``execution`` is given.

    With ``execution.resilience`` set the step instead has the signature
    ``step_fn(state, batch, key, fault_scale) -> (state, metrics)``:
    ``fault_scale`` is a *traced* scalar multiplying the loss (1.0 in normal
    operation — an IEEE bitwise identity — NaN / large values under fault
    injection), and when ``resilience.sentinel`` is true the optimizer
    update is gated on an in-graph non-finite/norm-explosion flag reported
    as ``metrics["sentinel_trip"]`` (see docs/resilience.md).

    ``compact_grads=True`` threads per-site gradient slots through the params
    tree so sketched sites' dW comes out of the backward as a
    :class:`~repro.core.compact_grad.CompactGrad` (rows + indices, no
    densify-scatter) and is applied by the optimizer as a sparse-row update.
    Requires ``accum == 1`` — microbatches sample different index sets, so
    compact gradients cannot be accumulated (enforced by ExecutionConfig).

    ``execution.telemetry`` (a :class:`repro.telemetry.TelemetryConfig` with
    ``probes=True``) additionally threads per-site *probe* slots: the step's
    metrics gain the telemetry summary (``probe_gsq`` / ``probe_var`` /
    ``probe_snr`` / ``probe_align`` and, optionally, per-site vectors under
    ``probe_sites``) as a side output of the same backward — no second
    backward, no extra pass over G. Sites routed through a TP shard_map plan
    probe too: the spine computes the per-shard probe inside the backward
    body and psums it over the model axis (see docs/telemetry.md).
    """
    if execution is None:
        execution = ExecutionConfig(mesh=mesh, act_sharding=act_sharding,
                                    data_axes=tuple(data_axes),
                                    model_axes=tuple(model_axes),
                                    tp_sketch=tp_sketch,
                                    compact_grads=compact_grads, accum=accum,
                                    cost_mode=cost_mode)
    ex = execution
    accum = ex.accum
    compact_grads = ex.compact_grads
    tel = ex.telemetry
    telemetry_on = (tel is not None and tel.probes and policy is not None
                    and accum == 1)
    rcfg = ex.resilience
    carry_on = pstate.policy_uses_carry(policy)
    if ex.fused_vmem_limit is not None or ex.obs is not None:
        # bind the execution-level kernel knobs once per step build: the
        # fused-dispatch VMEM budget and the obs metrics sink its
        # dispatch/fallback decisions are recorded into (kernels/ops.py)
        from repro.kernels import ops as kops
        from repro.obs import observability

        kops.configure(vmem_limit=ex.fused_vmem_limit,
                       metrics=observability(ex.obs).metrics)

    def ctx_for(key):
        return ex.make_ctx(policy=policy, key=key)

    def loss_fn(params, batch, key, fault_scale):
        total, metrics = lm.lm_loss(params, batch, ctx_for(key), cfg, key)
        if rcfg is not None:
            # traced operand: fault injection (NaN / spike multipliers on the
            # loss, hence on every cotangent) without a recompile per fault;
            # x * 1.0 is an IEEE bitwise identity, so the clean path is
            # bit-identical to a resilience-off step
            total = total * fault_scale
        return total, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def one_micro(params, batch, key, fault_scale):
        (loss, metrics), grads = grad_fn(params, batch, key, fault_scale)
        return loss, metrics, grads

    def base_step(state: TrainState, batch, key, fault_scale):
        probe_metrics = {}
        if accum == 1:
            params_in = state.params
            if compact_grads:
                params_in = cgrad.with_grad_slots(
                    state.params, policy, mesh=ex.mesh, data_axes=ex.data_axes,
                    model_axes=ex.model_axes, tp_sketch=ex.tp_sketch,
                    n_layers=cfg.n_layers)
            if telemetry_on:
                from repro.telemetry import probes as tprobes

                params_in = tprobes.with_probe_slots(
                    params_in, policy, n_layers=cfg.n_layers, mesh=ex.mesh,
                    data_axes=ex.data_axes, model_axes=ex.model_axes,
                    tp_sketch=ex.tp_sketch)
            loss, metrics, grads = one_micro(params_in, batch, key, fault_scale)
            if telemetry_on:
                grads, probe_vecs = tprobes.collect_probes(grads)
                probe_metrics = tprobes.summarize(probe_vecs,
                                                  per_site=tel.per_site)
            if compact_grads:
                grads = cgrad.fold_slot_grads(grads)
        else:
            def micro(carry, xs):
                mb, mkey = xs
                loss, metrics, grads = one_micro(state.params, mb, mkey,
                                                 fault_scale)
                acc_loss, acc_grads = carry
                return (acc_loss + loss / accum,
                        compat.tree_map(lambda a, g: a + g / accum, acc_grads, grads)), metrics

            def to_micro(name, x):
                ax = 1 if name == "positions" else 0  # M-RoPE positions: [3, B, S]
                b = x.shape[ax] // accum
                x = jnp.moveaxis(x, ax, 0)
                x = x.reshape((accum, b) + x.shape[1:])
                return jnp.moveaxis(x, 1, ax + 1) if ax else x

            mbs = {k: to_micro(k, v) for k, v in batch.items()}
            keys = jax.random.split(key, accum)
            zeros = compat.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss, grads), metrics = jax.lax.scan(micro, (jnp.zeros(()), zeros), (mbs, keys))
            metrics = compat.tree_map(lambda m: m[-1], metrics)
        with compat.named_scope(scopes.OPTIM):
            fresh_scores = {}
            if carry_on:
                # plan carry: the sslot cotangents ARE the refreshed scores —
                # pull them out (zeroing the leaves keeps the gradient tree
                # congruent for the optimizer and the grad norm; under accum
                # the scan has averaged the microbatches' scores, still a
                # valid carry)
                grads, fresh_scores = pstate.collect_plan_state(grads)
            new_params, new_opt = opt.update(grads, state.opt_state, state.params,
                                             state.step)
            if fresh_scores:
                # write the refreshed carry over whatever the optimizer did to
                # the sslot leaves (zero grads ⇒ only decay touched them) —
                # BEFORE sentinel gating, so a tripped step keeps the old carry
                new_params = pstate.write_plan_state(new_params, fresh_scores)
            gn = _global_norm(grads)
            if rcfg is not None and rcfg.sentinel:
                from repro.resilience.sentinel import gate_update, trip_flag

                # one scalar out of quantities the step already materializes;
                # a tripped step keeps the old params AND opt state (the
                # moment buffers must not ingest a poisoned gradient) — the
                # step counter still advances so the schedule/PRNG stay on
                # track
                ok, tripped = trip_flag(loss, gn, rcfg.max_grad_norm)
                new_params = gate_update(ok, new_params, state.params)
                new_opt = gate_update(ok, new_opt, state.opt_state)
                probe_metrics = dict(probe_metrics, sentinel_trip=tripped)
        new_state = TrainState(params=new_params, opt_state=new_opt, step=state.step + 1)
        if ex.mesh is not None:
            from repro.train import elastic

            # the state leaves the step in the layout it comes in with (the
            # mesh's state shardings): left free, XLA shards some replicated
            # leaves (the norm gains) on the way out, and the next call then
            # compiles the whole step a second time for the new layout
            new_state = jax.lax.with_sharding_constraint(
                new_state, elastic.state_shardings(new_state, ex.mesh))
        metrics = dict(metrics, loss=loss, grad_norm=gn, **probe_metrics)
        return new_state, metrics

    if rcfg is None:
        # the historical three-argument step: bit-compatible executables,
        # unchanged golden traces
        def step_fn(state: TrainState, batch, key):
            return base_step(state, batch, key, jnp.float32(1.0))
    else:
        def step_fn(state: TrainState, batch, key, fault_scale):
            return base_step(state, batch, key, fault_scale)

    return step_fn


def _global_norm(tree):
    return global_grad_norm(tree)
