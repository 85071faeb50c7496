"""The Runtime: one front door for sketched training, serving and dry-runs.

A :class:`Runtime` bundles the paper's three orthogonal knobs into one
frozen, hashable object:

  * **what** to estimate — :class:`~repro.core.policy.SketchPolicy`
    (which VJP sites get which unbiased estimator, resolved through the
    open estimator registry);
  * **where/how** to run — :class:`~repro.api.execution.ExecutionConfig`
    (mesh, shardings, TP-local sketching, compact gradients, accumulation);
  * **when** at which budget — :class:`~repro.api.schedule.BudgetSchedule`
    (piecewise-constant budget-vs-step, realised as pre-compiled buckets;
    reactive straggler mode).

Because the Runtime is hashable, compiled train steps are cached on it:
asking the same Runtime for the same (arch, optimizer, budget) step twice
returns the *same* jitted callable — one XLA compile per schedule bucket,
never one per call site. ``examples/``, ``benchmarks/``, ``launch/dryrun``
and ``serve/`` all consume this object; the legacy kwarg spellings on
``repro.train.trainer.train`` construct one internally (with a one-time
DeprecationWarning).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

from repro.api.execution import ExecutionConfig
from repro.api.schedule import BudgetSchedule
from repro.core import SketchPolicy

__all__ = ["Runtime"]

# Compiled-step cache: (runtime, cfg, opt, budget, donate, jitted) -> step fn.
# Module-level (not per-instance) so equal Runtimes share executables; the
# paired list records build keys for the recompile-count tests. LRU-bounded:
# Optimizer instances hash by the identity of their closures, so sweeps that
# rebuild optimizers would otherwise pin every compiled executable forever.
_STEP_CACHE: Dict[Tuple, Callable] = {}
_STEP_CACHE_MAX = 64
_STEP_BUILDS: list = []


def _cache_get(key):
    fn = _STEP_CACHE.pop(key, None)
    if fn is not None:
        _STEP_CACHE[key] = fn  # re-insert = move to LRU tail
    return fn


def _cache_put(key, fn):
    while len(_STEP_CACHE) >= _STEP_CACHE_MAX:
        _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
    _STEP_CACHE[key] = fn
    _STEP_BUILDS.append(key)


def _cache_clear():  # test hook
    _STEP_CACHE.clear()
    del _STEP_BUILDS[:]


def _ledger_key(runtime, cfg, budget, donate) -> str:
    """Human-readable spelling of one step-cache key for the compile ledger
    (same identity granularity as _STEP_CACHE: runtime hash disambiguates
    equal arch/budget under different policies/meshes)."""
    name = getattr(cfg, "name", type(cfg).__name__)
    return (f"train_step/{name}/budget={budget}/donate={donate}"
            f"/rt={hash(runtime) & 0xffffffff:08x}")


def _with_ledger(jfn, ob, lkey: str, want_memory: bool, op_table: bool = False):
    """Wrap a jitted step so its first call runs AOT lower+compile, timing
    the trace and compile phases separately and recording
    ``memory_analysis()`` into the shared ledgers; later calls dispatch to
    the compiled executable directly. With ``op_table`` the compiled HLO's
    op→layer table (``repro.obs.scopes``) is recorded on ``ob`` once per
    compile.

    Falls back to the plain jitted callable — permanently — if AOT is
    unavailable or a later call arrives with different arg shapes (the
    compiled object is monomorphic; ``jax.jit`` re-specializes instead).
    Host-side only: the computation, donation and outputs are unchanged.
    """
    from repro.obs import clock, ledgers

    state = {"compiled": None, "first": True}

    def step(*args, **kw):
        compiled = state["compiled"]
        if compiled is not None:
            try:
                return compiled(*args, **kw)
            except (TypeError, ValueError):
                # shape-polymorphic caller — hand back to jit's own cache
                state["compiled"] = None
                return jfn(*args, **kw)
        if not state["first"]:
            return jfn(*args, **kw)
        state["first"] = False
        t0 = clock.now()
        try:
            lowered = jfn.lower(*args, **kw)
            t1 = clock.now()
            compiled = lowered.compile()
            t2 = clock.now()
        except Exception:
            # AOT path unavailable on this release/call — time the first
            # call as one opaque trace+compile+run figure instead
            t0 = clock.now()
            out = jfn(*args, **kw)
            _ledger_compile(ob, lkey, first_call_s=clock.now() - t0)
            return out
        mem = None
        if want_memory:
            try:
                mem = ledgers.memory_summary(compiled.memory_analysis())
            except Exception:
                mem = None
        _ledger_compile(ob, lkey, trace_s=t1 - t0, compile_s=t2 - t1,
                        memory=mem)
        if op_table:
            from repro.obs import scopes

            ob.record_op_layers(*scopes.op_layer_table(compiled.as_text()))
        if ob is not None and ob.tracer.enabled:
            parent = ob.tracer.current_id()
            ob.tracer.add_span("jit_trace", t0, t1, parent=parent, key=lkey)
            ob.tracer.add_span("xla_compile", t1, t2, parent=parent, key=lkey)
        state["compiled"] = compiled
        return compiled(*args, **kw)

    # the executable the calls run (None before the first call or after a
    # fallback), for callers that inspect the program that actually ran
    step.compiled = lambda: state["compiled"]
    return step


def _ledger_compile(ob, lkey: str, *, trace_s=None, compile_s=None,
                    first_call_s=None, memory=None):
    from repro.obs import ledgers

    kw = dict(trace_s=trace_s, compile_s=compile_s, first_call_s=first_call_s)
    if ob is not None and ob.compile_ledger is not None:
        ob.compile_ledger.record_compile(lkey, **kw)
    if ob is not None and ob.memory_ledger is not None and memory is not None:
        ob.memory_ledger.record(lkey, memory)
        ob.memory_ledger.sample(lkey)
    if ledgers.global_active():
        ledgers.GLOBAL_COMPILE_LEDGER.record_compile(lkey, **kw)


def _ledger_hit(ob, lkey: str):
    from repro.obs import ledgers

    if ob is not None and ob.compile_ledger is not None:
        ob.compile_ledger.record_hit(lkey)
    if ledgers.global_active():
        ledgers.GLOBAL_COMPILE_LEDGER.record_hit(lkey)


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Unified sketched-backprop runtime (hashable; compare by value).

    ``Runtime()`` is a valid single-device exact-backprop runtime; every
    field upgrades one axis independently.
    """

    policy: Optional[SketchPolicy] = None
    execution: ExecutionConfig = dataclasses.field(default_factory=ExecutionConfig)
    schedule: BudgetSchedule = dataclasses.field(default_factory=BudgetSchedule)

    def replace(self, **kw) -> "Runtime":
        return dataclasses.replace(self, **kw)

    # -- policy / context ---------------------------------------------------

    def policy_at(self, budget: Optional[float] = 1.0) -> Optional[SketchPolicy]:
        """The effective policy at one schedule budget (see BudgetSchedule:
        None = exact, 1.0 = as configured, else per-site override)."""
        if budget is None or self.policy is None:
            return None
        if budget >= 1.0:
            return self.policy
        return self.policy.with_budget(budget)

    def ctx(self, key=None, *, budget: Optional[float] = 1.0,
            decode: bool = False, layer_index: int = 0, n_layers: int = 1):
        """A :class:`~repro.nn.common.Ctx` for hand-driven model calls
        (`examples/quickstart.py` pattern: custom loss, own loop)."""
        return self.execution.make_ctx(policy=self.policy_at(budget), key=key,
                                       decode=decode, layer_index=layer_index,
                                       n_layers=n_layers)

    # -- observability ------------------------------------------------------

    def observability(self):
        """The shared :class:`repro.obs.Observability` for this runtime's
        ``execution.obs`` config: tracer, metrics registries, compile/memory
        ledgers (``.report()`` gives the JSON-ready rollup — compile
        hit/miss, per-step memory, merged metrics). The disabled singleton
        when ``obs`` is None."""
        from repro.obs import observability

        return observability(self.execution.obs)

    # -- training -----------------------------------------------------------

    def train_step(self, cfg, opt, *, budget: Optional[float] = 1.0,
                   donate: bool = True, jitted: bool = True) -> Callable:
        """``step_fn(state, batch, key) -> (state, metrics)`` for this runtime.

        Jitted results are cached on (runtime, cfg, opt, budget, donate):
        the same Runtime yields the same executable — one compile per
        schedule bucket. ``jitted=False`` returns the raw step function for
        callers that jit with their own in_shardings (dry-run, benchmarks).
        """
        if self.policy is None:
            # every budget is the same exact step — collapse the cache key
            # so a multi-bucket schedule with no policy compiles once
            budget = 1.0
        from repro.obs import ledgers, observability

        ob = observability(self.execution.obs)
        ledger_on = jitted and (ob.compile_ledger is not None
                                or ob.memory_ledger is not None)
        global_on = jitted and ledgers.global_active()
        # tracing on: the step is wrapped too, to record its op->layer table
        trace_on = jitted and ob.tracer.enabled
        lkey = (_ledger_key(self, cfg, budget, donate)
                if (ledger_on or global_on or trace_on) else None)
        key = (self, cfg, opt, budget, donate, jitted)
        fn = _cache_get(key)
        if fn is not None:
            if lkey is not None:
                _ledger_hit(ob if ledger_on else None, lkey)
            return fn
        import jax

        from repro.train.train_step import make_train_step

        fn = make_train_step(cfg, opt, self.policy_at(budget),
                             execution=self.execution)
        if jitted:
            fn = jax.jit(fn, donate_argnums=(0,) if donate else ())
            if trace_on:
                # the persistent compile cache leaves metadata out of its key
                # by default: a traced process could load an executable built
                # from code without the device scopes, and its table would
                # come out empty
                jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
            if lkey is not None:
                fn = _with_ledger(fn, ob if (ledger_on or trace_on) else None, lkey,
                                  ob.memory_ledger is not None, op_table=trace_on)
        _cache_put(key, fn)
        return fn

    def train(self, cfg, opt, data: Iterable, tcfg=None, *, state=None,
              on_metrics: Optional[Callable] = None):
        """Run the training loop; returns ``(final_state, history)``.

        ``tcfg`` is a :class:`repro.train.trainer.TrainerConfig` (steps,
        logging, checkpointing); the sketch policy, execution environment and
        budget schedule all come from this Runtime.
        """
        from repro.train import trainer

        return trainer.train_loop(self, cfg, opt, data, tcfg, state=state,
                                  on_metrics=on_metrics)

    def init_state(self, key, cfg, opt):
        from repro.train.train_step import init_state

        # policy/execution let plan-carry estimators ("onepass"/"stale")
        # seed their permanent per-site score leaves (core/plan_state.py)
        return init_state(key, cfg, opt, self.policy,
                          execution=self.execution)

    # -- serving ------------------------------------------------------------

    def prefill_step(self, cfg, max_len: int) -> Callable:
        """``prefill_fn(params, batch) -> (logits, caches)`` (unjitted)."""
        from repro.serve.serve_step import make_prefill

        return make_prefill(cfg, max_len, execution=self.execution)

    def decode_step(self, cfg) -> Callable:
        """``decode_fn(params, caches, tokens, pos) -> (logits, caches)``
        (unjitted)."""
        from repro.serve.serve_step import make_decode_step

        return make_decode_step(cfg, execution=self.execution)

    def serve(self, params, cfg, *, serve=None, batch: int = 4,
              max_len: int = 256):
        """A continuous-batching :class:`~repro.serve.engine.Engine` whose
        prefill/decode steps run under this runtime's execution config.

        ``serve`` is a :class:`~repro.serve.config.ServeConfig` (slot count,
        KV budget, paged-cache geometry, prefill buckets/packing, stop
        tokens); the ``batch``/``max_len`` kwargs are the legacy spelling and
        build one. See docs/serving.md.
        """
        from repro.serve.engine import Engine

        return Engine(params, cfg, serve=serve, batch=batch, max_len=max_len,
                      runtime=self)

    # -- migration ----------------------------------------------------------

    @classmethod
    def from_legacy_kwargs(cls, policy=None, *, mesh=None, act_sharding=None,
                           data_axes=("data",), model_axes=("model",),
                           tp_sketch: bool = False, compact_grads: bool = False,
                           accum: int = 1, cost_mode: bool = False,
                           straggler_budgets: Tuple[float, ...] = (),
                           schedule: Optional[BudgetSchedule] = None) -> "Runtime":
        """Adapter for the pre-Runtime kwarg spelling (see docs/api.md for
        the migration table). ``straggler_budgets`` maps onto a reactive
        :class:`BudgetSchedule` exactly like the old trainer buckets."""
        if schedule is None:
            schedule = (BudgetSchedule.straggler(tuple(straggler_budgets))
                        if straggler_budgets else BudgetSchedule())
        return cls(policy=policy,
                   execution=ExecutionConfig(
                       mesh=mesh, act_sharding=act_sharding,
                       data_axes=tuple(data_axes), model_axes=tuple(model_axes),
                       tp_sketch=tp_sketch, compact_grads=compact_grads,
                       accum=accum, cost_mode=cost_mode),
                   schedule=schedule)
