"""Execution configuration: where and how compiled steps run.

Everything that used to ride as seven loose kwargs on ``train()`` /
``make_train_step`` (mesh, activation sharding, axis names, TP-local
sketching, compact gradients, gradient accumulation) lives in one frozen,
hashable object. ``ExecutionConfig`` is the *only* sanctioned factory for
``nn.common.Ctx`` outside the nn substrate itself — ``tests/test_compat.py``
greps for stray ``Ctx(...)`` construction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

__all__ = ["ExecutionConfig"]


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """Static execution environment of one Runtime (hashable; safe to key
    jit caches on).

    Attributes:
      mesh: ``jax.sharding.Mesh`` for distributed runs (None = single device).
      act_sharding: NamedSharding constraint pinned on [B, S, d] activations.
      data_axes / model_axes: mesh axis names carrying DP and TP/EP shards.
      tp_sketch: TP-local compact sketching with compressed DP gradient
        collectives — sites resolve onto the tp_column/tp_row execution
        plans of the one sketched-site spine (core/site.py; see
        :meth:`site_spec`).
      compact_grads: keep sketched dW compact (rows + indices) from the
        backward through clipping into sparse-row optimizer updates
        (core/compact_grad.py; requires ``accum == 1``).
      accum: gradient-accumulation microbatch count.
      cost_mode: python-unrolled loops for HLO cost artifacts (dry-run).
      telemetry: a :class:`repro.telemetry.TelemetryConfig` enabling the
        in-graph probes (per-site VJP-variance estimates emitted as a side
        output of the train step) and naming optional sinks; ``None`` (the
        default) disables telemetry entirely. See docs/telemetry.md.
      resilience: a :class:`repro.resilience.ResilienceConfig` enabling the
        fault-handling plumbing: the compiled step takes a traced
        ``fault_scale`` operand (fault injection without recompiles) and,
        with ``sentinel=True``, gates the optimizer update on an in-graph
        non-finite/norm-explosion flag — bit-identical training when the
        sentinel never trips. ``None`` (the default) compiles the plain
        three-argument step. See docs/resilience.md.
      obs: a :class:`repro.obs.ObsConfig` enabling execution observability:
        wall-clock spans on the train/serve/recovery hot paths, the unified
        metrics registry, compile/memory ledgers on steps built through
        ``Runtime.train_step``, and the flight recorder's crash bundles.
        Purely host-side — the compiled computation is untouched, so
        training stays bit-identical with obs on or off. ``None`` (the
        default) disables it entirely (null tracer, zero allocation on the
        step path). See docs/observability.md.
      fused_vmem_limit: VMEM budget (bytes) for the fused/streaming Pallas
        backward kernels' resident accumulators — above it the dispatch
        drops to the one-gather XLA fallback (``repro.kernels.ops``).
        ``None`` (the default) defers to the ``REPRO_FUSED_VMEM_LIMIT`` env
        var, then the built-in 16 MiB (the v5e compiler's default scoped-VMEM
        limit, which the estimates are calibrated against). Steps built from
        this config bind the value (and the obs metrics registry, which
        records every dispatch/fallback decision) via
        ``kernels.ops.configure``. See docs/perf.md.
    """

    mesh: Optional[Any] = None
    act_sharding: Optional[Any] = None
    data_axes: Tuple[str, ...] = ("data",)
    model_axes: Tuple[str, ...] = ("model",)
    tp_sketch: bool = False
    compact_grads: bool = False
    accum: int = 1
    cost_mode: bool = False
    telemetry: Optional[Any] = None  # repro.telemetry.TelemetryConfig
    resilience: Optional[Any] = None  # repro.resilience.ResilienceConfig
    obs: Optional[Any] = None  # repro.obs.ObsConfig
    fused_vmem_limit: Optional[int] = None  # bytes; kernels.ops.configure

    def __post_init__(self):
        object.__setattr__(self, "data_axes", tuple(self.data_axes))
        object.__setattr__(self, "model_axes", tuple(self.model_axes))
        if self.accum < 1:
            raise ValueError(f"accum must be >= 1, got {self.accum}")
        if self.compact_grads and self.accum != 1:
            raise ValueError("compact_grads requires accum == 1 (compact index "
                             "sets differ per microbatch; accumulate densely)")
        if (self.telemetry is not None and self.telemetry.probes
                and self.accum != 1):
            raise ValueError("telemetry probes require accum == 1 (probe slot "
                             "cotangents would silently average across "
                             "microbatch plans); use TelemetryConfig("
                             "probes=False) with accumulation")
        if self.resilience is not None and not hasattr(self.resilience,
                                                       "sentinel"):
            raise ValueError("resilience must be a repro.resilience."
                             f"ResilienceConfig, got {self.resilience!r}")
        if self.obs is not None and not hasattr(self.obs, "trace_capacity"):
            raise ValueError("obs must be a repro.obs.ObsConfig, got "
                             f"{self.obs!r}")
        if self.fused_vmem_limit is not None:
            if (not isinstance(self.fused_vmem_limit, int)
                    or self.fused_vmem_limit <= 0):
                raise ValueError("fused_vmem_limit must be a positive int "
                                 f"(bytes), got {self.fused_vmem_limit!r}")

    def site_spec(self, role: str, cfg, *, d_out: int, d_in: int,
                  has_bias: bool = False, x_ndim: int = 3):
        """Resolve one sketched-linear site against this execution
        environment to its declarative :class:`~repro.core.site.SiteSpec`
        (local / tp_column / tp_row plan, slot ranks, probe capability).
        This is the same memoized resolution ``nn.common.dense`` and the
        gslot/pslot builders consume — the one dispatch decision per site.
        """
        from repro.core.site import resolve_site

        return resolve_site(role, cfg, d_out=d_out, d_in=d_in,
                            has_bias=has_bias, x_ndim=x_ndim, mesh=self.mesh,
                            data_axes=self.data_axes,
                            model_axes=self.model_axes,
                            tp_sketch=self.tp_sketch)

    def make_ctx(self, *, policy=None, key=None, decode: bool = False,
                 cost_mode: Optional[bool] = None, layer_index: int = 0,
                 n_layers: int = 1):
        """Build the per-call :class:`~repro.nn.common.Ctx` this config
        describes (the one front door to Ctx outside ``repro/nn``)."""
        from repro.nn.common import Ctx

        return Ctx(policy=policy, key=key, layer_index=layer_index,
                   n_layers=n_layers, mesh=self.mesh,
                   model_axes=self.model_axes, data_axes=self.data_axes,
                   cost_mode=self.cost_mode if cost_mode is None else cost_mode,
                   decode=decode, act_sharding=self.act_sharding,
                   tp_sketch=self.tp_sketch)

    def replace(self, **kw) -> "ExecutionConfig":
        return dataclasses.replace(self, **kw)
