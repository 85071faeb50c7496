"""repro.obs — execution observability: spans, metrics, ledgers, flight data.

The *numerical* half of the paper's trade-off is instrumented by
``repro.telemetry`` (per-site variance probes); this package instruments the
*execution* half — where wall-clock, compile time and HBM actually go —
across training, serving and recovery:

* :mod:`repro.obs.tracing` — nestable wall-clock spans
  (``with tracer.span("decode_step", ...)``), Chrome-trace/Perfetto + JSONL
  export, per-request lifecycle reconstruction;
* :mod:`repro.obs.metrics` — one Counter/Gauge/Histogram registry behind
  the old ad-hoc counter dicts (``serve``/``resilience``), JSONL snapshots
  and Prometheus text exposition;
* :mod:`repro.obs.ledgers` — compile ledger (per-executable trace/compile
  time + step-cache hits) and memory ledger (``memory_analysis()`` + live
  ``device.memory_stats()`` where hardware has them);
* :mod:`repro.obs.flight` — bounded recent-history ring dumped as a crash
  bundle by the resilience Supervisor;
* :mod:`repro.obs.scopes` — the device scopes that name the train step's
  layers in the compiled program, and the op→layer table read from it
  (recorded per step executable when tracing is on: :meth:`Observability.op_layers`);
* :mod:`repro.obs.clock` — the one sanctioned wall-clock source
  (lint-enforced: ``time.perf_counter``/``time.time`` are forbidden in
  ``src/`` outside this package).

:class:`ObsConfig` below is the static, hashable switchboard riding on
:class:`repro.api.ExecutionConfig` (``ExecutionConfig.obs`` — the same
pattern as ``TelemetryConfig``). Because the config is hashable and
equal-by-value, :func:`observability` returns one shared mutable
:class:`Observability` per distinct config — the same keyed-state pattern as
the Runtime step cache — so a Runtime, its trainer, its serving engine and
its Supervisor all feed one tracer/registry/ledger set. ``None`` (the
default) yields the :data:`NULL_OBS` singleton: null tracer, no registries,
zero cost on hot paths. See docs/observability.md.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.obs import clock  # noqa: F401  (re-export: the sanctioned clock)
from repro.obs.flight import FlightRecorder
from repro.obs.ledgers import (CompileLedger, MemoryLedger,
                               GLOBAL_COMPILE_LEDGER, global_active)
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracing import NULL_TRACER, Tracer

__all__ = ["ObsConfig", "Observability", "observability", "shared", "NULL_OBS"]


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Static observability switchboard (frozen/hashable — safe on
    ExecutionConfig and therefore inside jit-cache keys).

    Attributes:
      trace: record wall-clock spans on the instrumented paths (trainer
        step/compile/checkpoint-wait, serve request lifecycle, recovery).
      metrics: route counters/gauges through the unified registry (the old
        dict spellings keep working either way — off just means each
        component gets a private registry nothing ever exports).
      compile_ledger / memory_ledger: record per-executable compile wall
        time + cache hits / ``memory_analysis()`` for steps built through
        ``Runtime.train_step`` (first call per executable runs AOT
        lower+compile so the phases can be timed separately).
      flight: keep the bounded recent-history ring and allow crash bundles.
      annotate: additionally open ``jax.profiler.TraceAnnotation`` per span
        (shows up in real profiler captures; off by default).
      trace_capacity / flight_capacity: ring sizes (completed spans /
        noted events).
      chrome_trace / trace_jsonl: optional export paths written by
        ``Observability.export()`` (the trainer and serving engine call it
        at loop end).
      crash_dir: directory for flight-recorder crash bundles; ``None``
        disables dumping (the ring still fills).
    """

    trace: bool = True
    metrics: bool = True
    compile_ledger: bool = True
    memory_ledger: bool = True
    flight: bool = True
    annotate: bool = False
    trace_capacity: int = 4096
    flight_capacity: int = 256
    chrome_trace: Optional[str] = None
    trace_jsonl: Optional[str] = None
    crash_dir: Optional[str] = None

    def __post_init__(self):
        if self.trace_capacity < 1:
            raise ValueError(f"trace_capacity must be >= 1, got "
                             f"{self.trace_capacity}")
        if self.flight_capacity < 1:
            raise ValueError(f"flight_capacity must be >= 1, got "
                             f"{self.flight_capacity}")


class Observability:
    """The mutable observability state for one :class:`ObsConfig`.

    Shared by every component constructed from an equal config (see
    :func:`observability`); ``NULL_OBS`` is the disabled singleton.
    """

    def __init__(self, cfg: Optional[ObsConfig]):
        self.cfg = cfg
        self.enabled = cfg is not None
        trace_on = self.enabled and cfg.trace
        self.tracer = (Tracer(cfg.trace_capacity, annotate=cfg.annotate)
                       if trace_on else NULL_TRACER)
        self.metrics = MetricsRegistry() if (self.enabled and cfg.metrics) else None
        self.compile_ledger = (CompileLedger()
                               if self.enabled and cfg.compile_ledger else None)
        self.memory_ledger = (MemoryLedger()
                              if self.enabled and cfg.memory_ledger else None)
        self.flight = (FlightRecorder(self.tracer if trace_on else None,
                                      self.metrics,
                                      capacity=cfg.flight_capacity)
                       if self.enabled and cfg.flight else None)
        # components: (name, registry) pairs adopted from multi-instance
        # subsystems (each serving engine owns its counters but registers
        # here so report()/prometheus() see them)
        self.components: List[Tuple[str, MetricsRegistry]] = []
        # HLO module name -> {instruction: (layer, scope path)}, one per step
        # executable built with tracing on (repro.obs.scopes)
        self._op_layers: Dict[str, Dict[str, Tuple[Optional[str], str]]] = {}

    # -- device scopes --------------------------------------------------------

    def record_op_layers(self, module: str, table: dict) -> None:
        # executables of one step function (budget buckets) share the HLO
        # module name: their tables merge, a later build's entry winning
        self._op_layers.setdefault(module, {}).update(table)

    def op_layers(self) -> Dict[str, Dict[str, Tuple[Optional[str], str]]]:
        """{HLO module name: {instruction name: (layer, scope path)}} for
        every step executable built while tracing was on — the join key of
        a profiler trace's device ops (module, op name) to the program's
        layers. Empty with tracing off."""
        return self._op_layers

    # -- component registries ----------------------------------------------

    def adopt(self, name: str, registry: MetricsRegistry) -> None:
        if self.enabled:
            self.components.append((name, registry))

    def _registries(self) -> List[Tuple[str, MetricsRegistry]]:
        regs: List[Tuple[str, MetricsRegistry]] = []
        if self.metrics is not None:
            regs.append(("", self.metrics))
        regs.extend(self.components)
        return regs

    def metrics_snapshot(self) -> dict:
        """Merged flat snapshot across the root registry and every adopted
        component registry (later duplicates get ``#<n>`` suffixes)."""
        out: Dict[str, object] = {}
        for _, reg in self._registries():
            for k, v in reg.snapshot().items():
                key, n = k, 1
                while key in out:
                    key = f"{k}#{n}"
                    n += 1
                out[key] = v
        return out

    def prometheus(self) -> str:
        return "".join(reg.to_prometheus() for _, reg in self._registries())

    # -- reporting ----------------------------------------------------------

    def report(self) -> dict:
        """One JSON-ready dict: compile hit/miss, per-step memory, metrics.
        (``Runtime.observability().report()`` is the documented read path.)"""
        if not self.enabled:
            return {"enabled": False}
        out: Dict[str, object] = {"enabled": True}
        if self.compile_ledger is not None:
            out["compile"] = self.compile_ledger.to_json()
        if self.memory_ledger is not None:
            out["memory"] = self.memory_ledger.to_json()
        out["metrics"] = self.metrics_snapshot()
        out["n_spans"] = len(self.tracer.spans())
        if self._op_layers:
            out["op_layers"] = self._op_layers
        return out

    def export(self) -> List[str]:
        """Write the configured trace exports; returns the paths written."""
        paths = []
        if self.enabled and self.tracer.enabled:
            if self.cfg.chrome_trace:
                paths.append(self.tracer.export_chrome(self.cfg.chrome_trace))
            if self.cfg.trace_jsonl:
                paths.append(self.tracer.export_jsonl(self.cfg.trace_jsonl))
        return paths

    def dump_crash(self, reason: str, extra: Optional[dict] = None) -> Optional[str]:
        """Flight-recorder crash bundle (None when flight recording or
        ``crash_dir`` is off — callers need no guards)."""
        if self.flight is None or not self.cfg.crash_dir:
            return None
        return self.flight.dump(self.cfg.crash_dir, reason, extra)


NULL_OBS = Observability(None)

# One shared Observability per distinct ObsConfig — same keyed-state idiom
# as the Runtime step cache (module-level so equal configs share state).
_OBS: Dict[ObsConfig, Observability] = {}


def observability(cfg: Optional[ObsConfig]) -> Observability:
    """The shared :class:`Observability` for ``cfg`` (``NULL_OBS`` for None)."""
    if cfg is None:
        return NULL_OBS
    ob = _OBS.get(cfg)
    if ob is None:
        ob = _OBS[cfg] = Observability(cfg)
    return ob


def shared() -> List[Observability]:
    """Every shared :class:`Observability` this process has made — how a
    reader outside the program (a profiler-trace reduction) finds the op
    tables and host spans of the run it traced."""
    return list(_OBS.values())


def _reset() -> None:  # test hook
    _OBS.clear()
