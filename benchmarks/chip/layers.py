"""Device time by the program's layers, and the trainer's own host spans,
for the per-layer metrics that read them.

The program names each layer of its train step with a device scope
(``repro.obs.scopes``) and, with tracing on, records for every step
executable it builds a table: HLO instruction name -> (layer, scope path).
The profiler trace names each device op by its HLO instruction, so the
join is by op name. The trace's reduction (``xtrace.Trace``) keeps no
module per op: the join runs over the tables of every step executable the
process built, which is exact while one step executable runs in the window
(one budget bucket, as in every cell here). An op of another program whose
name is also an instruction of the step is counted with the step.

The trainer's ``train_data`` and ``train_fetch`` spans are read from the
program's tracer (host clock); the ``train_step`` spans that both the tracer
and the profiler's host plane hold put them on the trace's clock.

A program without these (one built before the scopes) has no table and no
such spans: every reader then returns None.
"""
from __future__ import annotations

import bisect
import re
import statistics

from benchmarks.chip import xtrace


def _traced_obs() -> list:
    try:
        from repro import obs
    except ImportError:
        return []
    shared = getattr(obs, "shared", None)
    if shared is None:
        return []
    return [ob for ob in shared() if ob.tracer.enabled]


def op_tables() -> dict:
    """{HLO module: {instruction: (layer, scope path)}} of the traced program."""
    out = {}
    for ob in _traced_obs():
        out.update(ob.op_layers())
    return out


def layer_seconds(trace, tables: dict) -> dict:
    """Device seconds of the window's ops by layer, control-flow containers
    left out, averaged over devices. Key None: ops no table names a layer
    for, or that no table holds."""
    index = {}
    for table in tables.values():
        for name, (layer, _) in table.items():
            index.setdefault(name, layer)
    lo, hi = trace.window
    out = {}
    for ops in trace.ops.values():
        for name, s, e in ops:
            if xtrace._CONTAINER.match(name) or min(e, hi) <= max(s, lo):
                continue
            layer = index.get(name)
            out[layer] = out.get(layer, 0) + min(e, hi) - max(s, lo)
    n = max(len(trace.ops), 1)
    return {k: v / n * 1e-9 for k, v in out.items()}


def layer_share(r, layer):
    """Percent of device busy time in ``layer`` (None: in no layer)."""
    tables = op_tables()
    busy = r.trace.busy_s()
    if not tables or busy <= 0:
        return None
    return 100.0 * layer_seconds(r.trace, tables).get(layer, 0.0) / busy


def program_spans(name: str) -> list:
    """The program tracer's completed spans called ``name``, oldest first."""
    out = []
    for ob in _traced_obs():
        out += ob.tracer.spans(name)
    return sorted(out, key=lambda s: s.t0)


def window_spans(name: str) -> list:
    """The tracer's ``name`` spans inside its last ``train_loop`` span (the
    traced window's ``Runtime.train`` call)."""
    loops = program_spans("train_loop")
    if not loops:
        return []
    lo, hi = loops[-1].t0, loops[-1].t1
    return [s for s in program_spans(name) if s.t0 >= lo and s.t1 <= hi]


def to_trace_clock(r, spans: list) -> list:
    """[(start_ns, end_ns)] of tracer spans on the profiler trace's clock,
    by the median offset between the window's ``train_step`` spans as the
    tracer and as the trace's host plane hold them."""
    traced = sorted(s[1] for s in r.trace.spans if s[0] == "train_step")
    own = window_spans("train_step")
    if not traced or len(traced) != len(own):
        return []
    off = statistics.median(t - s.t0 * 1e9 for t, s in zip(traced, own))
    return [(s.t0 * 1e9 + off, s.t1 * 1e9 + off) for s in spans]


def idle_intervals(trace) -> list:
    """[(start, end)] of the window in which device 0 (by index) runs no op."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    edges = [lo] + [x for iv in trace.busy_intervals(min(trace.ops)) for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two lists of intervals (each list's
    intervals disjoint)."""
    tot = 0.0
    for s, e in a:
        for s2, e2 in b:
            tot += max(0.0, min(e, e2) - max(s, s2))
    return tot


_FINGERPRINT = re.compile(r"\(\d+\)$")


def module_ops(pd, dev: int = 0) -> list:
    """[(module, op name, start_ns, end_ns)] of the ``XLA Ops`` of TPU
    ``dev`` in a profile (``jax.profiler.ProfileData``), each op given the
    HLO module whose run on the ``XLA Modules`` line holds its start: the
    (module, op name) key of the program's op tables."""
    lines = {}
    for plane in pd.planes:
        if plane.name == f"/device:TPU:{dev}":
            lines = {l.name: l for l in plane.lines}
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns, _FINGERPRINT.sub("", e.name))
                  for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ()))
    starts = [r[0] for r in runs]
    out = []
    for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        module = runs[i][2] if i >= 0 and e.start_ns < runs[i][1] else None
        out.append((module, xtrace._op_name(e), e.start_ns, e.start_ns + e.duration_ns))
    return out
