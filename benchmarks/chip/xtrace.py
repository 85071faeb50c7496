"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time as the union of op intervals,
the traced window, time by kernel name, and idle gaps labelled by the host
span open in them."""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil

# host spans the idle gaps are labelled by, innermost first when nested
HOST_SPANS = ("train_step", "build_buckets", "train_loop")
_TPU = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    ops: dict          # device index -> [(name, start_ns, end_ns)] sorted by start
    spans: list        # host spans [(name, start_ns, end_ns)] sorted by start
    window: tuple      # (start_ns, end_ns) of the traced window
    kernels: list = dataclasses.field(default_factory=list)
    # [(name, HLO text, start_ns, end_ns)] of the Pallas kernels' calls

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, dev: int) -> list:
        """The union of op intervals on device ``dev``, clipped to the window."""
        lo, hi = self.window
        out = []
        for _, s, e in sorted(self.ops.get(dev, []), key=lambda o: o[1]):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def busy_s(self) -> float:
        """Busy seconds averaged over the traced devices."""
        if not self.ops:
            return 0.0
        tot = [sum(e - s for s, e in self.busy_intervals(d)) for d in self.ops]
        return sum(tot) / len(tot) * 1e-9

    def kernel_calls(self) -> list:
        """(name, HLO text, seconds) of the kernel calls in the window."""
        lo, hi = self.window
        return [(n, text, (e - s) * 1e-9) for n, text, s, e in self.kernels
                if s >= lo and e <= hi]

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def op_seconds(self, match) -> float:
        """Device seconds of ops whose name ``match`` accepts, averaged over
        devices."""
        if not self.ops:
            return 0.0
        tot = [sum(e - s for n, s, e in ops if match(n)) for ops in self.ops.values()]
        return sum(tot) / len(tot) * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` ops of device 0 (by index) with the most time in the
        window, control-flow containers left out."""
        agg = {}
        lo, hi = self.window
        for n, s, e in self.ops.get(min(self.ops), []) if self.ops else []:
            if not _CONTAINER.match(n) and min(e, hi) > max(s, lo):
                agg[n] = agg.get(n, 0) + min(e, hi) - max(s, lo)
        return [[n, t * 1e-9] for n, t in sorted(agg.items(), key=lambda x: -x[1])[:k]]

    def label_at(self, t: float) -> str:
        """The innermost listed host span open at time ``t``."""
        best = None
        for name, s, e in self.spans:
            if s <= t < e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0] if best else "outside spans"

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest idle gaps of device 0 (by index), labelled."""
        if not self.ops:
            return []
        busy = self.busy_intervals(min(self.ops))
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.label_at((s + e) / 2), (e - s) * 1e-9] for s, e in gaps[:k]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _op_name(event) -> str:
    """The HLO instruction's name: TPU traces name an op by its whole HLO
    text, ``%name = shape opcode(...)``; Pallas kernels keep their own name
    (``%col_l1_scores.84 = ...``)."""
    return event.name.split(" = ", 1)[0].lstrip("%")


# the Pallas sketch kernels, by the names their calls keep in the trace
KERNEL_NAMES = ("block_gather_matmul_fused", "block_stream_matmul_fused",
                "block_gather_matmul_dx", "block_gather_matmul_dw", "col_l1_scores")

# control-flow ops span the ops of their bodies; they count for busy time
# but are not ops of their own in the breakdown
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def from_profile(pd, chips: int) -> Trace:
    ops, spans, kernels = {}, [], []
    for plane in pd.planes:
        m = _TPU.match(plane.name)
        if m and int(m.group(1)) < chips:
            lines = {l.name: l for l in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            dev = int(m.group(1))
            ops[dev] = sorted(
                ((_op_name(e), e.start_ns, e.start_ns + e.duration_ns) for e in line.events),
                key=lambda o: o[1])
            if dev == 0:
                kernels = [(_op_name(e), e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events if _op_name(e).startswith(KERNEL_NAMES)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    spans.sort(key=lambda s: s[1])
    loops = [s for s in spans if s[0] == "train_loop"]
    if loops:
        window = (loops[0][1], loops[-1][2])
    else:
        starts = [o[1] for v in ops.values() for o in v]
        ends = [o[2] for v in ops.values() for o in v]
        window = (min(starts), max(ends)) if starts else (0, 0)
    return Trace(ops=ops, spans=spans, window=window, kernels=kernels)


def load(trace_dir: str, chips: int) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]), chips)


def remove(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def kernel_match(names):
    return lambda op: any(n in op for n in names)
