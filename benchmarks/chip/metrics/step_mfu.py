"""The whole step's share of the chip's bf16 peak over the traced window:
the exact model's FLOPs of the window's steps (``cost.model_flops_per_token``)
over window seconds × chips × peak. It bounds the kernels' rooflines from
above: a kernel taken off the path leaves its roofline silent, not this."""
from benchmarks.chip import bench, cost


def read(r):
    win = r.trace.window_s()
    if win <= 0 or r.steps <= 0:
        return None
    tr = r.cell.traffic
    tokens = r.steps * tr["batch"] * tr["seq_len"]
    flops = tokens * cost.model_flops_per_token(r.cell.config, tr["seq_len"], r.cell.here)
    return 100.0 * flops / (win * r.cell.chips * bench.peaks(r.device_kind)["bf16_flops_per_s"])
