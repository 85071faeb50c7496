"""Least time over measured time of the sketch Pallas kernels' calls in
the traced window: for each call, the larger of its FLOPs over the chip's
bf16 peak and its HBM bytes over HBM bandwidth (``cost.kernel_work``, from
the shapes and memory spaces in the call's HLO), summed, over the calls'
summed device time."""
from benchmarks.chip import bench, cost


def read(r):
    pk = bench.peaks(r.device_kind)
    least = spent = 0.0
    for name, hlo, seconds in r.trace.kernel_calls():
        work = cost.kernel_work(name, hlo)
        if work is None:
            continue
        flops, bytes_ = work
        least += max(flops / pk["bf16_flops_per_s"], bytes_ / pk["hbm_bytes_per_s"])
        spent += seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
