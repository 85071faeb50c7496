"""Device time of the sketch Pallas kernels (found by kernel name in the
trace) over device busy time."""
from benchmarks.chip import xtrace

KERNELS = ("block_gather_matmul", "block_stream_matmul", "col_l1_scores")


def read(r):
    busy = r.trace.busy_s()
    t = r.trace.op_seconds(xtrace.kernel_match(KERNELS))
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
