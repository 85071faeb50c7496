"""Share of the traced window in which device 0 runs no op while the
trainer's ``train_fetch`` span (a device->host fetch) is open: the part of
``device_idle_share`` the host's fetches leave idle."""
from benchmarks.chip import layers


def read(r):
    fetches = layers.to_trace_clock(r, layers.window_spans("train_fetch"))
    win = r.trace.window_s()
    if not fetches or win <= 0:
        return None
    return 100.0 * layers.overlap_ns(layers.idle_intervals(r.trace), fetches) * 1e-9 / win
