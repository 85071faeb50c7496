"""Share of the traced window in which no operation ran on the device
(1 - union of device op intervals / window), averaged over the chips."""


def read(r):
    if not r.trace.ops or r.trace.window_s() <= 0:
        return None
    return 100.0 * r.trace.idle_share()
