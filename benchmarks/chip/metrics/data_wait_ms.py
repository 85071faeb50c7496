"""Host milliseconds per step spent making the step's batch (the
benchmark's token stream, timed around each ``next`` of the feed)."""


def read(r):
    if not r.data_waits:
        return None
    return 1e3 * sum(r.data_waits) / len(r.data_waits)
