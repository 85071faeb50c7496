"""Host milliseconds per step inside the trainer's ``train_step`` span
(the obs tracer's span around one step's dispatch)."""


def read(r):
    if not r.host_spans:
        return None
    return 1e3 * sum(s.duration_s for s in r.host_spans) / len(r.host_spans)
