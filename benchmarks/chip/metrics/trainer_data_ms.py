"""Host milliseconds per window step inside the trainer's ``train_data``
span (its ``next`` of the batch iterator), from the program's tracer."""
from benchmarks.chip import layers


def read(r):
    spans = layers.window_spans("train_data")
    if not spans:
        return None
    return 1e3 * sum(s.duration_s for s in spans) / len(spans)
