"""Device time of the ops the program scopes ``head`` (final norm, LM
head, cross-entropy) over device busy time."""
from benchmarks.chip import layers


def read(r):
    return layers.layer_share(r, "head")
