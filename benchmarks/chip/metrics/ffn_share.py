"""Device time of the ops the program scopes ``ffn`` (the dense MLP; the
MoE router, dispatch and experts) over device busy time."""
from benchmarks.chip import layers


def read(r):
    return layers.layer_share(r, "ffn")
