"""Device time of the ops the program scopes ``attn`` (attention's
projections' exact parts, RoPE, scores and softmax, forward and backward)
over device busy time; ops joined to the program's op->layer table."""
from benchmarks.chip import layers


def read(r):
    return layers.layer_share(r, "attn")
