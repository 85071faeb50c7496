"""Device time of the ops the program scopes ``sketch`` (every site's
sketched VJP: column scores, plan, the gathered matmuls by Pallas kernel or
XLA fallback, and the dW scatter) over device busy time."""
from benchmarks.chip import layers


def read(r):
    return layers.layer_share(r, "sketch")
