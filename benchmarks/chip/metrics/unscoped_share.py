"""Device time of the ops that name no layer of the program's scopes, or
that no op table of the program holds, over device busy time."""
from benchmarks.chip import layers


def read(r):
    return layers.layer_share(r, None)
