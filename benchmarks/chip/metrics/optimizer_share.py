"""Device time of the ops the program scopes ``optim`` (global-norm clip,
AdamW, plan-state write-back) over device busy time."""
from benchmarks.chip import layers


def read(r):
    return layers.layer_share(r, "optim")
