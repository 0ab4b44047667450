"""unscoped_ms.train: device time of the traced operations under none of
the step's four scopes (copies the compiler inserts and the like) per
traced step, mean over the cell's chips, in ms: the guard on the scopes'
coverage. With the four scope metrics it partitions the traced steps'
operation time (loops and calls left out)."""
from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, scopes.UNSCOPED)
