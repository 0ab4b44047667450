"""apply_ms.train: device time of the update (the program's ``step.apply``
scope: optimizer, parameter update, the rule's window, counters, metrics)
per traced step, mean over the cell's chips, in ms."""
from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "step.apply")
