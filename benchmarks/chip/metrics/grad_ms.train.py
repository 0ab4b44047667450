"""grad_ms.train: device time of the fresh forward+backward (the program's
``step.grad`` scope, remat recompute included) per traced step, mean over
the cell's chips, in ms."""
from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "step.grad")
