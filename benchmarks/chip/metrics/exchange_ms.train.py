"""exchange_ms.train: device time between the gradients and the update (the
program's ``step.exchange`` scope: the rule's decision, the fused top-k/EF
encode, the collective and densify, the commits of payload, error buffers
and stale parameters) per traced step, mean over the cell's chips, in ms."""
from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "step.exchange")
