"""rule_grads_ms.train: device time of the passes only the selection rule
needs (the program's ``step.rule_grads`` scope: the forward+backward at the
stale parameters, and the probe pair when the rule probes) per traced step,
mean over the cell's chips, in ms."""
from harness import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "step.rule_grads")
