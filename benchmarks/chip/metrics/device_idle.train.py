"""device_idle.train: share of the traced window in which a chip runs no
operation (1 - the union of its operations' intervals over the window),
averaged over the cell's chips, in %."""
from harness import trace as T


def read(ctx):
    tr = ctx["trace"]
    if not tr.ops:
        return None
    return 100.0 * (1.0 - T.mean_busy_s(tr) * 1e9 / tr.window_ns)
