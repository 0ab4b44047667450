"""idle_unspanned_ms.train: time in the traced window during which a chip
runs no operation and no host span of the Trainer (``repro.obs.Spans``,
``train.*`` and ``host.gc`` in the trace) is open, per traced step, mean
over the cell's chips, in ms: device idle that the program's spans cannot
name."""
from harness import scopes


def read(ctx):
    tr, spans = ctx["trace"], scopes.program(ctx)["spans"]
    if not spans or not tr.ops:
        return None
    return scopes.idle_unspanned_ns(tr, spans) / 1e6 / ctx["window"]["traced_steps"]
