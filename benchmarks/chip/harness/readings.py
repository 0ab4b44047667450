"""What the per-layer metric readers share: the traced window put on the
host's clock."""
from __future__ import annotations


def traced_seconds(ctx) -> float:
    """Host seconds from the window's ``bench.mark`` to the trace's stop."""
    w = ctx["window"]
    return (w["trace_end_ns"] - w["mark_ns"]) / 1e9
