"""Rule ``dsize-collective``: data-moving collectives belong to the comm seam.

The paper's bit savings live or die on what crosses the wire, and
``BENCH_pipeline.json`` showed d-sized collectives slipping onto the hot
path unnoticed (ring traffic ~15x the compressed upload). The structural
fix: every collective that moves *data* (``psum``/``pmean``/``all_gather``/
``ppermute``/``psum_scatter``/``all_to_all`` on arrays) must live inside
``repro/comm/`` — the ``Transport`` seam that owns layout, collectives, and
the bit counters — so nothing can cross the wire unaccounted.

Exempt:
- metadata queries (``axis_index``/``axis_size``) — no payload;
- collectives whose operand is a numeric literal (``psum(1, axis)`` is the
  idiomatic static axis-size query);
- ``repro/comm/`` itself.

Known-accepted sites (the GPipe activation ring in ``dist/pipeline.py`` —
activation traffic by construction, classified and itemized by the HLO
audit's ``ring_collectives``) are recorded in ``analysis/baseline.json``
with justifications. The stage GRADIENT exchange no longer appears here:
it goes through the ``repro.comm`` Transport (the k-sized payload gather on
the hot path, ``stage_combine_leaf`` on the dense fallback).
"""
from __future__ import annotations

from typing import List

from repro.analysis.findings import Finding

from ._common import (
    AXIS_QUERIES,
    ScopedVisitor,
    collective_name,
    is_numeric_literal,
)

EXEMPT_PATHS = ("repro/comm/", "repro/analysis/")


class _Visitor(ScopedVisitor):
    def __init__(self, ctx):
        super().__init__()
        self.ctx = ctx
        self.findings: List[Finding] = []

    def visit_Call(self, node):  # noqa: N802
        name = collective_name(node)
        if (name is not None and name not in AXIS_QUERIES
                and node.args and not is_numeric_literal(node.args[0])):
            self.findings.append(self.ctx.finding(
                "dsize-collective", node, self.qualname,
                f"data-moving collective lax.{name} outside the repro.comm "
                "Transport seam; route it through Transport (or record it "
                "in analysis/baseline.json with a justification) so the "
                "bit counters see it",
            ))
        self.generic_visit(node)


def check_dsize_collectives(ctx) -> List[Finding]:
    if any(ctx.path.startswith(p) for p in EXEMPT_PATHS):
        return []
    v = _Visitor(ctx)
    v.visit(ctx.tree)
    return v.findings
