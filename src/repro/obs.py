"""Observability: device scopes and host spans on the profiler's clock.

``scope(name)`` names the device side: the operations traced inside carry
``name`` in their HLO ``op_name`` metadata (``jax.named_scope``), which costs
nothing at run time. The training step uses ``step.grad``,
``step.rule_grads``, ``step.exchange`` (children ``rule``, ``encode``,
``collective``, ``commit``) and ``step.apply``.

``Spans`` names the host side of one component (the Trainer keeps one):
``span(name, **meta)`` is a ``TraceAnnotation`` (a no-op outside a profiler
session; ``StepTraceAnnotation`` when ``step_num`` is given) and a ``Span``
record on ``time.perf_counter_ns`` in ``records``, which keeps the last
4096. While ``gc_spans()`` is entered, Python's garbage collections are
recorded as ``host.gc`` spans.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time
from typing import NamedTuple, Optional

import jax

MAX_SPANS = 4096


class Span(NamedTuple):
    name: str
    step: Optional[int]
    t0_ns: int
    t1_ns: int
    parent: Optional[str]


def scope(name: str):
    """A device scope around the operations traced inside it."""
    return jax.named_scope(name)


class Spans:
    """Host spans, recorded and annotated for the profiler."""

    def __init__(self):
        self.records: collections.deque = collections.deque(maxlen=MAX_SPANS)
        self._local = threading.local()

    def _open(self) -> list:
        """The spans open on this thread, innermost last: (name, step)."""
        if not hasattr(self._local, "open"):
            self._local.open = []
        return self._local.open

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        """A host span; ``step_num``, else the enclosing span's, is its step."""
        stack = self._open()
        parent = stack[-1] if stack else (None, None)
        step = meta.get("step_num", parent[1])
        ann = (jax.profiler.StepTraceAnnotation(name, **meta) if "step_num" in meta
               else jax.profiler.TraceAnnotation(name, **meta))
        stack.append((name, step))
        t0 = time.perf_counter_ns()
        try:
            with ann:
                yield
        finally:
            self.records.append(Span(name, step, t0, time.perf_counter_ns(), parent[0]))
            stack.pop()

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = jax.profiler.TraceAnnotation("host.gc")
            ann.__enter__()
            self._local.gc = (time.perf_counter_ns(), ann)
        elif getattr(self._local, "gc", None) is not None:
            (t0, ann), self._local.gc = self._local.gc, None
            ann.__exit__(None, None, None)
            stack = self._open()
            parent = stack[-1] if stack else (None, None)
            self.records.append(Span("host.gc", parent[1], t0, time.perf_counter_ns(),
                                     parent[0]))

    @contextlib.contextmanager
    def gc_spans(self):
        """Record every garbage collection as a ``host.gc`` span while entered."""
        gc.callbacks.append(self._on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(self._on_gc)
