"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (``trace_fixture.json``: the events of a few milliseconds of
one training step, as ``harness.trace.load`` read them), checked against a
brute-force count over every nanosecond.

  python -m pytest benchmarks/chip/tests/test_trace.py
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import tiny  # noqa: F401  (puts the harness on the path)
from harness import trace as T

FIXTURE = Path(__file__).with_name("trace_fixture.json")


def _ev(name, s, e):
    return T.Ev(name, s, e - s)


def _op(name, opcode, s, e):
    """An event named as the TPU trace names them: the HLO instruction."""
    return _ev(f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", s, e)


def _hand_trace():
    ops = [_op("fusion.1", "fusion", 0, 10), _op("fusion.2", "fusion", 5, 15),
           _op("all-gather.3", "all-gather", 10, 25),
           _op("topk_ef_kernel", "custom-call", 20, 30), _op("fusion.1", "fusion", 32, 35),
           _op("while.4", "while", 0, 35)]
    host = [_ev("bench.window", 0, 40), _ev("bench.train.metrics_sync", 14, 40)]
    return T.Trace({0: ops}, host, t0=0, t1=40)


def test_hand_counts():
    tr = _hand_trace()
    ops = tr.ops[0]
    # busy: the loop spans [0, 35)
    assert T.busy(ops, 0, 40) == [[0, 35]]
    assert T.busy_ns(tr, 0) == 35
    assert T.mean_busy_s(tr) == 35e-9
    tr.ops[0] = ops = ops[:-1]
    # without the loop: [0, 30) and [32, 35), 33 of 40 ns
    assert T.busy(ops, 0, 40) == [[0, 30], [32, 35]]
    assert T.busy_ns(tr, 0) == 33
    # kernel time by name
    assert T.op_ns(ops, "topk_ef") == 10
    assert T.op_ns(ops, r"^%fusion\.1 ") == 13
    # the all-gather runs alone from 15 to 20 only; a loop around it is
    # not an operation that hides it
    assert T.exposed_ns(ops, lo=0, hi=40) == 5
    assert T.exposed_ns(ops + [_op("while.4", "while", 0, 35)], lo=0, hi=40) == 5
    # idle: [30, 32) and [35, 40), both inside metrics_sync (the innermost span)
    assert T.idle_gaps(tr) == [["bench.train.metrics_sync", 5e-9],
                               ["bench.train.metrics_sync", 2e-9]]
    # grouped by name without the .N suffix
    assert T.top_ops(tr) == [["fusion", 23e-9], ["all-gather", 15e-9],
                             ["topk_ef_kernel", 10e-9]]


def test_window_clips():
    tr = _hand_trace()
    tr.ops[0] = tr.ops[0][:-1]
    tr.t0, tr.t1 = 8, 22
    assert T.busy_ns(tr, 0) == 14
    assert T.exposed_ns(tr.ops[0], lo=8, hi=22) == 5
    assert T.clip_events(tr.ops[0], 8, 22) == [tr.ops[0][2], tr.ops[0][3]]


def _brute(ops, t0, t1, pattern):
    """Every nanosecond of [t0, t1): busy, collective-alone, and the
    summed time of matching events."""
    n = t1 - t0
    busy = np.zeros(n, bool)
    coll = np.zeros(n, bool)
    other = np.zeros(n, bool)
    for e in ops:
        s, f = max(e.start, t0) - t0, min(e.end, t1) - t0
        if f <= s:
            continue
        busy[s:f] = True
        if T.is_collective(e):
            coll[s:f] = True
        elif not T.CONTAINER.match(e.opcode):
            other[s:f] = True
    matched = sum(e.dur for e in ops if pattern in e.text())
    return int(busy.sum()), int((coll & ~other).sum()), matched


def test_recorded_trace_against_brute_force():
    data = json.loads(FIXTURE.read_text())
    for dev, evs in data["ops"].items():
        ops = [T.Ev(e["name"], round(e["start"]), round(e["dur"])) for e in evs]
        t0, t1 = round(data["t0"]), round(data["t1"])
        busy, exposed, kernel = _brute(ops, t0, t1, data["kernel"])
        tr = T.Trace({0: ops}, [], t0=t0, t1=t1)
        assert T.busy_ns(tr, 0) == busy
        assert T.exposed_ns(ops, lo=t0, hi=t1) == exposed
        assert T.op_ns(ops, data["kernel"]) == kernel
        assert busy > 0
