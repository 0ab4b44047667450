"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into plain events:
for each device, the operations it ran (its "XLA Ops" line); for the
host, the benchmark's own spans (``TraceAnnotation``). All times are nanoseconds on the trace's
clock. The functions below only do interval arithmetic on those events,
so ``tests/test_trace.py`` checks them on a small recorded trace.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
# operations that only contain others (a loop's body runs as its own events)
CONTAINER = re.compile(r"^(while|conditional|call)$")


@dataclass
class Ev:
    name: str
    start: int          # ns
    dur: int            # ns
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> int:
        return self.start + self.dur

    def text(self) -> str:
        """Name and stat values, for matching by pattern."""
        return " ".join([self.name] + [str(v) for v in self.stats.values()])

    @property
    def short(self) -> str:
        """The HLO instruction's name: ``fusion.12`` of ``%fusion.12 = ...``."""
        m = re.match(r"%?([^\s=]+)", self.name)
        return m.group(1) if m else self.name

    @property
    def opcode(self) -> str:
        """The HLO opcode of an ``%x = <shape> <opcode>(...)`` event name
        (the shape may be a tuple), or "" when the name has no such form."""
        i = self.name.find(" = ")
        if i < 0:
            return ""
        rest = self.name[i + 3:]
        if rest.startswith("("):
            depth = 0
            for j, ch in enumerate(rest):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    rest = rest[j + 1:]
                    break
        else:
            rest = rest.split(" ", 1)[1] if " " in rest else ""
        m = re.match(r"\s*([a-z][\w-]*)\(", rest)
        return m.group(1) if m else ""


@dataclass
class Trace:
    ops: dict               # device index -> [Ev], sorted by start
    host: list              # benchmark spans [Ev]
    t0: int = 0             # trace clock at the window's start
    t1: int = 0             # ... and at its end

    @property
    def window_ns(self) -> int:
        return self.t1 - self.t0


def _stats(obj) -> dict:
    try:
        return {str(k): v for k, v in obj.stats}
    except Exception:  # stats a reader cannot decode are left out
        return {}


_DEV = re.compile(r"/device:TPU:(\d+)$")


def load(trace_dir: str, host_prefix: str = "bench.") -> Trace:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, host = {}, []
    for plane in pd.planes:
        m = _DEV.search(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = sorted((Ev(e.name, round(e.start_ns), round(e.duration_ns), _stats(e))
                                       for e in line.events), key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append(Ev(e.name, round(e.start_ns), round(e.duration_ns)))
    host.sort(key=lambda e: e.start)
    return Trace(ops, host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[tuple]) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: Iterable, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def length(intervals: Iterable) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(evs: list, lo: Optional[int] = None, hi: Optional[int] = None) -> list:
    """Merged intervals in which the device ran some operation."""
    iv = [(e.start, e.end) for e in evs]
    if lo is not None:
        iv = clip(iv, lo, hi)
    return union(iv)


def busy_ns(tr: Trace, dev: int) -> int:
    return length(busy(tr.ops.get(dev, []), tr.t0, tr.t1))


def mean_busy_s(tr: Trace) -> float:
    devs = sorted(tr.ops)
    return sum(busy_ns(tr, d) for d in devs) / max(len(devs), 1) / 1e9


def matching(evs: list, pattern: str) -> list:
    rx = re.compile(pattern)
    return [e for e in evs if rx.search(e.text())]


def op_ns(evs: list, pattern: str) -> int:
    """Summed device time of the operations whose name or stats match."""
    return sum(e.dur for e in matching(evs, pattern))


def is_collective(e: Ev) -> bool:
    return bool(COLLECTIVE.match(e.opcode))


def exposed_ns(evs: list, lo: Optional[int] = None, hi: Optional[int] = None) -> int:
    """Device time in collective operations during which no other
    operation runs on that device (loops and calls, which only contain
    other operations, do not count as running)."""
    coll = [e for e in evs if is_collective(e)]
    other = [e for e in evs if not is_collective(e) and not CONTAINER.match(e.opcode)]
    return length(subtract(busy(coll, lo, hi), busy(other, lo, hi)))


def idle_gaps(tr: Trace, dev: int = 0, top: int = 10) -> list:
    """The longest stretches of the window with no operation on ``dev``,
    each named by the innermost host span at its midpoint (or "no span")."""
    b = busy(tr.ops.get(dev, []), tr.t0, tr.t1)
    gaps = subtract([[tr.t0, tr.t1]], b)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        inside = [h for h in tr.host if h.start <= mid < h.end]
        name = min(inside, key=lambda h: h.dur).name if inside else "no span"
        out.append([name, (e - s) / 1e9])
    return out


def top_ops(tr: Trace, dev: int = 0, top: int = 10, key: Callable[[Ev], str] = None) -> list:
    """Device operations that took most time in the window, grouped by
    ``key`` (the instruction's name with any ``.N`` suffix dropped); loops
    and calls are left out, their bodies' operations are counted."""
    key = key or (lambda e: re.sub(r"\.\d+$", "", e.short))
    acc: dict = {}
    for e in tr.ops.get(dev, []):
        if e.end <= tr.t0 or e.start >= tr.t1 or CONTAINER.match(e.opcode):
            continue
        k = key(e)
        acc[k] = acc.get(k, 0) + e.dur
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def clip_events(evs: list, lo: int, hi: int) -> list:
    """Events that start inside [lo, hi)."""
    return [e for e in evs if lo <= e.start < hi]
