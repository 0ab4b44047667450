"""What the scope and span metrics share: each device operation's step scope
and the program's own host spans, both read from the traced run's
``.xplane.pb``.

The program names the phases of its training step with ``jax.named_scope``
(``repro.obs.scope``): ``step.grad``, ``step.rule_grads``, ``step.exchange``
and ``step.apply``. The name reaches each compiled instruction's ``op_name``
metadata, which the trace keeps in the compiled modules' HLO protos (the
``Hlo Proto`` stats of the ``/host:metadata`` plane); the operation events
themselves carry none. An operation belongs to the first of the four found
in its op_name; one under none of them is ``unscoped``. Loops,
conditionals and calls are left out, as ``trace.top_ops`` leaves them out,
so the five sums partition the traced operations' time.

The Trainer's host spans (``repro.obs.Spans``) are ``TraceAnnotation``s
named ``train.*`` and ``host.gc``, on the trace's clock already. A program
that has neither scopes nor spans gives nothing to read, and every reader
here then returns None.
"""
from __future__ import annotations

import bisect
import glob
import re
from typing import Iterator, Optional

from harness import common as C
from harness import trace as T

SCOPES = ("step.grad", "step.rule_grads", "step.exchange", "step.apply")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("train.", "host.")
_SCOPE_RX = re.compile("|".join(re.escape(s) + r"(?=/|$)" for s in SCOPES))
# the operations that run a computation as events of their own
_RUNNERS = ("while", "conditional", "call")


def scope_of(op_name: str) -> str:
    """The first step scope named in ``op_name``, or ``unscoped``."""
    m = _SCOPE_RX.search(op_name or "")
    return m.group(0) if m else UNSCOPED


def op_names(instrs: list) -> dict:
    """Instruction name -> op_name, for every instruction of a compiled
    module, given as ``(name, computation, opcode, op_name, operands,
    called)`` tuples (operands by instruction name, called computations by
    name). An instruction the compiler made often carries no op_name; it
    takes that of the loop, conditional or call that runs its computation,
    else that of its first operand that has one (the value it copies,
    slices or rearranges), else none."""
    comp, own, operands, runner = {}, {}, {}, {}
    for name, cname, opcode, on, ops, called in instrs:
        comp[name], own[name], operands[name] = cname, on, ops
        if opcode in _RUNNERS:
            for c in called:
                runner.setdefault(c, name)
    out = {}
    for name in own:
        chain = []
        while name is not None and name not in out and name not in chain:
            chain.append(name)
            if own[name]:
                out[name] = own[name]
                break
            up = runner.get(comp[name])
            if up is None:
                up = next((o for o in operands[name] if o in own), None)
            name = up
        value = out.get(name, "") if name is not None else ""
        for n in chain:
            out.setdefault(n, value)
    return out


# ---------------------------------------------------------------------------
# the compiled modules in the trace (protobuf wire format, read by hand)
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[tuple]:
    """(field number, value) of a message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} not read")


def _ints(v) -> list:
    """A repeated integer field's value: packed (a memoryview) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _module_instrs(hlo_proto) -> list:
    """``op_names``'s tuples of an ``HloProto``'s module."""
    module = next((v for n, v in _fields(hlo_proto) if n == 1), memoryview(b""))
    comp_name, raw = {}, []
    for n, c in _fields(module):
        if n != 3:                              # HloModuleProto.computations
            continue
        cname, cid, ins = "", None, []
        for m, v in _fields(c):
            if m == 1:
                cname = _str(v)
            elif m == 5:
                cid = v
            elif m == 2:
                ins.append(v)
        comp_name[cid] = cname
        raw += [(cname, i) for i in ins]
    by_id, rows = {}, []
    for cname, ins in raw:
        name, opcode, on, iid, ops, called = "", "", "", None, [], []
        for n, v in _fields(ins):
            if n == 1:
                name = _str(v)
            elif n == 2:
                opcode = _str(v)
            elif n == 7:                        # OpMetadata.op_name
                on = next((_str(x) for m, x in _fields(v) if m == 2), "")
            elif n == 35:
                iid = v
            elif n == 36:
                ops += _ints(v)
            elif n == 38:
                called += _ints(v)
        by_id[iid] = name
        rows.append((name, cname, opcode, on, ops, called))
    return [(name, cname, opcode, on, [by_id[o] for o in ops if o in by_id],
             [comp_name.get(x, "") for x in called])
            for name, cname, opcode, on, ops, called in rows]


def hlo_modules(xspace: bytes) -> dict:
    """Module name (as the device's "XLA Modules" line names its runs) ->
    instruction name -> op_name, for every module whose HLO proto the
    trace's ``/host:metadata`` plane holds and this reader can read."""
    buf = memoryview(xspace)
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if next((_str(v) for n, v in fields if n == 2), "") != "/host:metadata":
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:                          # map<int64, XStatMetadata>
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _str(meta.get(2, b""))
        for n, v in fields:
            if n != 4:                          # map<int64, XEventMetadata>
                continue
            entry = dict(_fields(v))
            meta = list(_fields(entry.get(2, memoryview(b""))))
            name = next((_str(x) for m, x in meta if m == 2), "")
            for m, stat in meta:
                if m != 5:
                    continue
                s = dict(_fields(stat))
                if stat_names.get(s.get(1)) == "Hlo Proto" and 6 in s:
                    try:
                        out[name] = op_names(_module_instrs(s[6]))
                    except (ValueError, IndexError):  # a proto this reader cannot read
                        continue
    return out


# ---------------------------------------------------------------------------
# the readers' view of one traced run
# ---------------------------------------------------------------------------

def load_program(trace_dir: str) -> dict:
    """What the newest ``.xplane.pb`` under ``trace_dir`` holds of the
    program: ``modules`` (``hlo_modules``), ``runs`` (device index -> the
    sorted (start, end, module name) of its "XLA Modules" line) and
    ``spans`` (the program's host spans as ``trace.Ev``, sorted)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    runs, spans = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = T._DEV.search(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Modules":
                runs[int(m.group(1))] = sorted((round(e.start_ns), round(e.end_ns), e.name)
                                               for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                spans += [T.Ev(e.name, round(e.start_ns), round(e.duration_ns))
                          for e in line.events if e.name.startswith(SPAN_PREFIXES)]
    spans.sort(key=lambda e: e.start)
    return {"modules": hlo_modules(raw), "runs": runs, "spans": spans}


def program(ctx) -> dict:
    """``load_program`` of the run's trace (``run.py`` keeps it under
    ``.bench_trace/<cell>`` while the readers run), read once per run."""
    if "program" not in ctx:
        ctx["program"] = load_program(str(C.ROOT / ".bench_trace" / ctx["cell"].name))
    return ctx["program"]


def _names_at(prog: dict, dev: int, merged: dict):
    """A function from an operation event to its op_name: by the module
    whose run on ``dev`` holds the event, else by ``merged``."""
    runs = prog["runs"].get(dev, [])
    starts = [r[0] for r in runs]

    def name(e: T.Ev) -> str:
        i = bisect.bisect_right(starts, e.start) - 1
        names = merged
        if i >= 0 and e.start < runs[i][1]:
            names = prog["modules"].get(runs[i][2], merged)
        return names.get(e.short, "")
    return name


def scope_ns(ctx) -> Optional[dict]:
    """Summed device time of the traced window's operations by scope
    (``SCOPES`` and ``unscoped``), mean over the chips, in ns; None when no
    operation lies under any scope."""
    if "scope_ns" in ctx:
        return ctx["scope_ns"]
    tr, prog = ctx["trace"], program(ctx)
    # for an operation outside every known run: the module with the most
    # scoped instructions (the step) names it first
    merged = {}
    for names in sorted(prog["modules"].values(),
                        key=lambda m: -sum(scope_of(v) != UNSCOPED for v in m.values())):
        for k, v in names.items():
            merged.setdefault(k, v)
    out = None
    if tr.ops and merged:
        acc = dict.fromkeys(SCOPES + (UNSCOPED,), 0)
        for dev, evs in tr.ops.items():
            name = _names_at(prog, dev, merged)
            for e in evs:
                if e.end > tr.t0 and e.start < tr.t1 and not T.CONTAINER.match(e.opcode):
                    acc[scope_of(name(e))] += e.dur
        if any(acc[s] for s in SCOPES):
            out = {k: v / len(tr.ops) for k, v in acc.items()}
    ctx["scope_ns"] = out
    return out


def scope_ms_per_step(ctx, scope: str) -> Optional[float]:
    ns = scope_ns(ctx)
    if ns is None:
        return None
    return ns[scope] / 1e6 / ctx["window"]["traced_steps"]


def idle_unspanned_ns(tr: T.Trace, spans: list) -> float:
    """Device-idle time in the window during which no host span is open,
    mean over the chips, in ns."""
    covered = T.union((s.start, s.end) for s in spans)
    total = 0
    for dev in tr.ops:
        idle = T.subtract([[tr.t0, tr.t1]], T.busy(tr.ops[dev], tr.t0, tr.t1))
        total += T.length(T.subtract(T.union(idle), covered))
    return total / max(len(tr.ops), 1)
