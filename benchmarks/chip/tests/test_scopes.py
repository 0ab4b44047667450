"""The step's scopes and the program's spans as ``harness/scopes.py`` reads
them: the scope partition and the unspanned idle time on hand-made events,
the op_name inheritance on a hand-made module, and the HLO protos of a
trace recorded here (CPU) against the compiled module's own text.

  python -m pytest benchmarks/chip/tests/test_scopes.py
"""
from __future__ import annotations

import re

import tiny  # noqa: F401  (puts the harness on the path)
from harness import scopes as S
from harness import trace as T


def _op(name, opcode, s, e):
    """An event named as the TPU trace names them: the HLO instruction."""
    return T.Ev(f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", s, e - s)


def _scoped_ctx():
    """Two chips, two traced steps; each operation named by its instruction,
    whose op_name the step module's map gives. Chip 0 also runs one other
    module, whose ``fusion.1`` is not the step's."""
    step = {"fusion.1": "jit(step)/step.grad/jvp(loss)/mul",
            "fusion.2": "jit(step)/step.rule_grads/transpose(jvp(loss))/dot",
            "topk_ef.3": "jit(step)/step.exchange/encode/topk_ef/pallas_call",
            "copy.4": "jit(step)/step.exchange/commit/select_n",
            "fusion.5": "jit(step)/step.apply/add",
            "copy.6": "",
            "while.7": "jit(step)/step.grad/while",
            "fusion.8": "jit(step)/step.grad.extra/not_a_scope"}
    other = {"fusion.1": "jit(convert)/convert"}
    ops0 = [_op("fusion.1", "fusion", 0, 10), _op("fusion.2", "fusion", 10, 18),
            _op("topk_ef.3", "custom-call", 20, 23), _op("copy.4", "copy", 23, 25),
            _op("fusion.5", "fusion", 25, 26), _op("copy.6", "copy", 30, 34),
            _op("while.7", "while", 0, 18), _op("fusion.8", "fusion", 36, 37),
            _op("fusion.1", "fusion", 38, 39),            # the other module's
            _op("fusion.1", "fusion", 50, 60)]            # after the window
    ops1 = [_op("fusion.1", "fusion", 0, 12), _op("fusion.5", "fusion", 12, 14)]
    tr = T.Trace({0: ops0, 1: ops1}, [], t0=0, t1=40)
    runs = {0: [(0, 37, "jit_step(1)"), (38, 39, "jit_convert(2)"), (50, 60, "jit_step(1)")],
            1: [(0, 14, "jit_step(1)")]}
    spans = [T.Ev("train.dispatch", -5, 6), T.Ev("train.metrics_sync", 26, 3),
             T.Ev("host.gc", 33, 3)]
    prog = {"modules": {"jit_step(1)": step, "jit_convert(2)": other}, "runs": runs,
            "spans": spans}
    return {"trace": tr, "window": {"traced_steps": 2}, "program": prog}


def test_scopes_partition_operation_time():
    ctx = _scoped_ctx()
    ns = S.scope_ns(ctx)
    # chip 0: grad 10, rule_grads 8, exchange 3 + 2, apply 1, unscoped
    # 4 + 1 + 1 (``step.grad.extra`` is no scope; the other module's
    # fusion.1); chip 1: grad 12, apply 2; the loop and the operation after
    # the window are left out
    assert ns == {"step.grad": 11.0, "step.rule_grads": 4.0, "step.exchange": 2.5,
                  "step.apply": 1.5, "unscoped": 3.0}
    tr = ctx["trace"]
    total = sum(e.dur for evs in tr.ops.values() for e in evs
                if e.end > tr.t0 and e.start < tr.t1 and not T.CONTAINER.match(e.opcode))
    assert sum(ns.values()) * len(tr.ops) == total
    assert S.scope_ms_per_step(ctx, "step.grad") == 11.0 / 1e6 / 2
    assert S.scope_of("jit(step)/step.exchange/rule/step.grad") == "step.exchange"


def test_scopes_without_module_runs_use_every_module():
    ctx = _scoped_ctx()
    ctx["program"]["runs"] = {}
    # the other module's fusion.1 now reads as the step's, the module with
    # the most scoped instructions
    ns = S.scope_ns(ctx)
    assert ns["step.grad"] == 11.5 and ns["unscoped"] == 2.5


def test_scopes_read_nothing_without_scopes():
    ctx = _scoped_ctx()
    mods = ctx["program"]["modules"]
    mods["jit_step(1)"] = {k: "jit(step)/mul" for k in mods["jit_step(1)"]}
    assert S.scope_ms_per_step(ctx, "step.grad") is None
    ctx = _scoped_ctx()
    ctx["program"]["modules"] = {}
    assert S.scope_ms_per_step(ctx, "unscoped") is None


def test_idle_unspanned():
    ctx = _scoped_ctx()
    tr, spans = ctx["trace"], ctx["program"]["spans"]
    # chip 0 idles over [18, 20), [26, 30), [34, 36), [37, 38) and [39, 40):
    # the spans cover [26, 29) and [34, 36), leaving 2 + 1 + 1 + 1 = 5;
    # chip 1 idles over [14, 40): 26 less 3 + 3 = 20
    assert S.idle_unspanned_ns(tr, spans) == (5 + 20) / 2


def test_op_names_inherit():
    # (name, computation, opcode, op_name, operands, called)
    instrs = [
        ("param_0", "fused", "parameter", "", [], []),
        ("neg.1", "fused", "negate", "jit(step)/step.apply/neg", ["param_0"], []),
        ("arg", "body", "parameter", "", [], []),
        ("gte.1", "body", "get-tuple-element", "", ["arg"], []),
        ("reduce-window.2", "body", "reduce-window", "", ["gte.1"], ["add"]),
        ("mul.3", "body", "multiply", "jit(step)/step.grad/mul", ["reduce-window.2"], []),
        ("p.1", "main", "parameter", "state.params", [], []),
        ("while.5", "main", "while", "jit(step)/step.grad/while", ["p.1"], ["cond", "body"]),
        ("gte.6", "main", "get-tuple-element", "", ["while.5"], []),
        ("copy.7", "main", "copy", "", ["gte.6"], []),
        ("copy.8", "main", "copy", "", ["p.1"], []),
        ("broadcast.9", "main", "broadcast", "", [], []),
        ("fusion.11", "main", "fusion", "jit(step)/step.apply/neg", ["copy.7"], ["fused"]),
    ]
    names = S.op_names(instrs)
    # own op_name; the loop's for its body's unnamed instructions; the
    # first named operand's for an unnamed copy; none for a copy of a
    # parameter or an operand-less broadcast
    assert names["mul.3"] == "jit(step)/step.grad/mul"
    assert names["reduce-window.2"] == "jit(step)/step.grad/while"
    assert names["copy.7"] == "jit(step)/step.grad/while"
    assert names["fusion.11"] == "jit(step)/step.apply/neg"
    assert S.scope_of(names["copy.8"]) == S.scope_of(names["broadcast.9"]) == S.UNSCOPED
    # a fusion's body does not run as events of its own: not the fusion's
    assert names["param_0"] == ""
    assert names["neg.1"] == "jit(step)/step.apply/neg"


def test_hlo_protos_of_a_recorded_trace(tmp_path):
    """The op_names the trace's HLO protos give are the compiled text's,
    instruction by instruction, for a module compiled before the trace."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("step.grad"):
            y = jnp.sin(x) @ x
        with jax.named_scope("step.apply"):
            return jax.lax.fori_loop(0, 3, lambda i, z: z * 2.0 + y, y)

    fn = jax.jit(f)
    x = jnp.ones((16, 16))
    text = fn.lower(x).compile().as_text()
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("train.step"):
        fn(x).block_until_ready()
    jax.profiler.stop_trace()
    prog = S.load_program(str(tmp_path))
    module = next(v for k, v in prog["modules"].items() if k.startswith("jit_f("))
    own = dict(re.findall(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*op_name="([^"]*)"', text,
                          flags=re.M))
    assert own and all(module[k] == v for k, v in own.items())
    assert {"step.grad", "step.apply"} <= {S.scope_of(v) for v in own.values()}
    assert [e.name for e in prog["spans"]] == ["train.step"]
