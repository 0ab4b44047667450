"""Training cells: the program's SASG step driven through its Trainer, as
``launch/train.py`` wires it (choose_strategy -> build_train_step ->
Trainer), compared after the window with the plain reference.

Set-up builds one Trainer over one compiled step, makes the state from the
seed in one jitted call, and drives the first ``SETUP_STEPS`` steps through
the same Trainer and feed the window uses. It reads the program's side of
the comparison from the state as those steps leave it: the first update,
error buffers and parameters' change after step 1; the parameters' change
after the last set-up step and over it alone (a skip in every run read so
far: the path that applies the cached payload again); the selection rule's
window of update norms; each step's loss and uploads. The window then runs
whole steps until ``--seconds`` have passed.

After the window the plain reference runs the same steps from the same
weights and rows, uploading where the program did (``sasg_ref``), and at
the last set-up step evaluates the exact rule, which the program's
decision there is compared with.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C
from . import sasg_ref, traffic, weights

SETUP_STEPS = 6


def program_config(cell):
    from repro.configs import get_config

    p = cell.config["program"]
    base = get_config(p["arch"])
    kw = dict(p["fields"])
    if "ssm" in p:
        kw["ssm"] = dataclasses.replace(base.ssm, **p["ssm"])
    return dataclasses.replace(base, **kw)


def check_layout(program_shapes, ref_shapes) -> None:
    """The program's parameters have the layout the reference reads."""
    got = {k: (tuple(v.shape), str(v.dtype)) for k, v in weights.leaf_paths(program_shapes).items()}
    want = {k: (tuple(v.shape), str(v.dtype)) for k, v in weights.leaf_paths(ref_shapes).items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise C.BenchError(f"program parameter layout differs from the reference's: {diff[:6]}")


class Feed:
    """Hands out successive prebuilt batches, whatever step the Trainer
    asks for: every step of a run gets rows of its own."""

    def __init__(self, batches):
        self.batches = batches
        self.i = 0

    def batch_at(self, step):
        b = self.batches[self.i % len(self.batches)]
        self.i += 1
        return b


def _payload_leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: hasattr(x, "blocked_shape"))
    return {weights.path_str(p): x for p, x in flat}


def _densify_mean(values, indices, blocked, shape):
    """Mean over workers (leading axis) of the block payloads, dense."""
    m = values.shape[0]
    bc = blocked[-1]
    v = values.reshape(-1, values.shape[-1]).astype(jnp.float32)
    i = indices.reshape(-1, indices.shape[-1]).astype(jnp.int32)
    rows = jax.vmap(lambda vv, ii: jnp.zeros((bc,), jnp.float32).at[ii].add(vv))(v, i)
    return rows.reshape((m,) + tuple(blocked)).mean(0).reshape(shape)


def first_step_norms(state) -> tuple:
    """Leaf norms of the update the first step applied (the workers' cached
    payloads, averaged) and of each worker's error buffer."""
    pay = _payload_leaves(state.wstate.stale_cache)
    ef = weights.leaf_paths(state.wstate.comp_state)

    def f(vals, idxs, efs):
        up = {k: jnp.linalg.norm(_densify_mean(vals[k], idxs[k], pay[k].blocked_shape,
                                               pay[k].orig_shape).ravel())
              for k in vals}
        en = {k: jax.vmap(lambda x: jnp.linalg.norm(x.ravel()))(x) for k, x in efs.items()}
        return up, en

    up, en = jax.jit(f)({k: p.values for k, p in pay.items()},
                        {k: p.indices for k, p in pay.items()}, ef)
    up, en = jax.device_get((up, en))
    workers = len(next(iter(en.values())))
    return ({k: float(v) for k, v in up.items()},
            [{k: float(v[m]) for k, v in en.items()} for m in range(workers)])


class Program:
    """The system under test for one training cell."""

    def __init__(self, cell, devs, spans: C.Spans):
        from repro.compat import make_mesh
        from repro.core import PRESETS
        from repro.core.types import tree_bytes
        from repro.dist.strategy import choose_strategy
        from repro.models import build
        from repro.optim import constant
        from repro.train import Trainer, TrainerConfig, build_train_step

        t = cell.traffic
        self.cell, self.spans = cell, spans
        self.cfg = program_config(cell)
        self.model = build(self.cfg, remat=t["remat"])
        self.workers = int(t["workers"])
        self.mesh = make_mesh((self.workers, 1), ("data", "model"), devices=list(devs))
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        check_layout(shapes, cell.reference.param_shapes(cell.config))
        self.strategy = choose_strategy(
            self.mesh, sasg_enabled=True, params_bytes=tree_bytes(shapes),
            trunk_layers=self.model.pipeline.n_layers if self.model.pipeline else 0)
        if self.strategy.name != t["strategy"] or self.strategy.num_workers != self.workers:
            raise C.BenchError(f"strategy {self.strategy} is not {t['strategy']} x {self.workers}")
        self.scfg = PRESETS[t["algo"]](k_ratio=t["k_ratio"], max_delay=t["max_delay"])
        comp = self.scfg.compressor
        if (comp.resolved_impl(), comp.resolved_layout(), comp.block_size) != (
                t["topk_impl"], t["layout"], t["block"]):
            raise C.BenchError(f"preset compressor {comp} is not the cell's")
        self.built = build_train_step(self.model, self.scfg, self.mesh, self.strategy,
                                      constant(t["lr"]))
        self.shapes = shapes
        self.deadline = None
        self.stop_trace_at = None
        self.t_last = None
        self._sync = None

        prog = self

        class WindowTrainer(Trainer):
            def _fetch_batch(self, step):
                with prog.spans("bench.train.batch_fetch"):
                    return super()._fetch_batch(step)

            def _maybe_ckpt(self, state, step, force=False):
                prog._end_sync()
                if not force:
                    prog.t_last = time.perf_counter()
                    if prog.stop_trace_at is not None and len(self.history) >= prog.stop_trace_at:
                        prog.stop_trace()
                    if prog.deadline is not None and prog.t_last >= prog.deadline:
                        self.cfg.total_steps = step
                return super()._maybe_ckpt(state, step, force)

        jit_step = self.built.jit_step

        def dispatch(state, batch, *rest):
            with prog.spans("bench.train.step_dispatch"):
                out = jit_step(state, batch, *rest)
            prog._begin_sync()
            return out

        self.trainer = WindowTrainer(
            self.built._replace(jit_step=dispatch), None,
            TrainerConfig(total_steps=1, max_restarts=0, log_every=10 ** 9),
            log_fn=lambda s: None)
        self.trace_stop = None
        self.trace_end_ns = None

    # the span from a step's dispatch to its metrics' arrival on the host
    def _begin_sync(self):
        self._sync = (time.perf_counter_ns(), None)
        if self.spans.on:
            ann = jax.profiler.TraceAnnotation("bench.train.metrics_sync")
            ann.__enter__()
            self._sync = (self._sync[0], ann)

    def _end_sync(self):
        if self._sync is None:
            return
        t0, ann = self._sync
        if ann is not None:
            ann.__exit__(None, None, None)
        self.spans.records.append(("bench.train.metrics_sync", t0, time.perf_counter_ns()))
        self._sync = None

    def stop_trace(self):
        if self.trace_stop is not None:
            self.trace_end_ns = time.perf_counter_ns()
            self.trace_stop()
            self.trace_stop = None

    def make_state(self, key):
        """Weights from the seed and the SASG state around them, in one
        jitted call, placed as the step expects."""
        from repro.core.types import CommCounters
        from repro.train.step import TrainState

        ex, M = self.built.exchange, self.workers
        ref, cfgj = self.cell.reference, self.cell.config

        def mk(key):
            params = jax.tree_util.tree_map_with_path(
                lambda p, s: weights.draw(key, weights.path_str(p), s.shape, s.dtype,
                                          ref.init_rule(weights.path_str(p), s.shape, cfgj)),
                self.shapes)
            ws = ex.init_worker(params)
            wstate = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (M,) + x.shape), ws)
            return TrainState(params, (), wstate, ex.init_global(), CommCounters.zeros(), key)

        return jax.jit(mk, out_shardings=self.built.state_shardings)(key)

    def steps(self, state, n: int):
        self.trainer.cfg.total_steps = n
        return self.trainer.run(state=state)


def trace_mark(on: bool) -> int:
    """A host event that ties the profiler's clock to perf_counter_ns."""
    t = time.perf_counter_ns()
    if on:
        with jax.profiler.TraceAnnotation("bench.mark"):
            pass
    return t


def topk_bytes(prog: Program, t: dict) -> float:
    from .flops import topk_ef_bytes

    shapes = [s.shape for s in jax.tree.leaves(prog.shapes)]
    return topk_ef_bytes(shapes, t["k_ratio"], t["block"])


def _diff_norms(a, b) -> dict:
    """Leaf norms of a - b, two trees of one layout (device or host
    arrays), in float32."""
    f = jax.jit(lambda a, b: {k: jnp.linalg.norm((a[k].astype(jnp.float32)
                                                  - b[k].astype(jnp.float32)).ravel())
                              for k in a})
    return {k: float(v) for k, v in
            jax.device_get(f(weights.leaf_paths(a), weights.leaf_paths(b))).items()}


def readings_program(prog: Program, state) -> tuple:
    """The set-up steps and the program's side of the comparison: the
    first update, error buffers and parameters' change from the state
    after step 1; the parameters' change after the last set-up step and
    over it alone; the rule's window; each step's loss and uploads."""
    hist = prog.trainer.history
    init = jax.device_get(state.params)
    state = prog.steps(state, 1)
    upd, ef = first_step_norms(state)
    change1 = _diff_norms(state.params, init)
    for _ in range(SETUP_STEPS - 2):
        state = prog.steps(state, 1)
    before = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(state.params)
    state = prog.steps(state, 1)
    last = _diff_norms(state.params, before)
    del before
    change = _diff_norms(state.params, init)
    window = [float(x) for x in jax.device_get(state.gstate.window)[:SETUP_STEPS]][::-1]
    read = {"loss": [h["loss"] for h in hist[:SETUP_STEPS]],
            "sent": [int(round(h["num_sent"])) for h in hist[:SETUP_STEPS]],
            "update_norms": upd, "ef_norms": ef, "change1_norms": change1,
            "change_norms": change, "last_norms": last, "window": window}
    return state, read


def reference_readings(cell, seed: int, prec: str = "f32", half_batch: bool = False,
                       batches=None, forced=None) -> dict:
    """The plain reference's set-up steps from the seed's weights and rows,
    uploading as ``forced`` says where given (see ``sasg_ref``).
    ``prec="fp8"`` is the control; ``half_batch`` plants the fault that
    leaves out half of each worker's rows."""
    t, ref, cfgj = cell.traffic, cell.reference, cell.config
    key = C.jax_key(seed, "weights")
    params = weights.make(ref.param_shapes(cfgj), key, ref.init_rule, cfgj)
    if batches is None:
        batches = batch_list(cell, seed, SETUP_STEPS)
    if half_batch:
        M = int(t["workers"])
        rows = t["rows_per_worker"]
        keep = np.concatenate([np.arange(m * rows, m * rows + rows // 2) for m in range(M)])
        batches = [{k: np.asarray(v)[keep] for k, v in b.items()} for b in batches]

    def row_grad(p, tok, lab):
        pf = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return jax.value_and_grad(ref.row_loss)(pf, tok, lab, cfgj, prec)

    M = int(t["workers"])
    r = sasg_ref.Reference(row_grad, M, t["lr"], t["k_ratio"], t["block"],
                           t["max_delay"], t["alpha_scale"],
                           devices=jax.devices()[:M] if M > 1 else None)
    out = r.run(params, batches, SETUP_STEPS, forced=forced)
    out["change1_norms"] = _diff_norms(out.pop("params1"), params)
    out["last_norms"] = _diff_norms(out["params_last"], out.pop("params_before_last"))
    out["change_norms"] = _diff_norms(out.pop("params_last"), params)

    def named(tree):
        return {weights.path_str(p): float(v)
                for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}

    out["update_norms"] = named(out["update_norms"])
    out["ef_norms"] = [named(e) for e in out["ef_norms"]]
    out["grad_norms"] = named(out["grad_norms"])
    return out


def compare(prog: dict, ref: dict) -> dict:
    """The numbers of the comparison (PERF.md says which of them decide
    ``correct`` and how each limit was set): the worst relative loss gap
    over the steps the reference took a gradient at; the worst leaf's and
    the median leaf's gap of the first update's norm, of each worker's
    first error buffer's norm, of the parameters' change after step 1,
    after the last set-up step (``change``) and over it alone
    (``skip_change``); the worst relative gap of the rule's window of
    update norms; and the workers whose decision at the last set-up step
    differs from the exact rule's. Leaves whose reference gradient is
    under a thousandth of the median leaf's are left out of the leaf
    gaps."""
    gn = ref["grad_norms"]
    med = float(np.median(list(gn.values())))
    keep = {k for k, v in gn.items() if v >= 1e-3 * med}
    out, worst = {}, {}
    out["loss_gap"] = max(abs(a - b) / abs(b)
                          for a, b in zip(prog["loss"], ref["loss"]) if b is not None)
    pairs = {"update": (prog["update_norms"], ref["update_norms"]),
             "change1": (prog["change1_norms"], ref["change1_norms"]),
             "change": (prog["change_norms"], ref["change_norms"]),
             "skip_change": (prog["last_norms"], ref["last_norms"])}
    for name, (p, r) in pairs.items():
        gap, worst[name], median = sasg_ref.leaf_gap(p, r, keep)
        out[f"{name}_gap"], out[f"{name}_median_gap"] = gap, median
    ef = [sasg_ref.leaf_gap(p, r, keep) for p, r in zip(prog["ef_norms"], ref["ef_norms"])]
    out["ef_gap"], worst["ef"], _ = max(ef)
    out["ef_median_gap"] = max(g[2] for g in ef)
    out["window_gap"] = max(abs(a - b) / max(abs(b), 1e-30)
                            for a, b in zip(prog["window"], ref["window"]))
    rule = ref["rule"][SETUP_STEPS - 1]
    out["rule_mismatch"] = float(abs(prog["sent"][-1] - rule["sent"]))
    out["excluded_leaves"] = float(len(gn) - len(keep))
    out["worst"] = worst
    return out


def batch_list(cell, seed: int, n: int) -> list:
    """``n`` global batches of the seed's bigram stream over the source's
    vocabulary."""
    t = cell.traffic
    rows = int(t["rows_per_worker"]) * int(t["workers"])
    return traffic.bigram_batches(int(cell.config["vocab_size"]), rows, int(t["seq"]), seed,
                                  n, t["bigram_order"])


def run(cell, args, devs, peaks, counter: C.CompileCounter, trace_dir=None) -> C.Result:
    t = cell.traffic
    res = C.Result()
    spans = C.Spans(on=bool(args.trace))
    t_setup = time.perf_counter()
    prog = Program(cell, devs, spans)
    key = C.jax_key(args.seed, "weights")
    steps_max = SETUP_STEPS + int(args.seconds / t["min_step_s"]) + 2
    host_batches = batch_list(cell, args.seed, steps_max)
    shard = prog.built.batch_sharding_fn(host_batches[0])
    prog.trainer.data = Feed([jax.device_put(b, shard) for b in host_batches])
    C.progress(f"step built, {steps_max} batches on the device")
    state = prog.make_state(key)
    C.progress("state made from the seed")
    state, read = readings_program(prog, state)
    setup_s = time.perf_counter() - t_setup
    C.progress(f"{SETUP_STEPS} set-up steps read")
    n0, h0, _ = counter.snapshot()

    # the window: whole steps until --seconds have passed
    tokens_per_step = int(t["rows_per_worker"]) * prog.workers * int(t["seq"])
    if args.trace:
        jax.profiler.start_trace(trace_dir)
        prog.trace_stop = jax.profiler.stop_trace
        prog.stop_trace_at = SETUP_STEPS + int(t["trace_steps"])
    t0 = time.perf_counter()
    mark = trace_mark(spans.on)
    prog.deadline = t0 + args.seconds
    state = prog.steps(state, 10 ** 9)
    window_s = prog.t_last - t0
    prog.stop_trace()
    steps = len(prog.trainer.history) - SETUP_STEPS
    n1, h1, _ = counter.snapshot()
    mem = C.memory_peak_bytes(devs)
    hist = prog.trainer.history
    res.notes.update({
        "setup_s": setup_s, "window_s": window_s, "window_steps": steps,
        "compiles_in_window": n1 - n0, "cache_loads_in_window": h1 - h0,
        "sends": [int(h["num_sent"]) for h in hist],
        "losses": [round(h["loss"], 5) for h in hist],
        "rounds_total": hist[-1]["rounds_total"], "bits_wire_total": hist[-1]["bits_wire_total"],
        "program_loss": read["loss"], "program_sent": read["sent"],
    })
    res.attempted = steps
    res.failed = 0 if steps > 0 else 1
    res.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": mem}
    res.window = {"steps": steps, "seconds": window_s, "tokens_per_step": tokens_per_step,
                  "mark_ns": mark, "trace_end_ns": prog.trace_end_ns,
                  "traced_steps": int(t["trace_steps"]), "spans": spans.records,
                  "flops_per_token": cell.reference.train_flops_per_token(cell.config),
                  "topk_ef_bytes": topk_bytes(prog, t)}
    if not args.trace:
        res.metrics = {
            "train_tokens_per_s": C.metric(steps * tokens_per_step / window_s, "tokens/s"),
            "setup_s": C.metric(setup_s, "s"),
        }

    # the comparison, once the program's state is gone
    host_setup = host_batches[:SETUP_STEPS]
    del state, prog
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, args.seed, batches=host_setup, forced=read["sent"])
    nums = compare(read, ref)
    res.notes["reference_s"] = time.perf_counter() - t_ref
    res.notes["reference_loss"] = ref["loss"]
    res.notes["reference_rule"] = ref["rule"]
    res.notes["program_window"] = read["window"]
    res.notes["reference_window"] = ref["window"]
    res.notes["excluded_leaves"] = nums.pop("excluded_leaves")
    res.notes["worst_leaves"] = nums.pop("worst")
    for name, value in nums.items():
        if name in cell.limits:
            res.checks.append(C.Check(name, float(value), float(cell.limits[name])))
        else:
            res.notes[name] = value
    return res
