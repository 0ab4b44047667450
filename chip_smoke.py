#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # one four-chip host: the multi-worker phase only

One chip:
  train   mamba2_370m at its published config, SASG preset (fused Pallas
          top-k/error-feedback kernel, per-shard layout), through
          choose_strategy -> build_train_step -> Trainer as launch/train.py
          wires them, on a (1, 1) data x model mesh;
  kernel  the fused kernel against the unfused reference on seeded
          real-width mamba2_370m leaves, on the chip;
  serve   starcoder2_3b at its published config through build_serve +
          BatchedServer with the paged KV cache.
Four chips: flat SASG with 4 workers on a (4, 1) mesh beside plain
data-parallel SGD on the same chips, with the same seed and batches.

Weights and data are random, made from --seed. Everything runs in this one
process, the only one that touches JAX. The readings printed on the way are
smoke readings, not benchmark results. The last line of stdout is one JSON
object, {"ok": true, "device": {...}}, printed only when every check passed
on a TPU; with no TPU, or any failed check, the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import PRESETS  # noqa: E402
from repro.core.compressors import CompressorConfig, make_topk_ef  # noqa: E402
from repro.core.types import tree_bytes, tree_flatten_with_paths  # noqa: E402
from repro.data import indexed_token_stream  # noqa: E402
from repro.dist.strategy import choose_strategy  # noqa: E402
from repro.launch import runtime  # noqa: E402
from repro.models import build  # noqa: E402
from repro.optim import constant  # noqa: E402
from repro.serve import BatchedServer, Request, build_serve  # noqa: E402
from repro.train import Trainer, TrainerConfig, build_train_step  # noqa: E402

TRAIN_ARCH = "mamba2_370m"
SERVE_ARCH = "starcoder2_3b"
SEQ_LEN = 2048
# per-worker batch and remat policy, from the AOT memory analysis of the
# whole jitted SASG step for one v5e (CHANGES.md)
BATCH = 12
REMAT = "full"
STEPS = 8
STEPS_4CHIP = 2
LR = 0.01
K_RATIO = 0.01
# real-width leaves for the kernel-vs-reference check: block width 256
# (kb 3) and the odd width 120 of lm_head's blocked view (kb 2)
KERNEL_LEAVES = ("unit/0/ssd/w_out", "lm_head")
SERVE_BATCH = 4
SERVE_REQUESTS = 8
PROMPT_LEN = (128, 512)
NEW_TOKENS = 32
PAGE = 16  # KV block size
# step-0 loss of flat SASG against plain data parallelism: same init, same
# global batch, so the same number up to float error. The two are different
# programs (a manual shard_map region against auto-SPMD) running a bf16
# forward, so fusion and reduction order differ; 1e-3 relative is a few
# bf16 roundings (2^-8 each) averaged over 4 x BATCH x SEQ_LEN tokens.
LOSS_RTOL = 1e-3
# counters are float32 accumulators: rounds stay exact small integers, the
# bit totals round like float32
COUNTER_RTOL = 1e-6


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Executables JAX builds (compiled, or loaded from the persistent
    cache) and the seconds spent on them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.secs += duration


def peak_gib() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings,
    )


def train(model, cfg, mesh, algo: str, steps: int, global_batch: int,
          seed: int, counter: CompileCounter, read_program: bool = True,
          after_first=None) -> dict:
    """Build the step as launch/train.py does and run ``steps`` steps
    through the Trainer, with no restarts. Returns what the checks need.
    ``read_program`` first compiles the step ahead of the run to read its
    text and buffer sizes (the run then compiles it once more)."""
    params_bytes = tree_bytes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    strategy = choose_strategy(
        mesh, sasg_enabled=algo != "sgd", params_bytes=params_bytes,
        trunk_layers=model.pipeline.n_layers if model.pipeline else 0,
    )
    scfg = PRESETS[algo](k_ratio=K_RATIO) if algo == "sasg" else PRESETS[algo]()
    built = build_train_step(model, scfg, mesh, strategy, constant(LR))
    stream = indexed_token_stream(cfg.vocab_size, global_batch, SEQ_LEN, seed=seed)
    key = jax.random.PRNGKey(seed)

    say(f"{algo}: strategy={strategy.name} workers={strategy.num_workers} "
        f"global batch={global_batch} seq={SEQ_LEN} remat={REMAT}")
    text = None
    if read_program:
        batch0 = stream.batch_at(0)
        bsh = built.batch_sharding_fn(batch0)
        t0 = time.perf_counter()
        compiled = jax.jit(
            built.step, in_shardings=(built.state_shardings, bsh),
            out_shardings=(built.state_shardings, None), donate_argnums=(0,),
        ).lower(
            _abstract(jax.eval_shape(built.init, key), built.state_shardings),
            _abstract(batch0, bsh),
        ).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        say(f"{algo}: step compiled ahead in {time.perf_counter() - t0:.1f} s; "
            f"program bytes: args {mem.argument_size_in_bytes} temp "
            f"{mem.temp_size_in_bytes} out {mem.output_size_in_bytes} alias "
            f"{mem.alias_size_in_bytes}")

    step_s, step_compiles = [], []
    jit_step = built.jit_step

    def timed_step(state, batch, force_skip=None):
        n0, t = counter.n, time.perf_counter()
        out = (jit_step(state, batch) if force_skip is None
               else jit_step(state, batch, force_skip))
        jax.block_until_ready(out)
        step_s.append(time.perf_counter() - t)
        step_compiles.append(counter.n - n0)
        if after_first is not None and len(step_s) == 1:
            after_first(built, out[0])
        return out

    trainer = Trainer(
        built._replace(jit_step=timed_step), stream,
        TrainerConfig(total_steps=steps, max_restarts=0, log_every=1),
        log_fn=say,
    )
    state = trainer.run(init_key=key)
    return {"built": built, "scfg": scfg, "state": state,
            "history": trainer.history,
            "text": text, "step_s": step_s,
            "step_compiles": step_compiles}


def check_run(name: str, run: dict, steps: int, mesh) -> None:
    """Finite losses, no compile after the first step, state on the mesh's
    devices, and counters that agree with the sends."""
    hist, built, state = run["history"], run["built"], run["state"]
    check(len(hist) == steps, f"{name}: {len(hist)} of {steps} steps ran")
    losses = [h["loss"] for h in hist]
    check(all(math.isfinite(x) for x in losses), f"{name}: loss not finite: {losses}")
    check(sum(run["step_compiles"][1:]) == 0,
          f"{name}: compiles after the first step: {run['step_compiles']}")
    devices = set(mesh.devices.flat)
    for path, leaf in zip(*tree_flatten_with_paths(state)[:2]):
        check(leaf.sharding.device_set == devices,
              f"{name}: state leaf {path} lives on {leaf.sharding.device_set}")
    sent = sum(h["num_sent"] for h in hist)
    last = hist[-1]
    check(last["rounds_total"] == sent == float(state.counters.rounds),
          f"{name}: rounds {last['rounds_total']} vs sends {sent}")
    for key, per_upload in (("bits_paper", built.bits_paper),
                            ("bits_wire", built.bits_wire)):
        got = float(getattr(state.counters, key))
        check(got == last[f"{key}_total"]
              and math.isclose(got, sent * per_upload, rel_tol=COUNTER_RTOL),
              f"{name}: {key} {got} vs {sent} sends x {per_upload}")
    s = run["step_s"]
    say(f"{name}: losses {[round(x, 4) for x in losses]}; sends {sent:.0f}; "
        f"first step {s[0]:.3f} s; later steps median "
        f"{float(np.median(s[1:])):.3f} s (host clock, smoke reading)")


def kernel_vs_reference(seed: int) -> None:
    """The fused kernel (topk_impl="kernel") and the unfused reference
    select the same indices and values and leave the same residual, bit
    for bit, on seeded real-width leaves on the chip. No tolerance: both run
    the same float32 add and the same iterative masked argmax."""
    shapes = jax.eval_shape(build(get_config(TRAIN_ARCH)).init, jax.random.PRNGKey(0))
    paths, leaves, _ = tree_flatten_with_paths(shapes)
    by_path = dict(zip(paths, leaves))
    for i, path in enumerate(KERNEL_LEAVES):
        leaf = by_path[path]
        kx, ke = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), i))
        g = {"w": jax.random.normal(kx, leaf.shape, leaf.dtype)}
        e = {"w": 0.1 * jax.random.normal(ke, leaf.shape, jnp.float32)}
        out = {}
        for impl in ("kernel", "reference"):
            comp = make_topk_ef(CompressorConfig(k_ratio=K_RATIO, topk_impl=impl))
            out[impl] = jax.jit(lambda e, g, c=comp: c.compress(e, g, None))(e, g)
        (pk, ek), (pr, er) = out["kernel"], out["reference"]
        pk, pr = pk["w"], pr["w"]
        same = (np.array_equal(np.asarray(pk.indices), np.asarray(pr.indices))
                and np.array_equal(np.asarray(pk.values), np.asarray(pr.values))
                and np.array_equal(np.asarray(ek["w"]), np.asarray(er["w"])))
        check(same, f"kernel and reference differ on {path} {leaf.shape}")
        say(f"kernel == reference on {path} {tuple(leaf.shape)}: blocked "
            f"{pk.blocked_shape}, kb {pk.values.shape[-1]}, bit for bit")


def one_chip(seed: int, counter: CompileCounter) -> None:
    dev = jax.devices()[0]
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])

    cfg = get_config(TRAIN_ARCH)
    run = train(build(cfg, remat=REMAT), cfg, mesh, "sasg", STEPS, BATCH, seed,
                counter)
    comp = run["scfg"].compressor
    check(comp.resolved_impl() == "kernel" and comp.resolved_layout() == "per_shard",
          f"SASG preset runs topk_impl={comp.topk_impl} layout={comp.layout}")
    check("tpu_custom_call" in run["text"],
          "the compiled SASG step holds no tpu_custom_call: kernel not compiled")
    check_run("train sasg", run, STEPS, mesh)
    say(f"train: {TRAIN_ARCH} peak device memory {peak_gib()}; "
        f"{BATCH * SEQ_LEN / float(np.median(run['step_s'][1:])):.0f} tok/s at "
        "the median later step (smoke reading)")
    del run

    kernel_vs_reference(seed)

    cfg = get_config(SERVE_ARCH)
    model = build(cfg)
    serve = build_serve(model, mesh, fsdp="data", tp="model")
    params = jax.jit(model.init, out_shardings=serve.param_shardings)(
        jax.random.PRNGKey(seed)
    )
    max_seq = -(-(PROMPT_LEN[1] + NEW_TOKENS) // PAGE) * PAGE
    srv = BatchedServer(serve, params, cfg, SERVE_BATCH, max_seq, paged=True,
                        block_size=PAGE)
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=SERVE_REQUESTS)
    for uid, n in enumerate(lens):
        srv.submit(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=NEW_TOKENS,
        ))
    n0, c0, t0 = counter.n, counter.secs, time.perf_counter()
    done, _ = srv.drain(strict=True)
    wall = time.perf_counter() - t0
    compile_s = counter.secs - c0
    check(sorted(c["uid"] for c in done) == list(range(SERVE_REQUESTS)),
          f"serve: completed {sorted(c['uid'] for c in done)}")
    for c in done:
        toks = c["tokens"]
        check(len(toks) == NEW_TOKENS and all(0 <= t < cfg.vocab_size for t in toks),
              f"serve: request {c['uid']} gave {len(toks)} tokens, "
              f"max id {max(toks, default=-1)} (vocab {cfg.vocab_size})")
    stats = srv.cache_stats()
    say(f"serve: {SERVE_ARCH} batch {SERVE_BATCH}, {SERVE_REQUESTS} requests, "
        f"prompts {int(lens.min())}-{int(lens.max())} tokens, {NEW_TOKENS} new "
        f"each, paged cache (block {PAGE}, max_seq {max_seq}); "
        f"{stats['ticks']} ticks, {counter.n - n0} executables built in "
        f"{compile_s:.1f} s; {stats['decode_tokens'] / max(wall - compile_s, 1e-9):.1f} "
        f"generated tok/s with compile time taken out (smoke reading); "
        f"peak device memory {peak_gib()}")


def four_chips(seed: int, counter: CompileCounter) -> None:
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    mesh = make_mesh((4, 1), ("data", "model"), devices=devs[:4])
    cfg = get_config(TRAIN_ARCH)
    model = build(cfg, remat=REMAT)
    pairs = [(i, j) for i in range(4) for j in range(i + 1, 4)]
    ef_equal = {}

    def ef_after_first(built, state):
        # each worker's error buffer is its own gradient's top-k residual:
        # equal buffers would mean two workers saw the same batch slice
        leaves = jax.tree.leaves(state.wstate.comp_state)
        eq = jax.jit(lambda ls: jnp.stack([
            jnp.all(jnp.stack([jnp.all(x[i] == x[j]) for x in ls]))
            for i, j in pairs
        ]))(leaves)
        ef_equal["pairs"] = np.asarray(eq).tolist()

    sasg = train(model, cfg, mesh, "sasg", STEPS_4CHIP, 4 * BATCH, seed, counter,
                 read_program=False, after_first=ef_after_first)
    check(sasg["built"].strategy.name == "flat"
          and sasg["built"].strategy.num_workers == 4,
          f"sasg strategy {sasg['built'].strategy}")
    check_run("4-chip sasg", sasg, STEPS_4CHIP, mesh)
    check(sasg["history"][0]["num_sent"] == 4,
          f"step 1: {sasg['history'][0]['num_sent']} of 4 workers sent")
    check(not any(ef_equal["pairs"]),
          f"EF buffers equal across workers after step 1 (pairs {pairs}: "
          f"{ef_equal['pairs']})")
    say(f"4-chip sasg: EF buffers differ for all {len(pairs)} worker pairs "
        "after step 1")
    for name, tree in (("params", sasg["state"].params),
                       ("EF", sasg["state"].wstate.comp_state)):
        spans = {len(x.sharding.device_set) for x in jax.tree.leaves(tree)}
        check(spans == {4}, f"4-chip sasg: {name} leaves span {spans} devices")
    loss_sasg = sasg["history"][0]["loss"]
    del sasg

    sgd = train(model, cfg, mesh, "sgd", STEPS_4CHIP, 4 * BATCH, seed, counter,
                read_program=False)
    check(sgd["built"].strategy.name == "plain",
          f"sgd strategy {sgd['built'].strategy}")
    check_run("4-chip sgd", sgd, STEPS_4CHIP, mesh)
    loss_sgd = sgd["history"][0]["loss"]
    check(math.isclose(loss_sasg, loss_sgd, rel_tol=LOSS_RTOL),
          f"step-0 loss sasg {loss_sasg} vs sgd {loss_sgd} (rtol {LOSS_RTOL})")
    say(f"4-chip: step-0 loss sasg {loss_sasg!r} vs plain data-parallel sgd "
        f"{loss_sgd!r} (rtol {LOSS_RTOL}); peak device memory {peak_gib()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = runtime.enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU ({runtime.device_line()}); nothing was run",
              file=sys.stderr)
        return 2
    say(f"{runtime.device_line()}; compile cache {cache}")
    counter = CompileCounter()
    if args.chips == 4:
        four_chips(args.seed, counter)
    else:
        one_chip(args.seed, counter)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
