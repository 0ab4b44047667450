"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3_8b --reduced \
      --algo sasg --steps 200 --mesh-shape 2,4 --ckpt-dir /tmp/ckpt

On the CPU (JAX_PLATFORMS=cpu, or --fake-devices N) the mesh is built from
that many fake host devices; on an accelerator it uses the real devices, so
--mesh-shape must multiply to at most the device count.
"""
import argparse
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--algo", default="sasg",
                    choices=["sgd", "sparse", "lasg", "sasg"])
    ap.add_argument("--k-ratio", type=float, default=0.01)
    ap.add_argument("--compressor", default=None,
                    help="override the preset's compressor (topk_ef, randk, "
                         "qsgd, signsgd_ef, terngrad, identity) — every "
                         "compressor composes with --stages via the "
                         "repro.comm transport")
    ap.add_argument("--topk-impl", default=None,
                    help="topk_ef impl: kernel (fused Pallas, default) | "
                         "reference | exact")
    ap.add_argument("--layout", default=None,
                    help="wire layout: per_shard | per_tensor | flat")
    ap.add_argument("--wire-dtype", default=None,
                    help="payload value dtype on the wire (e.g. bfloat16)")
    ap.add_argument("--k-ratio-per-layer", default=None,
                    help="layer-wise k schedule: 'pattern=ratio,...' matched "
                         "against leaf paths (Shi et al., 2019)")
    ap.add_argument("--max-delay", type=int, default=10)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--mesh-shape", default="4,2",
                    help="data,model (or pod,data,model) sizes")
    ap.add_argument("--stages", type=int, default=1,
                    help="GPipe pipeline stages; >1 inserts a stage axis of "
                         "that size before the LAST --mesh-shape entry (the "
                         "model axis — keep the data axis in --mesh-shape, "
                         "e.g. --mesh-shape 2,1 --stages 2)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="GPipe microbatches per worker (0 -> stages)")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="fake CPU host devices (default on CPU: the mesh size)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--resize", default=None,
                    help="in-run elastic membership events: 'STEP:WORKERS,"
                         "STEP:WORKERS,...' (e.g. '50:2,100:4' shrinks the "
                         "worker axis to 2 at step 50, grows back to 4 at "
                         "100 — no restart, state carried per DESIGN.md §5)")
    ap.add_argument("--faults", default=None,
                    help="chaos injection: 'KIND@STEP,...' with KIND in "
                         "crash, straggler, corrupt_ckpt, save_fail, "
                         "data_hiccup (e.g. 'crash@30,data_hiccup@70')")
    args = ap.parse_args(argv)

    shape = tuple(int(x) for x in args.mesh_shape.split(","))
    if args.stages > 1:
        shape = shape[:-1] + (args.stages, shape[-1])
    ndev = 1
    for s in shape:
        ndev *= s
    from repro.launch import runtime

    runtime.force_host_devices(ndev, args.fake_devices)

    import jax

    from repro.compat import make_mesh

    from repro.configs import get_config
    from repro.core import PRESETS
    from repro.data import (
        indexed_classification_stream,
        indexed_token_stream,
        synthetic_classification,
    )
    from repro.dist.strategy import choose_strategy
    from repro.models import build
    from repro.optim import constant
    from repro.train import (
        ElasticTrainer,
        Fault,
        FaultPlan,
        Trainer,
        TrainerConfig,
        WorkerMembership,
        build_train_step,
    )
    from repro.core.types import tree_bytes

    print(f"[train] {runtime.device_line()} "
          f"compile cache {runtime.enable_compile_cache()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg, remat=args.remat)

    if args.stages > 1:
        axes = ("pod", "data", "stage", "model")[-len(shape):]
    else:
        axes = ("pod", "data", "model")[-len(shape):]
    mesh = make_mesh(shape, axes)
    params_bytes = tree_bytes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    strategy = choose_strategy(
        mesh, sasg_enabled=args.algo != "sgd", params_bytes=params_bytes,
        pipeline_stages=args.stages, microbatches=args.microbatches,
        trunk_layers=model.pipeline.n_layers if model.pipeline else 0,
    )
    print(f"[train] arch={cfg.name} algo={args.algo} mesh={dict(zip(axes, shape))} "
          f"strategy={strategy.name} workers={strategy.num_workers} "
          f"stages={strategy.pipeline_stages}")

    if args.algo in ("sasg", "sparse"):
        scfg = PRESETS[args.algo](k_ratio=args.k_ratio)
    else:
        scfg = PRESETS[args.algo]()
    comp_overrides = {}
    if args.compressor:
        comp_overrides["name"] = args.compressor
    if args.topk_impl:
        comp_overrides["topk_impl"] = args.topk_impl
    if args.layout:
        comp_overrides["layout"] = args.layout
    if args.wire_dtype:
        comp_overrides["wire_dtype"] = args.wire_dtype
    if args.k_ratio_per_layer:
        schedule = []
        for item in args.k_ratio_per_layer.split(","):
            pattern, sep, ratio = item.partition("=")
            if not sep or not pattern:
                ap.error(f"--k-ratio-per-layer entry {item!r} is not "
                         "'pattern=ratio'")
            try:
                schedule.append((pattern, float(ratio)))
            except ValueError:
                ap.error(f"--k-ratio-per-layer ratio {ratio!r} is not a float")
        comp_overrides["k_ratio_per_layer"] = tuple(schedule)
    if comp_overrides:
        import dataclasses

        scfg = dataclasses.replace(
            scfg, compressor=dataclasses.replace(scfg.compressor, **comp_overrides)
        )
    built = build_train_step(model, scfg, mesh, strategy, constant(args.lr))
    if built.exchange is not None:
        t = built.exchange.transport
        print(f"[train] transport kind={t.kind} layout={t.layout} "
              f"bits/upload paper={built.bits_paper:.3e} "
              f"wire={built.bits_wire:.3e}")

    # replayable (step-indexed) streams: batch t is a pure function of
    # (seed, t), so recovery and elastic resizes replay the exact batch
    # sequence an uninterrupted run would consume (DESIGN.md §5)
    if cfg.family in ("mlp", "cnn"):
        # paper nets train on the synthetic classification mixture, not tokens
        img = (28, 28, 1) if cfg.family == "mlp" else (32, 32, 3)
        xs, ys = synthetic_classification(2048, cfg.vocab_size, img, seed=0)
        stream = indexed_classification_stream(xs, ys, args.global_batch, seed=0)
    else:
        stream = indexed_token_stream(
            cfg.vocab_size, args.global_batch, args.seq_len, seed=0
        )

    tcfg = TrainerConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        log_every=max(args.steps // 20, 1),
    )
    plan = None
    if args.resize or args.faults:
        plan = FaultPlan()
        for item in (args.resize or "").split(",") if args.resize else ():
            step_s, sep, workers_s = item.partition(":")
            if not sep:
                ap.error(f"--resize entry {item!r} is not 'STEP:WORKERS'")
            step_i, target = int(step_s), int(workers_s)
            cur = strategy.num_workers
            plan = (plan.worker_drop(step_i, to=target) if target < cur
                    else plan.worker_join(step_i, to=target))
        for item in (args.faults or "").split(",") if args.faults else ():
            kind, sep, step_s = item.partition("@")
            if not sep:
                ap.error(f"--faults entry {item!r} is not 'KIND@STEP'")
            try:
                plan = plan._with(Fault(kind, int(step_s)))
            except ValueError as e:
                ap.error(str(e))
    if plan is not None:
        def resized_mesh(n):
            # keep the non-worker axes (model/stage) and retarget only the
            # worker axis size; fake devices cap how far we can grow
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            wa = strategy.worker_axes[0] if strategy.worker_axes else "data"
            sizes[wa] = n
            return make_mesh(tuple(sizes.values()), tuple(sizes.keys()))

        membership = WorkerMembership(
            model, scfg, constant(args.lr), mesh_fn=resized_mesh,
            sasg_enabled=args.algo != "sgd", params_bytes=params_bytes,
        )
        trainer = ElasticTrainer(built, stream, tcfg,
                                 membership=membership, plan=plan)
    else:
        trainer = Trainer(built, stream, tcfg)
    state = trainer.run(init_key=jax.random.PRNGKey(0))
    print(f"[train] done: {args.steps} steps; total rounds "
          f"{float(state.counters.rounds):.0f}; bits(paper) "
          f"{float(state.counters.bits_paper):.3e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
