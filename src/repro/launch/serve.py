"""Serving driver: continuous-batching engine over a mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch internvl2_2b --reduced \
      --batch 4 --requests 12 --mesh-shape 4,2
"""
import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internvl2_2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--mesh-shape", default="4,2")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="fake CPU host devices (default on CPU: the mesh size)")
    ap.add_argument("--dense", action="store_true",
                    help="dense per-slot KV cache (default: paged when the "
                         "arch has global-attention layers)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--cache-dtype", default=None,
                    help="paged-block wire dtype (default: compute dtype, "
                         "bit-exact)")
    args = ap.parse_args(argv)

    shape = tuple(int(x) for x in args.mesh_shape.split(","))
    ndev = 1
    for s in shape:
        ndev *= s
    from repro.launch import runtime

    runtime.force_host_devices(ndev, args.fake_devices)

    import jax
    import numpy as np

    from repro.compat import make_mesh
    from repro.configs import get_config
    from repro.models import build
    from repro.serve import BatchedServer, Request, build_serve

    device = runtime.device_line()
    print(f"[serve] {device} compile cache {runtime.enable_compile_cache()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    axes = ("pod", "data", "model")[-len(shape):]
    mesh = make_mesh(shape, axes)
    serve = build_serve(model, mesh, fsdp="data", tp="model")
    params = jax.jit(model.init, out_shardings=serve.param_shardings)(
        jax.random.PRNGKey(0)
    )
    paged = False if args.dense else None  # None = auto (paged when pageable)
    srv = BatchedServer(serve, params, cfg, args.batch, args.max_seq,
                        paged=paged, block_size=args.block_size,
                        cache_dtype=args.cache_dtype)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        srv.submit(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab_size, size=6).astype(np.int32),
            max_new_tokens=args.max_new,
        ))
    t0 = time.time()
    done, pending = srv.drain(strict=True)
    dt = time.time() - t0
    stats = srv.cache_stats()
    mode = "paged" if srv.paged else "dense"
    print(f"[serve] {len(done)} requests, {stats['ticks']} engine ticks "
          f"({mode} cache, {stats['cache_dtype']}), "
          f"{stats['decode_tokens'] / dt:.1f} tok/s ({device})")
    if srv.paged:
        print(f"[serve] block high-water {stats['block_high_water']}"
              f"/{stats['num_blocks']}: {stats['high_water_bytes']:.0f} B "
              f"vs dense-equivalent {stats['dense_equiv_bytes']:.0f} B")
    return 0


if __name__ == "__main__":
    sys.exit(main())
