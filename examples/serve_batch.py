"""Serving example: batched continuous decoding of a reduced InternVL2
language backbone on a 4x2 mesh — the decode path the decode_32k/long_500k
dry-run shapes lower at production scale.

  PYTHONPATH=src python examples/serve_batch.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np

from repro.configs import get_config
from repro.compat import make_mesh
from repro.models import build
from repro.serve import BatchedServer, Request, build_serve


def main():
    cfg = get_config("internvl2_2b").reduced()
    model = build(cfg)
    mesh = make_mesh((4, 2), ("data", "model"))
    serve = build_serve(model, mesh, fsdp="data", tp="model")
    params = jax.jit(model.init, out_shardings=serve.param_shardings)(
        jax.random.PRNGKey(0)
    )

    srv = BatchedServer(serve, params, cfg, batch_size=4, max_seq=128)
    rng = np.random.default_rng(0)
    for i in range(10):
        srv.submit(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=int(rng.integers(3, 9))).astype(np.int32),
            max_new_tokens=8,
        ))
    done, pending = srv.drain(strict=True)
    stats = srv.cache_stats()
    mode = "paged" if srv.paged else "dense"
    print(f"served {len(done)} requests in continuous batches of {srv.batch} "
          f"({mode} KV cache)")
    for r in sorted(done, key=lambda r: r["uid"])[:5]:
        print(f"  request {r['uid']}: generated {r['tokens']}")
    assert len(done) == 10 and not pending


if __name__ == "__main__":
    main()
