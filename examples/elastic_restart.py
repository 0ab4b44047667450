"""Fault-tolerance + elasticity example: train with SASG on a 4-worker mesh,
kill the run mid-flight (simulated node failure), then resume the SAME
checkpoint on a DIFFERENT mesh layout (2-pod hierarchical) — parameters carry
over exactly; SASG error-feedback state re-initializes per DESIGN.md §5.

  PYTHONPATH=src python examples/elastic_restart.py
"""
import os
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import sasg_config
from repro.data import token_stream
from repro.dist.strategy import Strategy, choose_strategy
from repro.compat import make_mesh
from repro.models import build
from repro.optim import constant
from repro.train import Trainer, TrainerConfig, build_train_step


def main():
    cfg = get_config("starcoder2_3b").reduced()
    model = build(cfg)
    scfg = sasg_config(k_ratio=0.02, max_delay=5)
    stream = token_stream(cfg.vocab_size, 8, 32, seed=0)

    def data():
        for b in stream:
            yield {k: jnp.asarray(v) for k, v in b.items()}

    ckpt = tempfile.mkdtemp(prefix="sasg_ckpt_")

    # phase 1: flat 4-worker mesh; a fault fires at step 7 and the Trainer
    # recovers from the last checkpoint automatically
    mesh1 = make_mesh((4, 2), ("data", "model"))
    strat1 = Strategy("flat", ("data",), ("data",), None, None, "model", 4)
    built1 = build_train_step(model, scfg, mesh1, strat1, constant(0.05))
    boom = {7}

    def fault(step):
        if step in boom:
            boom.discard(step)
            raise RuntimeError("simulated node failure")

    tr1 = Trainer(built1, data(),
                  TrainerConfig(total_steps=12, ckpt_dir=ckpt, ckpt_every=3,
                                log_every=3, ckpt_async=False),
                  fault_hook=fault)
    tr1.run(init_key=jax.random.PRNGKey(0))
    print("\n-- phase 1 done (survived 1 injected failure); resizing mesh --\n")

    # phase 2: resume the checkpoint on a 2-pod hierarchical mesh (elastic
    # resize: 4 flat workers -> 2 pod workers)
    mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    strat2 = choose_strategy(mesh2, sasg_enabled=True)
    built2 = build_train_step(model, scfg, mesh2, strat2, constant(0.05))
    tr2 = Trainer(built2, data(),
                  TrainerConfig(total_steps=20, ckpt_dir=ckpt, ckpt_every=5,
                                log_every=4, ckpt_async=False))
    state = tr2.run(init_key=jax.random.PRNGKey(1))
    print(f"\nresumed on {strat2.name} mesh and reached step 20 "
          f"(loss {tr2.history[-1]['loss']:.4f})")


if __name__ == "__main__":
    main()
