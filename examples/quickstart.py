"""Quickstart: train a reduced LLaMA-3-family model with SASG on a 4x2
device mesh (8 fake CPU devices), watching the adaptive rule skip uploads.

  PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import sasg_config
from repro.data import token_stream
from repro.dist.strategy import choose_strategy
from repro.compat import make_mesh
from repro.models import build
from repro.optim import constant
from repro.train import build_train_step


def main():
    cfg = get_config("llama3_8b").reduced()
    model = build(cfg)

    mesh = make_mesh((4, 2), ("data", "model"))
    strategy = choose_strategy(mesh, sasg_enabled=True)
    print(f"strategy: {strategy.name} ({strategy.num_workers} SASG workers, "
          f"TP over '{strategy.tp_axis}')")

    built = build_train_step(
        model,
        sasg_config(k_ratio=0.01, max_delay=10),   # paper: top-1%, D=10
        mesh, strategy, constant(0.05),
    )
    state = built.init(jax.random.PRNGKey(0))

    stream = token_stream(cfg.vocab_size, batch=8, seq=64, seed=0)
    for step in range(40):
        batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
        state, mets = built.jit_step(state, batch)
        if step % 5 == 0:
            print(f"step {step:3d}  loss {float(mets['loss']):7.4f}  "
                  f"uploads {float(mets['num_sent']):.0f}/{strategy.num_workers}  "
                  f"cum-bits(paper) {float(mets['bits_paper_total']):.3e}")
    dense_bits = 40 * strategy.num_workers * 32.0 * sum(
        x.size for x in jax.tree.leaves(state.params)
    )
    print(f"\nSASG transmitted {float(state.counters.bits_paper):.3e} bits; "
          f"dense SGD would have transmitted {dense_bits:.3e} "
          f"({dense_bits / float(state.counters.bits_paper):.0f}x more)")


if __name__ == "__main__":
    main()
