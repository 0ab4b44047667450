"""Pinned reproductions of the XLA SPMD partitioner limits this framework
designs around (DESIGN.md §8). If these start PASSING after a jaxlib upgrade,
the workarounds (TP-only hierarchical FSDP, rotate-half RoPE, iterative
argmax selection) can be revisited.

Each repro runs in a SUBPROCESS because the failure mode is a fatal CHECK
(process abort), not a Python exception.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_PREFIX = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro import compat  # make_mesh with Auto axis types
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = compat.make_mesh((2,2,2), ("pod","data","model"))
"""


def _run(body: str) -> bool:
    """Returns True if the snippet compiles (exit 0)."""
    p = subprocess.run(
        [sys.executable, "-c", _PREFIX + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    return p.returncode == 0 and "COMPILE_OK" in p.stdout


@pytest.mark.slow
def test_topk_sort_gathers_sharded_operand():
    """lax.top_k (sort) all-gathers a sharded operand even when the sort dim
    is local — why blocked_topk uses iterative masked argmax."""
    ok = _run("""
    import re
    x = jax.ShapeDtypeStruct((64, 16, 896), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "model", None)))
    @jax.jit
    def f(x):
        v, i = jax.lax.top_k(jnp.abs(x), 4)
        return v.sum() + i.sum()
    txt = f.lower(x).compile().as_text()
    big = [l for l in txt.splitlines()
           if re.search(r'all-gather\\(', l) and "f32[64,16,896]" in l]
    assert not big, "sort gathered the full operand"
    print("COMPILE_OK")
    """)
    assert not ok, (
        "lax.top_k now partitions sharded batch dims locally — the iterative "
        "argmax workaround in repro.core.topk.blocked_topk can be retired"
    )


@pytest.mark.slow
def test_fsdp_inside_manual_podaxis_shardmap_crashes():
    """Params FSDP-sharded over 'data' inside a manual-'pod' shard_map hits
    spmd_partitioner_util.cc CHECK — why hierarchical SASG is TP-only."""
    ok = _run("""
    from repro.configs import get_config
    from repro.models import build
    from repro.core import sasg_config
    from repro.dist.strategy import Strategy
    from repro.train import build_train_step
    from repro.optim import constant
    cfg = get_config("llama3_8b").reduced()
    model = build(cfg)
    strat = Strategy("hierarchical", ("pod",), ("pod","data"), "data", "data", "model", 2)
    built = build_train_step(model, sasg_config(k_ratio=0.05, max_delay=5), mesh, strat, constant(0.05))
    state = built.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((8, 64), jnp.int32), "labels": jnp.zeros((8, 64), jnp.int32)}
    jax.jit(built.step).lower(state, batch).compile()
    print("COMPILE_OK")
    """)
    assert not ok, (
        "FSDP-over-data now composes with manual-pod shard_map — re-enable "
        "fsdp_axis='data' in dist/strategy.py hierarchical mode"
    )


@pytest.mark.slow
def test_workarounds_compile():
    """The shipped configuration (TP-only hierarchical) does compile."""
    ok = _run("""
    from repro.configs import get_config
    from repro.models import build
    from repro.core import sasg_config
    from repro.dist.strategy import choose_strategy
    from repro.train import build_train_step
    from repro.optim import constant
    cfg = get_config("llama3_8b").reduced()
    model = build(cfg)
    strat = choose_strategy(mesh, sasg_enabled=True)
    built = build_train_step(model, sasg_config(k_ratio=0.05, max_delay=5), mesh, strat, constant(0.05))
    state = built.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((8, 64), jnp.int32), "labels": jnp.zeros((8, 64), jnp.int32)}
    jax.jit(built.step).lower(state, batch).compile()
    print("COMPILE_OK")
    """)
    assert ok
