"""Ahead-of-time compiles of the main path's Pallas kernels for a described
TPU v5e, at the real widths of ``mamba2_370m``'s SASG step: the fused
top-k/error-feedback kernel and the SSD's chunked-scan kernels.

Nothing runs: the TPU compiler that ships with jaxlib lowers and compiles
for a chip that is described, not attached, and refuses what the chip would
refuse (Mosaic tiling, block shapes, VMEM). Interpret mode on the CPU sees
none of that. Each case asserts the kernel reached the compiled program as
a Mosaic custom call (``tpu_custom_call``), i.e. it was not interpreted.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import topk as topk_lib
from repro.core.compressors import CompressorConfig, _blocked_kb
from repro.core.types import tree_flatten_with_paths
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.topk_ef import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the persistent
    # cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def tpu_tiling(monkeypatch):
    # the described chip is not the default backend: steer the wrapper onto
    # its TPU path (VMEM-sized row tiles, compiled rather than interpreted)
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)


def _mamba_leaf(path: str):
    """(blocked view, kb) of one mamba2_370m parameter leaf under the SASG
    preset's per-shard layout (block_size 256, k_ratio 0.01)."""
    from repro.models import build

    shapes = jax.eval_shape(build(get_config("mamba2_370m")).init,
                            jax.random.PRNGKey(0))
    paths, leaves, _ = tree_flatten_with_paths(shapes)
    shape = dict(zip(paths, leaves))[path].shape
    blocked = topk_lib.blocked_view_shape(shape, None, 256, 1)
    return blocked, _blocked_kb(CompressorConfig(), shape, blocked, path)


def _assert_kernel_compiled(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# final_norm: 4 rows (8 does not divide them); embed: 201,120 rows, which
# the 256-row tile does not divide; w_in: 1,572,864 rows of 137 lanes,
# kb 2 — the largest leaf, half the model
@pytest.mark.parametrize("path,kb_expected", [
    ("final_norm/scale", 3),
    ("embed", 3),
    ("unit/0/ssd/w_in", 2),
])
def test_blocked_topk_ef_compiles_at_mamba2_widths(one_chip, tpu_tiling, path,
                                                   kb_expected):
    blocked, kb = _mamba_leaf(path)
    assert kb == kb_expected
    x = jax.ShapeDtypeStruct(blocked, jnp.float32, sharding=one_chip)
    _assert_kernel_compiled(lambda g, e: ops.blocked_topk_ef(g, e, kb), x, x)


def test_flat_topk_ef_compiles_at_block_2048(one_chip, tpu_tiling):
    # one w_in layer as a flat vector: 2,192 blocks of 2048, kb = 21
    d = 1024 * 4384
    k = int(0.01 * d)
    assert -(-k // (d // 2048)) == 21
    x = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    _assert_kernel_compiled(
        lambda g, e, lr: ops.topk_ef(g, e, lr, k, block_size=2048), x, x, lr
    )


@pytest.fixture
def ssd_compiled(monkeypatch):
    monkeypatch.setattr(ssd_ops, "_use_interpret", lambda: False)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ssd_kernels_compile_at_mamba2_widths(one_chip, ssd_compiled, direction):
    """The chunked scan's forward (under jit) and its custom VJP's backward
    (under grad) at the benchmark cell's shapes: 12 x 2048 tokens, 32 heads
    of 64, one group of d_state 128, chunks of 256, bf16 activations."""
    from repro.models.ssd import ssd_dims

    cfg = get_config("mamba2_370m")
    s = cfg.ssm
    _, h = ssd_dims(cfg)
    assert (h, s.head_dim, s.n_groups, s.d_state, s.chunk_size) == (32, 64, 1, 128, 256)
    bsz, seq = 12, 2048

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg((bsz, seq, h, s.head_dim), jnp.bfloat16), arg((bsz, seq, h), jnp.float32),
            arg((h,), jnp.float32), arg((bsz, seq, s.n_groups, s.d_state), jnp.bfloat16),
            arg((bsz, seq, s.n_groups, s.d_state), jnp.bfloat16))

    def fwd(x, dt, a_log, b, c):
        return ssd_ops.ssd_chunked(x, dt, a_log, b, c, s.chunk_size)

    def loss(*a):
        y, hfin = fwd(*a)
        return jnp.sum(y) + jnp.sum(hfin)

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(rf"%\S*ssd_chunk_{direction}\S* = .*tpu_custom_call", text)
