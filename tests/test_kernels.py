"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py
pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_topk.block_topk import block_topk_pallas
from repro.kernels.block_topk.ref import block_topk_ref
from repro.kernels.topk_ef.ref import topk_ef_ref
from repro.kernels.topk_ef.topk_ef import topk_ef_pallas
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.models.ssd import ssd_chunked as ssd_ref


@pytest.mark.parametrize("nb,bs", [(8, 128), (16, 256), (4, 512), (32, 64)])
@pytest.mark.parametrize("kb", [1, 3, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_topk_kernel_sweep(nb, bs, kb, dtype):
    rng = np.random.default_rng(nb * bs + kb)
    x = jnp.asarray(rng.normal(size=(nb, bs)), dtype).astype(jnp.float32)
    v_k, i_k = block_topk_pallas(x, kb, interpret=True)
    v_r, i_r = block_topk_ref(x, kb)
    # same selected SET per row (tie order may differ): compare sorted |values|
    np.testing.assert_allclose(
        np.sort(np.abs(np.asarray(v_k)), -1),
        np.sort(np.abs(np.asarray(v_r)), -1),
        rtol=1e-6, atol=1e-6,
    )
    # kernel indices must point at the values it claims
    got = np.take_along_axis(np.asarray(x), np.asarray(i_k), axis=1)
    np.testing.assert_allclose(got, np.asarray(v_k), rtol=1e-6)


@pytest.mark.parametrize("nb,bs,kb", [(8, 128, 2), (16, 256, 5), (4, 64, 1)])
@pytest.mark.parametrize("lr", [1.0, 0.05])
def test_topk_ef_kernel_sweep(nb, bs, kb, lr):
    rng = np.random.default_rng(nb + bs + kb)
    g = jnp.asarray(rng.normal(size=(nb, bs)).astype(np.float32))
    e = jnp.asarray(rng.normal(size=(nb, bs)).astype(np.float32)) * 0.1
    ne_k, v_k, i_k = topk_ef_pallas(g, e, jnp.float32(lr), kb, interpret=True)
    ne_r, v_r, i_r = topk_ef_ref(g, e, lr, kb)
    np.testing.assert_allclose(
        np.sort(np.abs(np.asarray(v_k)), -1),
        np.sort(np.abs(np.asarray(v_r)), -1),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(np.asarray(ne_k), np.asarray(ne_r), rtol=1e-5, atol=1e-6)
    # fusion invariant: selected + residual == lr*g + e exactly
    corrected = lr * np.asarray(g) + np.asarray(e)
    dense = np.zeros_like(corrected)
    np.put_along_axis(dense, np.asarray(i_k), np.asarray(v_k), axis=1)
    np.testing.assert_allclose(dense + np.asarray(ne_k), corrected, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rows,bs,kb,tile", [
    (4, 256, 3, 8),      # fewer rows than one tile
    (37, 256, 3, 8),     # 8 does not divide the rows: 3 zero rows padded
    (21, 137, 2, 16),    # an odd block width, as mamba2's w_in blocks are
    (13, 2048, 21, 8),   # the flat layout's block and kb
])
def test_topk_ef_kernel_tpu_tiling_matches_ref(rows, bs, kb, tile):
    """The tiling the chip uses (8-row multiples, zero-padded rows whose
    outputs are dropped) selects exactly what ref.py selects: same indices
    in the same order, same values and residual, bit for bit. lr is 1, as
    on the compressor path (the learning rate is folded into the gradient
    before the kernel), so no multiply-add contraction can round apart."""
    rng = np.random.default_rng(rows * bs + kb)
    g = jnp.asarray(rng.normal(size=(rows, bs)).astype(np.float32))
    e = jnp.asarray(rng.normal(size=(rows, bs)).astype(np.float32)) * 0.1
    ne_k, v_k, i_k = topk_ef_pallas(g, e, jnp.float32(1.0), kb,
                                    tile_blocks=tile, interpret=True)
    ne_r, v_r, i_r = topk_ef_ref(g, e, jnp.float32(1.0), kb)
    assert ne_k.shape == (rows, bs) and v_k.shape == i_k.shape == (rows, kb)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(ne_k), np.asarray(ne_r))


def test_topk_ef_ops_payload_roundtrip():
    from repro.kernels.topk_ef.ops import topk_ef

    rng = np.random.default_rng(3)
    d = 1000  # non-multiple of block: exercises padding
    g = jnp.asarray(rng.normal(size=d).astype(np.float32))
    e = jnp.zeros((d,), jnp.float32)
    p, ne = topk_ef(g, e, jnp.float32(1.0), k=50, block_size=128)
    assert int(p.indices.max()) < d
    np.testing.assert_allclose(
        np.asarray(p.densify() + ne), np.asarray(g), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 16, 1, 16, 32),
    (1, 64, 2, 8, 2, 8, 16),
    (2, 96, 6, 8, 3, 4, 32),
])
def test_ssd_kernel_vs_oracle(b, s, h, p, g, n, chunk):
    rng = np.random.default_rng(b + s + h)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.05, 0.5, size=(b, s, h)).astype(np.float32))
    a_log = jnp.asarray(rng.uniform(-1, 1, size=(h,)).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32)) * 0.3
    cm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32)) * 0.3
    y_k, h_k = ssd_ops.ssd_chunked(x, dt, a_log, bm, cm, chunk)
    y_r, h_r = ssd_ref(x, dt, a_log, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), rtol=2e-4, atol=2e-4)


def test_ssd_kernel_with_initial_state():
    rng = np.random.default_rng(9)
    b, s, h, p, g, n, chunk = 1, 64, 2, 8, 1, 8, 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.05, 0.5, size=(b, s, h)).astype(np.float32))
    a_log = jnp.asarray(rng.uniform(-1, 1, size=(h,)).astype(np.float32))
    bm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32)) * 0.3
    cm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32)) * 0.3
    h0 = jnp.asarray(rng.normal(size=(b, h, p, n)).astype(np.float32)) * 0.1
    y_k, hf_k = ssd_ops.ssd_chunked(x, dt, a_log, bm, cm, chunk, h0)
    y_r, hf_r = ssd_ref(x, dt, a_log, bm, cm, chunk, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hf_k), np.asarray(hf_r), rtol=2e-4, atol=2e-4)
