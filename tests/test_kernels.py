"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py
pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.compat

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


def _ssd_case(b, s, h, p, g, n, dtype, with_h0, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32)).astype(dtype)
    dt = jnp.asarray(rng.uniform(0.05, 0.5, size=(b, s, h)).astype(np.float32))
    a_log = jnp.asarray(rng.uniform(-1, 1, size=(h,)).astype(np.float32))
    bm = jnp.asarray(0.3 * rng.normal(size=(b, s, g, n)).astype(np.float32)).astype(dtype)
    cm = jnp.asarray(0.3 * rng.normal(size=(b, s, g, n)).astype(np.float32)).astype(dtype)
    h0 = (jnp.asarray(0.1 * rng.normal(size=(b, h, p, n)).astype(np.float32))
          if with_h0 else None)
    return x, dt, a_log, bm, cm, h0


def _assert_close(got, want, tol, what):
    """Largest error over the largest reference entry, at most ``tol``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= tol, f"{what}: {err:.3e} > {tol}"


# bf16 operands on the MXU round each product's inputs to 8 bits (2^-9
# relative); the kernels' worst error over the largest entry reads 1e-3 to
# 6e-3 on these cases against the float32 oracle, and a term left out of
# any cotangent reads 7e-2 or more
_BF16_TOL = 2e-2


@pytest.mark.parametrize("b,s,h,p,g,n,chunk,with_h0,lanes", [
    (1, 512, 8, 64, 1, 32, 256, False, 256),  # G = 1 and chunks of 256, as in mamba2_370m
    (1, 128, 8, 16, 2, 16, 32, False, 32),    # G > 1
    (2, 96, 6, 8, 3, 4, 32, True, None),      # an initial state
])
def test_ssd_kernel_grads_vs_oracle(monkeypatch, b, s, h, p, g, n, chunk, with_h0, lanes):
    """The custom VJP's Pallas backward against jax.grad of the jnp oracle on
    bf16 inputs: y, h_final, and the cotangents of x, dt, a_log, B, C, h0.
    The first two cases split each group's heads over two tiles."""
    from repro.kernels.ssd_scan import ssd_scan

    if lanes:
        monkeypatch.setattr(ssd_scan, "_TILE_LANES", lanes)
        t = ssd_scan.tiling(b, s, chunk, h, p, g, n)
        assert t.tiles_per_group == 2 and t.chunk // t.block == 2
    x, dt, a_log, bm, cm, h0 = _ssd_case(b, s, h, p, g, n, jnp.bfloat16, with_h0, s + h)
    rng = np.random.default_rng(7)
    wy = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
    wh = jnp.asarray(rng.normal(size=(b, h, p, n)).astype(np.float32))
    argnums = (0, 1, 2, 3, 4) + ((5,) if with_h0 else ())

    def run(fn):
        def loss(*args):
            y, hf = fn(*args[:5], chunk, args[5])
            return jnp.sum(y * wy) + jnp.sum(hf * wh), (y, hf)
        return jax.grad(loss, argnums=argnums, has_aux=True)(x, dt, a_log, bm, cm, h0)

    grads_k, (y_k, h_k) = run(ssd_ops.ssd_chunked)
    grads_r, (y_r, h_r) = run(ssd_ref)
    _assert_close(y_k, y_r, _BF16_TOL, "y")
    _assert_close(h_k, h_r, _BF16_TOL, "h_final")
    for name, gk, gr in zip(("x", "dt", "a_log", "B", "C", "h0"), grads_k, grads_r):
        assert gk.dtype == gr.dtype, name
        _assert_close(gk, gr, _BF16_TOL, f"d{name}")


def test_ssd_kernel_path_in_the_model(monkeypatch):
    """Loss and gradients of a tiny Mamba-2 through lm_forward's layer scan
    with full remat: the kernel path (forced, interpret mode) matches the
    jnp path to the kernel tests' float32 tolerance."""
    from repro.configs import get_config
    from repro.models import build
    from repro.models import ssd

    model = build(get_config("mamba2_370m").reduced(), remat="full")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 256, size=(2, 64)), jnp.int32)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def loss_and_grads(kernel):
        monkeypatch.setattr(ssd, "_kernel_path", lambda seq, chunk: kernel)
        return jax.jit(jax.value_and_grad(model.loss_fn))(params, batch)

    loss_k, grads_k = loss_and_grads(True)
    loss_r, grads_r = loss_and_grads(False)
    np.testing.assert_allclose(float(loss_k), float(loss_r), rtol=2e-4)
    for gk, gr in zip(jax.tree.leaves(grads_k), jax.tree.leaves(grads_r)):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), rtol=2e-4,
                                   atol=2e-4 * float(np.max(np.abs(gr))))


def test_ssd_kernel_dispatch(monkeypatch):
    """The model takes the kernels on a TPU where nothing auto-partitions
    them, and the jnp oracle on the CPU."""
    from jax.sharding import PartitionSpec as P

    from repro.models import ssd

    assert not ssd._kernel_path(2048, 256)          # this CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not ssd._kernel_path(2000, 256)          # not whole chunks
    assert not ssd._kernel_path(1, 1)               # decode
    assert jax.device_count() > 1
    assert not ssd._kernel_path(2048, 256)          # auto-partitioned over devices
    mesh = repro.compat.make_mesh((jax.device_count() // 2, 2), ("data", "model"))
    seen = {}

    def region(name):
        def body(x):
            seen[name] = ssd._kernel_path(2048, 256)
            return x
        return body

    x = jax.ShapeDtypeStruct((jax.device_count(), 4), jnp.float32)
    for name, manual in (("manual", {"data", "model"}), ("auto model", {"data"})):
        jax.eval_shape(jax.shard_map(region(name), mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data"), axis_names=manual,
                                     check_vma=False), x)
    assert seen == {"manual": True, "auto model": False}
