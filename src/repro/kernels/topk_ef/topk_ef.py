"""Pallas TPU kernel: fused error-feedback + block top-k + residual update —
the SASG hot loop (paper Algorithm 1, lines 4/7-8).

Unfused, the per-step compression path reads/writes HBM four times over the
model dimension d:

    g = lr*grad + e     (read grad, read e, write g)
    topk(g)             (read g)
    e' = g - T_k(g)     (read g, write e')

Fused, each d-element flows HBM->VMEM once and back once:

    read grad, read e  ->  compute g, per-block top-k, e'  ->  write e', (v,i)

i.e. 2 reads + 1 write of d floats + O(k) outputs versus 4 reads + 2 writes —
a ~2x cut on the memory-bound term of the compression stage. Selection uses
the same iterative masked-argmax as block_topk (VPU-only, no gathers).

Grid/BlockSpec: grid=(rows/TILE,), tiles (TILE, BS) of grad and err in
VMEM; outputs: err' tile (TILE, BS), values/indices tiles (TILE, KB); lr is
a (1,1) VMEM operand broadcast by the index map. Mosaic's block-shape rule
wants TILE divisible by 8 (BS and KB are whole array dims), so the wrapper
pads the rows up to a whole number of tiles with zero rows and drops their
outputs. The selected values/indices ride in the loop carry and each output
block is written once after the loop: a per-iteration column store at a
dynamic lane offset is refused by the TPU compiler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _topk_ef_kernel(lr_ref, grad_ref, err_ref, newerr_ref, vals_ref, idx_ref,
                    *, kb: int):
    lr = lr_ref[0, 0]
    g = lr * grad_ref[...].astype(jnp.float32) + err_ref[...].astype(jnp.float32)
    tb, bs = g.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, bs), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (tb, kb), 1)

    def body(i, carry):
        mag_c, vals, idx = carry
        mx = jnp.max(mag_c, axis=1, keepdims=True)
        first = jnp.min(jnp.where(mag_c == mx, col, bs), axis=1, keepdims=True)
        sel = col == first
        v = jnp.sum(jnp.where(sel, g, 0.0), axis=1, keepdims=True)
        vals = jnp.where(kcol == i, v, vals)
        idx = jnp.where(kcol == i, first, idx)
        return jnp.where(sel, -jnp.inf, mag_c), vals, idx

    mag, vals, idx = jax.lax.fori_loop(
        0, kb, body,
        (jnp.abs(g), jnp.zeros((tb, kb), jnp.float32),
         jnp.zeros((tb, kb), jnp.int32)),
    )
    # |g| is never -inf, so -inf marks exactly the selected coordinates
    newerr_ref[...] = jnp.where(mag == -jnp.inf, 0.0, g)
    vals_ref[...] = vals
    idx_ref[...] = idx


def topk_ef_pallas(
    grad2d: jax.Array,       # (rows, block_size)
    err2d: jax.Array,        # (rows, block_size) fp32
    lr: jax.Array,           # scalar
    kb: int,
    tile_blocks: int = 8,
    interpret: bool = False,
):
    """Fused EF + per-row top-kb. ``tile_blocks`` is rounded up to a
    multiple of 8; rows are zero-padded to a whole number of tiles and the
    padded rows' outputs dropped."""
    nb, bs = grad2d.shape
    tile = -(-tile_blocks // 8) * 8
    padded = -(-nb // tile) * tile
    if padded != nb:
        pad = ((0, padded - nb), (0, 0))
        grad2d, err2d = jnp.pad(grad2d, pad), jnp.pad(err2d, pad)
    kernel = functools.partial(_topk_ef_kernel, kb=kb)
    newerr, vals, idx = pl.pallas_call(
        kernel,
        grid=(padded // tile,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),          # lr scalar
            pl.BlockSpec((tile, bs), lambda i: (i, 0)),      # grad
            pl.BlockSpec((tile, bs), lambda i: (i, 0)),      # err
        ],
        out_specs=[
            pl.BlockSpec((tile, bs), lambda i: (i, 0)),      # err'
            pl.BlockSpec((tile, kb), lambda i: (i, 0)),      # values
            pl.BlockSpec((tile, kb), lambda i: (i, 0)),      # indices
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, bs), jnp.float32),
            jax.ShapeDtypeStruct((padded, kb), jnp.float32),
            jax.ShapeDtypeStruct((padded, kb), jnp.int32),
        ],
        interpret=interpret,
        name="topk_ef",
    )(lr.reshape(1, 1).astype(jnp.float32), grad2d, err2d)
    if padded != nb:
        newerr, vals, idx = newerr[:nb], vals[:nb], idx[:nb]
    return newerr, vals, idx
