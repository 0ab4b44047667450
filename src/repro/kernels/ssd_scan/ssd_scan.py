"""Pallas TPU kernels: Mamba-2's chunked SSD (arXiv:2405.21060), forward and
backward, each one kernel that walks the chunks in order and carries the
state in VMEM, so the quadratic in-chunk work and the states never reach HBM.

Per head, in one chunk of Q rows (c = the in-chunk cumulative log-decay
``cum``, a column; r = the same as a row; d = dt as a row; h = the state
entering the chunk, kept transposed as (N, P)):

    L      = exp(c_i - r_j) . [i >= j]                   (Q, Q)
    y      = (C B^T . L . d) x + exp(c) . (C h)          (Q, P)
    h_next = exp(c_Q) h + (B^T . exp(c_Q - r) d) x      (N, P)

The Q x Q matrices are built in blocks of Q/2 rows and columns: the block
above the diagonal is zero and skipped, and only the two diagonal blocks
need the causal mask.

Operands:

- in the model's own layouts: x, y, dx (B, S, H*P); B, C (B, S, G*N)
- cum as columns (B, n_tiles, S, per_tile), cum and dt as rows
  (B, n_tiles, per_tile, S): small (B, S, H) arrays laid out so that a
  head is a static lane or sublane of its block
- states: (B, N, H*P), and each chunk's entering state (n_chunks, B, N, H*P),
  which the forward writes for the backward
- dB, dC: per tile, transposed, (n_tiles, B, N, S), summed by the caller.

Grid: (batch, head tile, chunk), chunks in order (the backward in reverse),
the state (or its cotangent) carried in VMEM scratch. A tile is a group of
heads whose lanes (per_tile * P) make the block's width, all in one B/C
group. MXU operands take x's dtype (bf16 in the model, as the compiled XLA
einsums had them) with float32 accumulation; decays, exps and states stay
float32.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

# lanes of x per block: sixteen 64-wide heads, unrolled in the kernel body
_TILE_LANES = 1024


class Tiling(NamedTuple):
    bsz: int
    seq: int
    chunk: int
    heads: int
    head_dim: int
    groups: int
    d_state: int
    per_tile: int    # heads per tile

    @property
    def n_chunks(self) -> int:
        return self.seq // self.chunk

    @property
    def n_tiles(self) -> int:
        return self.heads // self.per_tile

    @property
    def tiles_per_group(self) -> int:
        return self.n_tiles // self.groups

    @property
    def lanes(self) -> int:
        return self.per_tile * self.head_dim

    @property
    def block(self) -> int:
        """Rows and columns of the in-chunk blocks: half a chunk, so that
        the block above the diagonal can be skipped."""
        return self.chunk // 2 if self.chunk % 16 == 0 else self.chunk

    @property
    def grid(self) -> tuple:
        return (self.bsz, self.n_tiles, self.n_chunks)


def tiling(bsz, seq, chunk, heads, head_dim, groups, d_state) -> Tiling:
    assert seq % chunk == 0 and heads % groups == 0, (seq, chunk, heads, groups)
    per_tile = math.gcd(heads // groups, max(1, _TILE_LANES // head_dim))
    return Tiling(bsz, seq, chunk, heads, head_dim, groups, d_state, per_tile)


# ---------------------------------------------------------------------------
# block specs on the (batch, tile, chunk) grid; ``rev`` walks chunks backward
# ---------------------------------------------------------------------------

def _chunk(t: Tiling, rev: bool):
    return (lambda z: t.n_chunks - 1 - z) if rev else (lambda z: z)


def _specs(t: Tiling, rev: bool) -> dict:
    ch, tpg = _chunk(t, rev), t.tiles_per_group
    q, n, lanes, ht = t.chunk, t.d_state, t.lanes, t.per_tile
    return {
        "rows": pl.BlockSpec((1, q, lanes), lambda b, i, z: (b, ch(z), i)),
        "group": pl.BlockSpec((1, q, n), lambda b, i, z: (b, ch(z), i // tpg)),
        "cols": pl.BlockSpec((1, 1, q, ht), lambda b, i, z: (b, i, ch(z), 0)),
        "vrows": pl.BlockSpec((1, 1, ht, q), lambda b, i, z: (b, i, 0, ch(z))),
        "state": pl.BlockSpec((1, n, lanes), lambda b, i, z: (b, 0, i)),
        "entering": pl.BlockSpec((1, 1, n, lanes), lambda b, i, z: (ch(z), b, 0, i)),
        "group_t": pl.BlockSpec((1, 1, n, q), lambda b, i, z: (i, b, 0, ch(z))),
    }


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# in-kernel pieces
# ---------------------------------------------------------------------------

def _nt(a, b):
    """a b^T: contract the last dims."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32)


def _tn(a, b):
    """a^T b: contract the first dims."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


def _row_sums(a):
    """Row sums of a float32 block, on the MXU: the block split into three
    bf16 parts (8 bits each, so their sum is the float32 value), each times
    ones, accumulated in float32. A cross-lane sum costs the backward about
    half its time on v5e."""
    ones = jnp.ones((a.shape[1], 128), jnp.bfloat16)
    total = 0.0
    for _ in range(3):
        part = a.astype(jnp.bfloat16)
        total = total + _nn(part, ones)
        a = a - part.astype(F32)
    return total[:, :1]


class _Blocks:
    """The lower-triangular blocks (I, J), J <= I, of a chunk's Q x Q
    matrices, with the causal mask of the diagonal ones."""

    def __init__(self, t: Tiling):
        self.t = t.block
        self.n = t.chunk // t.block
        row = jax.lax.broadcasted_iota(jnp.int32, (self.t, self.t), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (self.t, self.t), 1)
        self.causal = row >= col

    def rows(self, i):
        return slice(i * self.t, (i + 1) * self.t)

    def pairs(self):
        return [(i, j) for i in range(self.n) for j in range(i + 1)]

    def col_blocks(self, ref, k):
        """Head k's column of a (1, 1, Q, per_tile) block, block by block."""
        return [ref[0, 0, self.rows(i), k:k + 1] for i in range(self.n)]

    def row_blocks(self, ref, k):
        """Head k's row of a (1, 1, per_tile, Q) block, block by block (each
        loaded on its own: a slice of a loaded row keeps a lane offset that
        Mosaic cannot broadcast)."""
        return [ref[0, 0, k:k + 1, self.rows(j)] for j in range(self.n)]

    def decay(self, c, r, i, j):
        """L of block (i, j) from the column blocks c and row blocks r:
        exp(c_i - r_j), masked on the diagonal."""
        e = c[i] - r[j]
        if i == j:
            e = jnp.where(self.causal, e, -jnp.inf)
        return jnp.exp(e)


def _lanes(t: Tiling, k: int):
    return slice(k * t.head_dim, (k + 1) * t.head_dim)


def _operands(t: Tiling, mx, b_ref, c_ref):
    """B and C in the MXU dtype, their float32 transposes (N, Q), C B^T."""
    b, c = b_ref[0].astype(F32), c_ref[0].astype(F32)
    return b.T, c.T, c.astype(mx), _nt(c.astype(mx), b.astype(mx))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(t: Tiling, x_ref, cumc_ref, cumr_ref, dtr_ref, b_ref, c_ref, h0_ref,
                y_ref, hin_ref, hfin_ref, h_scr):
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _():
        h_scr[...] = h0_ref[0]

    hin_ref[0, 0] = h_scr[...]
    mx = x_ref.dtype
    bt, _, cm, cb = _operands(t, mx, b_ref, c_ref)
    blk = _Blocks(t)
    growth = jnp.exp(cumc_ref[0, 0])            # (Q, per_tile): every head's at once
    for k in range(t.per_tile):
        lanes = _lanes(t, k)
        cs = blk.col_blocks(cumc_ref, k)
        rs, ds = blk.row_blocks(cumr_ref, k), blk.row_blocks(dtr_ref, k)
        r, d = cumr_ref[0, 0, k:k + 1, :], dtr_ref[0, 0, k:k + 1, :]
        x = x_ref[0, :, lanes]
        h = h_scr[:, lanes]
        # in-chunk term, block row by block row, then the entering state's read-out
        y = []
        for i in range(blk.n):
            acc = 0.0
            for j in range(i + 1):
                m = cb[blk.rows(i), blk.rows(j)] * blk.decay(cs, rs, i, j) * ds[j]
                acc = acc + _nn(m.astype(mx), x[blk.rows(j)])
            y.append(acc)
        y = jnp.concatenate(y, axis=0) + growth[:, k:k + 1] * _nn(cm, h.astype(mx))
        y_ref[0, :, lanes] = y
        # the state leaving the chunk
        last = r[:, -1:]
        w = jnp.exp(last - r) * d                                        # (1, Q)
        h_scr[:, lanes] = h * jnp.exp(last) + _nn((bt * w).astype(mx), x)

    @pl.when(z == t.n_chunks - 1)
    def _():
        hfin_ref[0] = h_scr[...]


def forward(t: Tiling, x, cum_cols, cum_rows, dt_rows, b, c, h0, *, interpret=False):
    """y (B, S, H*P) float32, the state entering each chunk (n_chunks, B, N,
    H*P), and the final state (B, N, H*P), from the initial state ``h0``."""
    sp = _specs(t, rev=False)
    hp = t.heads * t.head_dim
    return pl.pallas_call(
        functools.partial(_fwd_kernel, t),
        grid=t.grid,
        in_specs=[sp["rows"], sp["cols"], sp["vrows"], sp["vrows"], sp["group"],
                  sp["group"], sp["state"]],
        out_specs=[sp["rows"], sp["entering"], sp["state"]],
        out_shape=[
            jax.ShapeDtypeStruct((t.bsz, t.seq, hp), F32),
            jax.ShapeDtypeStruct((t.n_chunks, t.bsz, t.d_state, hp), F32),
            jax.ShapeDtypeStruct((t.bsz, t.d_state, hp), F32),
        ],
        scratch_shapes=[pltpu.VMEM((t.d_state, t.lanes), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(x, cum_cols, cum_rows, dt_rows, b, c, h0)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(t: Tiling, x_ref, dy_ref, cumc_ref, cumr_ref, dtr_ref, b_ref, c_ref,
                hin_ref, dhfin_ref,
                dx_ref, dcumc_ref, dcumr_ref, ddtr_ref, dbt_ref, dct_ref, dh0_ref, g_scr):
    step = pl.program_id(2)           # chunks walk backward: step 0 is the last

    @pl.when(step == 0)
    def _():
        g_scr[...] = dhfin_ref[0]

    mx = x_ref.dtype
    q = t.chunk
    bt, ct, _, cb = _operands(t, mx, b_ref, c_ref)
    blk = _Blocks(t)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    dcb = {ij: 0.0 for ij in blk.pairs()}      # d(C B^T), summed over the tile's heads
    dbt = jnp.zeros(bt.shape, F32)             # d(B^T), d(C^T): (N, Q)
    dct = jnp.zeros(ct.shape, F32)
    for k in range(t.per_tile):
        lanes = _lanes(t, k)
        cs = blk.col_blocks(cumc_ref, k)
        rs, ds = blk.row_blocks(cumr_ref, k), blk.row_blocks(dtr_ref, k)
        r, d = cumr_ref[0, 0, k:k + 1, :], dtr_ref[0, 0, k:k + 1, :]
        x = x_ref[0, :, lanes]
        dy = dy_ref[0, :, lanes]
        dym = dy.astype(mx)
        h, g = hin_ref[0, 0, :, lanes], g_scr[:, lanes]          # (N, P)
        # in-chunk term: y_i = sum_j M_ij x_j, M = CB . L . d
        dx = [0.0] * blk.n
        dcum_c = [0.0] * blk.n                 # the c_i of L, as columns
        dcum_r = [0.0] * blk.n                 # the r_j of L, as rows
        dd = [0.0] * blk.n
        for i, j in blk.pairs():
            lmat = blk.decay(cs, rs, i, j)
            dj = ds[j]
            cbij = cb[blk.rows(i), blk.rows(j)]
            dx[j] = dx[j] + _tn((cbij * lmat * dj).astype(mx), dym[blk.rows(i)])
            kmat = _nt(dym[blk.rows(i)], x[blk.rows(j)]) * lmat        # dM . L
            dcb[i, j] = dcb[i, j] + kmat * dj
            kc = kmat * cbij
            col = jnp.sum(kc, axis=0, keepdims=True)
            dd[j] = dd[j] + col
            dcum_r[j] = dcum_r[j] - col * dj
            dcum_c[i] = dcum_c[i] + _row_sums(kc * dj)
        dx = jnp.concatenate(dx, axis=0)
        dcum_r = jnp.concatenate(dcum_r, axis=1)
        dd = jnp.concatenate(dd, axis=1)
        # read-out of the entering state: y += exp(c) . (C h)
        e = jnp.exp(r)                                                   # (1, Q)
        hy = _nt(h.astype(mx), dym)                                      # (N, Q)
        dct = dct + hy * e
        dcum_r = dcum_r + e * jnp.sum(ct * hy, axis=0, keepdims=True)
        dh = _nn((ct * e).astype(mx), dym)                               # (N, P)
        # the state leaving the chunk: h_next = exp(c_Q) h + (B^T . w) x
        last = r[:, -1:]
        decay = jnp.exp(last)
        s = jnp.exp(last - r)
        w = s * d
        gm = g.astype(mx)
        dx = dx + _tn((bt * w).astype(mx), gm)
        dbw = _nt(gm, x)                                                 # (N, Q)
        dbt = dbt + dbw * w
        dw = jnp.sum(dbw * bt, axis=0, keepdims=True)
        dd = dd + dw * s
        dlast = jnp.sum(dw * w, axis=1, keepdims=True) + jnp.sum(g * h) * decay
        dcum_r = dcum_r - dw * w + jnp.where(at_last, dlast, 0.0)
        g_scr[:, lanes] = g * decay + dh
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        dcumc_ref[0, 0, :, k:k + 1] = jnp.concatenate(dcum_c, axis=0)
        dcumr_ref[0, 0, k:k + 1, :] = dcum_r
        ddtr_ref[0, 0, k:k + 1, :] = dd
    # C B^T: dC_i += sum_j dCB_ij B_j, dB_j += sum_i dCB_ij C_i (transposed)
    dct_blocks = [dct[:, blk.rows(i)] for i in range(blk.n)]
    dbt_blocks = [dbt[:, blk.rows(j)] for j in range(blk.n)]
    for (i, j), dcbij in dcb.items():
        dcbm = dcbij.astype(mx)
        dct_blocks[i] = dct_blocks[i] + _nt(bt[:, blk.rows(j)].astype(mx), dcbm)
        dbt_blocks[j] = dbt_blocks[j] + _nn(ct[:, blk.rows(i)].astype(mx), dcbm)
    dbt_ref[0, 0] = jnp.concatenate(dbt_blocks, axis=1)
    dct_ref[0, 0] = jnp.concatenate(dct_blocks, axis=1)

    @pl.when(step == t.n_chunks - 1)
    def _():
        dh0_ref[0] = g_scr[...]


def backward(t: Tiling, x, dy, cum_cols, cum_rows, dt_rows, b, c, h_in, dhfin, *,
             interpret=False):
    """Cotangents of x, cum (as columns and as rows), dt (rows), B and C
    (per tile, transposed) and the initial state, from dy and the cotangent
    of the final state."""
    sp = _specs(t, rev=True)
    vrows = jax.ShapeDtypeStruct((t.bsz, t.n_tiles, t.per_tile, t.seq), F32)
    grp = jax.ShapeDtypeStruct((t.n_tiles, t.bsz, t.d_state, t.seq), F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, t),
        grid=t.grid,
        in_specs=[sp["rows"], sp["rows"], sp["cols"], sp["vrows"], sp["vrows"],
                  sp["group"], sp["group"], sp["entering"], sp["state"]],
        out_specs=[sp["rows"], sp["cols"], sp["vrows"], sp["vrows"], sp["group_t"],
                   sp["group_t"], sp["state"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((t.bsz, t.n_tiles, t.seq, t.per_tile), F32),
            vrows,
            vrows,
            grp,
            grp,
            jax.ShapeDtypeStruct(dhfin.shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((t.d_state, t.lanes), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(x, dy, cum_cols, cum_rows, dt_rows, b, c, h_in, dhfin)
