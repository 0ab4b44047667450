"""Chunked SSD through the Pallas kernels, differentiable by a custom VJP
whose backward is a Pallas kernel too; signature-compatible with the
pure-jnp oracle (repro.models.ssd.ssd_chunked).

The in-chunk cumulative log-decay ``cum`` is computed here in jnp, outside
the custom VJP, so autodiff carries its ``cumsum`` and ``-exp(a_log) dt``.
Inside it, one kernel runs the forward (outputs, and the state entering each
chunk, kept for the backward) and one the backward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ssd_scan as K

F32 = jnp.float32


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# layouts of the small (B, S, H) per-head vectors

def _cols(t: K.Tiling, v):
    """(B, S, H) -> (B, n_tiles, S, per_tile)."""
    return v.reshape(t.bsz, t.seq, t.n_tiles, t.per_tile).transpose(0, 2, 1, 3)


def _uncols(t: K.Tiling, v):
    return v.transpose(0, 2, 1, 3).reshape(t.bsz, t.seq, t.heads)


def _rows(t: K.Tiling, v):
    """(B, S, H) -> (B, n_tiles, per_tile, S)."""
    return v.reshape(t.bsz, t.seq, t.n_tiles, t.per_tile).transpose(0, 2, 3, 1)


def _unrows(t: K.Tiling, v):
    return v.transpose(0, 3, 1, 2).reshape(t.bsz, t.seq, t.heads)


def _group_sum(t: K.Tiling, v):
    """Per-tile transposed (n_tiles, B, N, S) -> (B, S, G*N)."""
    v = v.reshape(t.groups, t.tiles_per_group, t.bsz, t.d_state, t.seq).sum(axis=1)
    return v.transpose(1, 3, 0, 2).reshape(t.bsz, t.seq, t.groups * t.d_state)


def _fwd(t, x, dt, cum, b, c, h0):
    cumc, cumr, dtr = _cols(t, cum), _rows(t, cum), _rows(t, dt)
    y, h_in, hfin = K.forward(t, x, cumc, cumr, dtr, b, c, h0, interpret=_use_interpret())
    return (y, hfin), (x, b, c, cumc, cumr, dtr, h_in)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssd(t, x, dt, cum, b, c, h0):
    return _fwd(t, x, dt, cum, b, c, h0)[0]


def _ssd_bwd(t, res, cts):
    x, b, c, cumc, cumr, dtr, h_in = res
    dy, dhfin = cts
    dx, dcumc, dcumr, ddtr, dbt, dct, dh0 = K.backward(
        t, x, dy.astype(F32), cumc, cumr, dtr, b, c, h_in, dhfin.astype(F32),
        interpret=_use_interpret(),
    )
    return (dx, _unrows(t, ddtr), _uncols(t, dcumc) + _unrows(t, dcumr),
            _group_sum(t, dbt).astype(b.dtype), _group_sum(t, dct).astype(c.dtype), dh0)


_ssd.defvjp(_fwd, _ssd_bwd)


def ssd_chunked(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)   softplus'd
    a_log: jax.Array,  # (H,)
    b: jax.Array,      # (B, S, G, N)
    c: jax.Array,      # (B, S, G, N)
    chunk: int,
    h0: Optional[jax.Array] = None,  # (B, H, P, N)
):
    """Returns (y: (B, S, H, P) float32, h_final: (B, H, P, N) float32)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    t = K.tiling(bsz, s, chunk, h, p, g, n)
    dt = dt.astype(F32)
    da = (-jnp.exp(a_log))[None, None, :] * dt
    cum = jnp.cumsum(da.reshape(bsz, t.n_chunks, chunk, h), axis=2).reshape(bsz, s, h)
    # states are kept transposed, (B, N, H*P)
    h0 = (jnp.zeros((bsz, n, h * p), F32) if h0 is None
          else h0.astype(F32).transpose(0, 3, 1, 2).reshape(bsz, n, h * p))
    y, hfin = _ssd(t, x.reshape(bsz, s, h * p), dt, cum,
                   b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n), h0)
    return y.reshape(bsz, s, h, p), hfin.reshape(bsz, n, h, p).transpose(0, 2, 3, 1)
