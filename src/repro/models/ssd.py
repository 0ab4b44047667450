"""Mamba-2 SSD block (state-space duality, arXiv:2405.21060).

Chunked "discrete dual" form (paper Listing 1): sequence split into chunks of
Q; within a chunk the output is a masked (causal, decay-weighted) quadratic
contraction; across chunks the SSM state h in R^{H x P x N} is carried by a
linear recurrence (implemented with lax.scan — the cross-chunk loop is short:
S/Q steps). Decode is the O(1) recurrent update.

On a TPU the chunked form runs as the Pallas kernels of
``repro.kernels.ssd_scan`` (forward and a custom VJP), with this file's
`ssd_chunked` as their oracle; elsewhere it runs as `ssd_chunked`.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssd_scan import ops as ssd_ops


def _pdtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def _dtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _init_normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def ssd_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = cfg.d_model * s.expand
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads


def ssd_block_init(key, cfg: ModelConfig) -> Any:
    s = cfg.ssm
    d = cfg.d_model
    d_inner, h = ssd_dims(cfg)
    n, g = s.d_state, s.n_groups
    ks = jax.random.split(key, 6)
    sc = 1.0 / math.sqrt(d)
    # fused input projection: [x (d_inner), z gate (d_inner), B (g*n), C (g*n), dt (h)]
    proj_out = 2 * d_inner + 2 * g * n + h
    return {
        "w_in": _init_normal(ks[0], (d, proj_out), sc, _pdtype(cfg)),
        "conv_w": _init_normal(
            ks[1], (s.d_conv, d_inner + 2 * g * n), 0.5, _pdtype(cfg)
        ),
        "a_log": jnp.log(jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), _pdtype(cfg)),
        "w_out": _init_normal(ks[5], (d_inner, d), 1.0 / math.sqrt(d_inner), _pdtype(cfg)),
    }


def _segsum(a: jax.Array) -> jax.Array:
    """Stable 'segment sum': L[..., i, j] = sum_{j < m <= i} a[..., m], with
    -inf above the diagonal. a: (..., Q) -> (..., Q, Q)."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    ii, jj = jnp.meshgrid(jnp.arange(q), jnp.arange(q), indexing="ij")
    return jnp.where(ii[..., :, :] >= jj[..., :, :], diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)   softplus'd step sizes
    a_log: jax.Array,  # (H,)
    b: jax.Array,      # (B, S, G, N)
    c: jax.Array,      # (B, S, G, N)
    chunk: int,
    h0: Optional[jax.Array] = None,  # (B, H, P, N)
):
    """Chunked SSD. Returns (y: (B,S,H,P), h_final: (B,H,P,N))."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"
    nc = s // chunk
    rep = h // g

    da = (-jnp.exp(a_log))[None, None, :] * dt            # (B, S, H) log-decay
    xr = x.reshape(bsz, nc, chunk, h, p).astype(jnp.float32)
    br = b.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    cr = c.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    dtr = dt.reshape(bsz, nc, chunk, h)
    dar = da.reshape(bsz, nc, chunk, h)

    # intra-chunk (diagonal) term
    lmat = jnp.exp(_segsum(dar.transpose(0, 1, 3, 2)))     # (B, nc, H, Q, Q)
    cb = jnp.einsum("bzqgn,bzkgn->bzgqk", cr, br)          # (B, nc, G, Q, Q)
    cb = jnp.repeat(cb, rep, axis=2)                       # (B, nc, H, Q, Q)
    y_diag = jnp.einsum("bzhij,bzjh,bzjhp->bzihp", cb * lmat, dtr, xr)

    # per-chunk final states (B expanded from groups to heads)
    cum = jnp.cumsum(dar, axis=2)
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)        # (B, nc, Q, H)
    brh = jnp.repeat(br, rep, axis=3)                      # (B, nc, Q, H, N)
    states = jnp.einsum(
        "bzqhn,bzqh,bzqhp->bzhpn", brh, decay_states * dtr, xr
    )                                                       # (B, nc, H, P, N)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # (B, nc, H)

    def scan_body(hprev, inp):
        st, dec = inp                                      # (B,H,P,N), (B,H)
        hnew = hprev * dec[..., None, None] + st
        return hnew, hprev

    hinit = (
        jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    )
    hfin, hprevs = jax.lax.scan(
        scan_body,
        hinit,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    hprevs = hprevs.transpose(1, 0, 2, 3, 4)               # (B, nc, H, P, N)

    # off-diagonal (state) contribution
    state_decay = jnp.exp(cum)                             # (B, nc, Q, H)
    y = (y_diag + _y_off_grouped(cr, hprevs, state_decay, rep)).reshape(bsz, s, h, p)
    return y, hfin


def _y_off_grouped(cr, hprevs, state_decay, rep):
    """Grouped C: (B,nc,Q,G,N) x states (B,nc,H,P,N) -> (B,nc,Q,H,P)."""
    ch = jnp.repeat(cr, rep, axis=3)  # (B, nc, Q, H, N)
    return jnp.einsum("bzqhn,bzhpn,bzqh->bzqhp", ch, hprevs, state_decay)


def ssd_step(
    x: jax.Array,      # (B, 1, H, P)
    dt: jax.Array,     # (B, 1, H)
    a_log: jax.Array,
    b: jax.Array,      # (B, 1, G, N)
    c: jax.Array,      # (B, 1, G, N)
    h0: jax.Array,     # (B, H, P, N)
):
    """O(1) recurrent decode step."""
    hnum = x.shape[2]
    g = b.shape[2]
    rep = hnum // g
    da = jnp.exp((-jnp.exp(a_log))[None, :] * dt[:, 0])    # (B, H)
    bh = jnp.repeat(b[:, 0], rep, axis=1)                  # (B, H, N)
    ch = jnp.repeat(c[:, 0], rep, axis=1)
    upd = jnp.einsum(
        "bhn,bh,bhp->bhpn", bh.astype(jnp.float32), dt[:, 0], x[:, 0].astype(jnp.float32)
    )
    hnew = h0 * da[..., None, None] + upd
    y = jnp.einsum("bhn,bhpn->bhp", ch.astype(jnp.float32), hnew)
    return y[:, None], hnew


def _kernel_path(seq: int, chunk: int) -> bool:
    """Whether the chunked scan runs as the Pallas kernels: on a TPU, over
    whole chunks, and where no automatic partitioner would have to split
    them (Mosaic kernels cannot be auto-partitioned) — on one device, or
    inside a region in which every mesh axis larger than one is manual."""
    if jax.default_backend() != "tpu" or seq <= 1 or seq % chunk:
        return False
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    return all(n == 1 or t == jax.sharding.AxisType.Manual
               for n, t in zip(mesh.axis_sizes, mesh.axis_types))


def ssd_block_apply(
    params: Any,
    cfg: ModelConfig,
    xin: jax.Array,                 # (B, S, d)
    state: Optional[dict] = None,   # decode: {"h": (B,H,P,N), "conv": (B,K-1,C)}
):
    s = cfg.ssm
    dt_ = _dtype(cfg)
    bsz, seq, _ = xin.shape
    d_inner, h = ssd_dims(cfg)
    g, n, p = s.n_groups, s.d_state, s.head_dim

    proj = xin.astype(dt_) @ params["w_in"].astype(dt_)
    x, z, bmat, cmat, dt_raw = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + g * n, 2 * d_inner + 2 * g * n],
        axis=-1,
    )

    # causal depthwise conv over concat([x, B, C])
    conv_in = jnp.concatenate([x, bmat, cmat], axis=-1)
    k = s.d_conv
    if state is None:
        cpad = jnp.pad(conv_in, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        cpad = jnp.concatenate([state["conv"].astype(conv_in.dtype), conv_in], 1)
    w = params["conv_w"].astype(dt_)
    conv = sum(cpad[:, i : i + seq, :] * w[i][None, None, :] for i in range(k))
    conv = jax.nn.silu(conv)
    new_conv_state = cpad[:, -(k - 1):, :]
    x, bmat, cmat = jnp.split(conv, [d_inner, d_inner + g * n], axis=-1)

    xh = x.reshape(bsz, seq, h, p)
    bh = bmat.reshape(bsz, seq, g, n)
    ch = cmat.reshape(bsz, seq, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])

    if state is not None and seq == 1:
        y, hfin = ssd_step(xh, dt, params["a_log"], bh, ch, state["h"])
    else:
        h0 = None if state is None else state["h"]
        chunked = ssd_ops.ssd_chunked if _kernel_path(seq, s.chunk_size) else ssd_chunked
        y, hfin = chunked(xh, dt, params["a_log"], bh, ch, s.chunk_size, h0)

    y = y + params["d_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, seq, d_inner)
    # gated RMS norm (Mamba-2 uses normalization before out-proj)
    y32 = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    y32 = y32 * jax.lax.rsqrt(var + 1e-6) * params["norm_scale"].astype(jnp.float32)
    out = y32.astype(dt_) @ params["w_out"].astype(dt_)
    new_state = {"h": hfin, "conv": new_conv_state}
    return out, new_state


def ssd_init_state(cfg: ModelConfig, batch: int) -> dict:
    s = cfg.ssm
    d_inner, h = ssd_dims(cfg)
    return {
        "h": jnp.zeros((batch, h, s.head_dim, s.d_state), jnp.float32),
        "conv": jnp.zeros(
            (batch, s.d_conv - 1, d_inner + 2 * s.n_groups * s.d_state), _dtype(cfg)
        ),
    }
