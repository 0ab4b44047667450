"""Plain reference of mamba2_370m (Mamba-2, arXiv:2405.21060) as the
benchmark runs it, in float32 jax.numpy.

The SSD mixer is written in its quadratic ("attention") form over the whole
sequence, the form the paper derives the chunked algorithm from:

    y_i = sum_{j <= i} exp(a_{j+1} + ... + a_i) (C_i . B_j) dt_j x_j + D x_i,
    a_t = -exp(A_log) dt_t,  dt_t = softplus(dt_raw_t + dt_bias).

It differs from the published model where the program does, so that the
two compute the same function: the vocabulary padded to 50,280 rows, no
bias on the depthwise conv, and the gated RMSNorm's epsilon is 1e-6
(``departures`` in the JSON). The residual stream is float32 here (the
source's ``residual_in_fp32``).

Parameters come in the program's layout (stacked layers under
``unit/0``); the reference takes them as a nested dict and imports nothing
of the program. ``prec`` is "f32" (the reference) or "fp8" (the control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness import numerics as N

GATED_NORM_EPS = 1e-6


def vocab_rows(c: dict) -> int:
    """Embedding rows as the program holds them (``departures``: it pads
    the source's vocabulary to a multiple of 8, not 16)."""
    return c["program"]["fields"]["vocab_size"]


def dims(c: dict) -> dict:
    d = c["d_model"]
    di = c["expand"] * d
    g, n = c["ngroups"], c["d_state"]
    h = di // c["headdim"]
    return dict(d=d, di=di, g=g, n=n, h=h, p=c["headdim"], k=c["d_conv"],
                L=c["n_layer"], V=vocab_rows(c))


def param_shapes(c: dict) -> dict:
    """The program's parameter layout at this configuration."""
    D = dims(c)
    bf, f32 = jnp.dtype(c["param_dtype"]), jnp.float32
    L, d, di, h = D["L"], D["d"], D["di"], D["h"]
    S = jax.ShapeDtypeStruct
    ssd = {
        "w_in": S((L, d, 2 * di + 2 * D["g"] * D["n"] + h), bf),
        "conv_w": S((L, D["k"], di + 2 * D["g"] * D["n"]), bf),
        "a_log": S((L, h), f32),
        "dt_bias": S((L, h), f32),
        "d_skip": S((L, h), f32),
        "norm_scale": S((L, di), bf),
        "w_out": S((L, di, d), bf),
    }
    out = {
        "embed": S((D["V"], d), bf),
        "unit": [{"norm1": {"scale": S((L, d), f32)}, "ssd": ssd}],
        "rem": [],
        "final_norm": {"scale": S((d,), f32)},
    }
    if not c["tie_embeddings"]:
        out["lm_head"] = S((d, D["V"]), bf)
    return out


def init_rule(path: str, shape, c: dict):
    leaf = path.split("/")[-1]
    if leaf == "embed":
        return ("normal", c["initializer_range"])
    if leaf in ("scale", "norm_scale", "d_skip"):
        return ("ones",)
    if leaf == "a_log":
        return ("a_log", 1.0, 16.0)
    if leaf == "dt_bias":
        return ("dt_bias", 1e-3, 1e-1)
    # fan-in of (.., in, out); the conv's (.., width, channels) gets 1/sqrt(width)
    return ("normal", 1.0 / math.sqrt(shape[-2]))


def _ssd(p, h, D, prec):
    """One Mamba-2 mixer over one sequence h: (S, d) -> (S, d)."""
    S = h.shape[0]
    di, g, n, H, P, K = D["di"], D["g"], D["n"], D["h"], D["p"], D["k"]
    proj = N.mm(h, p["w_in"], prec)
    xs, z, B, C, dtr = jnp.split(
        proj, [di, 2 * di, 2 * di + g * n, 2 * di + 2 * g * n], axis=-1)
    conv_in = jnp.concatenate([xs, B, C], axis=-1)
    w = p["conv_w"].astype(jnp.float32)
    pad = jnp.pad(conv_in, ((K - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(pad[i:i + S] * w[i] for i in range(K)))
    xs, B, C = jnp.split(conv, [di, di + g * n], axis=-1)
    xh = xs.reshape(S, H, P)
    dt = jax.nn.softplus(dtr + p["dt_bias"])                      # (S, H)
    a = -jnp.exp(p["a_log"]) * dt
    cs = jnp.cumsum(a, axis=0)
    causal = jnp.tril(jnp.ones((S, S), bool))
    seg = cs[:, None, :] - cs[None, :, :]                         # (i, j, H)
    decay = jnp.where(causal[..., None], jnp.exp(jnp.where(causal[..., None], seg, 0.0)), 0.0)
    cb = N.einsum("ign,jgn->gij", C.reshape(S, g, n), B.reshape(S, g, n), prec)
    cb = jnp.repeat(cb, H // g, axis=0)                           # (H, i, j)
    m = decay.transpose(2, 0, 1) * cb
    y = N.einsum("hij,jhp->ihp", m, dt[..., None] * xh, prec)
    y = y + p["d_skip"][None, :, None] * xh
    y = y.reshape(S, di) * jax.nn.silu(z)
    y = N.rmsnorm(y, p["norm_scale"], GATED_NORM_EPS)
    return N.mm(y, p["w_out"], prec)


def hidden(params, tokens, c: dict, prec: str = "f32"):
    """Final-normed hidden states of one sequence (S,) -> (S, d)."""
    D = dims(c)
    eps = c["norm_epsilon"]
    x = params["embed"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def layer(x, lp):
        h = N.rmsnorm(x, lp["norm1"]["scale"], eps)
        return x + _ssd(lp["ssd"], h, D, prec), None

    x, _ = jax.lax.scan(layer, x, params["unit"][0])
    return N.rmsnorm(x, params["final_norm"]["scale"], eps)


def head(params, c: dict):
    return params["embed"].T if c["tie_embeddings"] else params["lm_head"]


def row_loss(params, tokens, labels, c: dict, prec: str = "f32"):
    """Mean next-token cross-entropy of one sequence."""
    x = hidden(params, tokens, c, prec)
    return N.cross_entropy(N.mm(x, head(params, c), prec), labels)


# ---------------------------------------------------------------------------
# work counts (model FLOPs), from the configuration's shapes
# ---------------------------------------------------------------------------

def forward_flops_per_token(c: dict) -> float:
    """Multiply-adds x 2 of one token's forward pass: the projections, the
    depthwise conv, the chunked SSD (the causal half of each chunk's Q x Q
    products, the chunk states and their read-out) and the output head.
    Norms and elementwise work are not counted."""
    D = dims(c)
    d, di, g, n, H, P, K = D["d"], D["di"], D["g"], D["n"], D["h"], D["p"], D["k"]
    q = c["chunk_size"]
    proj = 2 * d * (2 * di + 2 * g * n + H) + 2 * di * d
    conv = 2 * K * (di + 2 * g * n)
    ssd = 2 * (q / 2) * g * n + 2 * (q / 2) * H * P + 2 * (2 * H * P * n)
    return D["L"] * (proj + conv + ssd) + 2 * d * D["V"]


def train_flops_per_token(c: dict) -> float:
    """One forward and one backward (twice the forward) per trained token;
    recomputation and the selection rule's second pass are not counted."""
    return 3.0 * forward_flops_per_token(c)
