"""jit'd wrapper: fused EF + block top-k over a flat vector, producing a
SparsePayload and the updated error buffer — drop-in for the unfused
(compress + densify-subtract) path in repro.core.compressors."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.topk import SparsePayload
from repro.core.types import ceil_div, pad_to_multiple

from .topk_ef import topk_ef_pallas


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# f32 elements of one (tile, block) operand tile on TPU, counted with the
# block padded to whole 128-lane vregs: 256 KiB keeps the double-buffered
# in/out tiles plus the kernel's loop temporaries a few MiB, well inside the
# default scoped VMEM
_TILE_ELEMS = 64 * 1024


def _tile_rows(n_rows: int, block: int) -> int:
    """Row tile for ``topk_ef_pallas``, which rounds it up to a multiple of 8
    and zero-pads the rows to a whole number of tiles. Interpret mode runs
    the grid sequentially in the XLA interpreter, so one tile over all rows
    is fastest on CPU. On TPU the tile is the largest power-of-two multiple
    of 8 that fits ``_TILE_ELEMS``: power-of-two tiles divide most leaf row
    counts, so the pad (a copy of the operands) is rarely needed."""
    if _use_interpret():
        return n_rows
    lanes = -(-block // 128) * 128
    tile = 8
    while 2 * tile * lanes <= _TILE_ELEMS:
        tile *= 2
    return min(tile, n_rows)


def block_topk(x: jax.Array, k: int, block_size: int = 2048) -> SparsePayload:
    """Plain block top-k through the fused kernel (zero error, lr=1)."""
    p, _ = topk_ef(x, jnp.zeros_like(x, dtype=jnp.float32), jnp.float32(1.0),
                   k, block_size)
    return p


def blocked_topk_ef(
    grad_blocked: jax.Array,   # (*lead, nbc, block_c) — the per-shard view
    err_blocked: jax.Array,    # same shape, EF accumulator
    kb: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused EF + top-kb on an already shard-aligned blocked view.

    The per-shard transport path: the caller has laid the leaf out as
    ``(*lead, nbc, block_c)`` with block boundaries aligned to the sharded
    axis (``repro.core.topk.blocked_view_shape``), and has folded the
    learning rate into ``grad_blocked`` already (lr=1 here). Returns
    ``(values, indices, new_err)`` with values/indices shaped
    ``(*lead, nbc, kb)`` and block-LOCAL int32 indices — bit-identical to
    the unfused ``blocked_topk`` + scatter-subtract reference (same
    iterative masked-argmax, same first-index tie-break).
    """
    assert grad_blocked.shape == err_blocked.shape
    lead = grad_blocked.shape[:-1]
    bc = grad_blocked.shape[-1]
    rows = 1
    for d in lead:
        rows *= d
    g2 = grad_blocked.reshape(rows, bc).astype(jnp.float32)
    e2 = err_blocked.reshape(rows, bc).astype(jnp.float32)
    new_err, vals, idx = topk_ef_pallas(
        g2, e2, jnp.float32(1.0), kb,
        tile_blocks=_tile_rows(rows, bc), interpret=_use_interpret(),
    )
    return (
        vals.reshape(lead + (kb,)),
        idx.reshape(lead + (kb,)),
        new_err.reshape(grad_blocked.shape),
    )


def topk_ef(
    grad: jax.Array,        # (d,) flat gradient
    err: jax.Array,         # (d,) fp32 error buffer
    lr: jax.Array,          # scalar
    k: int,
    block_size: int = 2048,
) -> tuple[SparsePayload, jax.Array]:
    assert grad.ndim == 1 and err.shape == grad.shape
    d = grad.size
    gp = pad_to_multiple(grad.astype(jnp.float32), block_size)
    ep = pad_to_multiple(err.astype(jnp.float32), block_size)
    nb = gp.size // block_size
    kb = min(max(1, ceil_div(int(min(k, d)), nb)), block_size)
    g2, e2 = gp.reshape(nb, block_size), ep.reshape(nb, block_size)
    # zero the padded tail so it is never selected
    pos = jnp.arange(nb * block_size).reshape(nb, block_size)
    g2 = jnp.where(pos < d, g2, 0.0)
    e2 = jnp.where(pos < d, e2, 0.0)
    new_err, vals, idx = topk_ef_pallas(
        g2, e2, lr, kb, tile_blocks=_tile_rows(nb, block_size), interpret=_use_interpret()
    )
    flat_idx = idx + (jnp.arange(nb, dtype=jnp.int32) * block_size)[:, None]
    in_range = flat_idx < d
    vals = jnp.where(in_range, vals, 0.0)
    flat_idx = jnp.where(in_range, flat_idx, d - 1)
    payload = SparsePayload(vals.reshape(-1), flat_idx.reshape(-1), d)
    return payload, new_err.reshape(-1)[:d]
