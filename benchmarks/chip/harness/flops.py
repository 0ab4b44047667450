"""Bytes of the fused top-k / error-feedback compressor, from the leaf
shapes and the preset's block rule (``sasg_ref.block_view``)."""
from __future__ import annotations

import numpy as np

from .sasg_ref import block_k, block_view


def topk_ef_bytes(shapes: list, k_ratio: float, block: int) -> float:
    """Least HBM traffic of one compression of every leaf: the float32
    gradient and error buffer read, the error buffer written, and the
    payload (float32 value, int32 index per kept entry) written."""
    total = 0.0
    for shape in shapes:
        size = int(np.prod(shape))
        blocked = block_view(tuple(shape), block)
        kb = block_k(tuple(shape), blocked, k_ratio)
        kept = size // blocked[-1] * kb
        total += 3 * 4 * size + 8 * kept
    return total
