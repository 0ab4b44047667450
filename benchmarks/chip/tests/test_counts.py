"""The work counts the metrics divide by, against hand counts at the
configuration's published shapes.

  python -m pytest benchmarks/chip/tests/test_counts.py
"""
from __future__ import annotations

import json

import tiny  # noqa: F401  (puts the harness on the path)
from harness import common as C
from harness.flops import topk_ef_bytes
from harness.sasg_ref import block_k, block_view


def _cfg(name):
    ref = C.load_module(C.BENCH_DIR / "configs" / f"{name}.py", f"count_{name}")
    return ref, json.loads((C.BENCH_DIR / "configs" / f"{name}.json").read_text())


def test_mamba2_forward_flops_hand_count():
    ref, c = _cfg("mamba2_370m")
    # d 1024, d_inner 2048, one group, state 128, 32 heads of 64, conv 4,
    # chunk 256, 48 layers, 50280 rows
    proj = 2 * 1024 * (2 * 2048 + 2 * 128 + 32) + 2 * 2048 * 1024      # 13,172,736
    conv = 2 * 4 * (2048 + 2 * 128)                                     # 18,432
    ssd = 2 * 128 * 128 + 2 * 128 * 32 * 64 + 2 * 2 * 32 * 64 * 128    # 1,605,632
    want = 48 * (proj + conv + ssd) + 2 * 1024 * 50280                  # 813,219,840
    assert want == 813_219_840
    assert ref.forward_flops_per_token(c) == want
    assert ref.train_flops_per_token(c) == 3 * want


def test_topk_ef_bytes_hand_count():
    # mamba2's stacked in-projection (48, 1024, 4384): 4384 = 32 x 137, so
    # blocks of 137 (the largest divisor not above 256), 1,572,864 blocks,
    # k = round(0.01 x 215,482,368) = 2,154,824, so 2 per block
    shape = (48, 1024, 4384)
    assert block_view(shape, 256) == (48, 1024, 32, 137)
    assert block_k(shape, (48, 1024, 32, 137), 0.01) == 2
    size = 48 * 1024 * 4384
    want = 3 * 4 * size + 8 * (size // 137) * 2
    assert want == 2_610_954_240
    assert topk_ef_bytes([shape], 0.01, 256) == want
    # a vector of 32 heads: one block of 32, k = max(1, round(0.32)) = 1
    assert block_view((32,), 256) == (1, 32)
    assert topk_ef_bytes([(32,)], 0.01, 256) == 3 * 4 * 32 + 8 * 1
