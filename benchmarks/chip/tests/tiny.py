"""A tiny version of the benchmark's training cell, for the tests on the
CPU: the same files and harness modules, with every size cut so that a
test run holds them."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parents[1] / "src"))

from harness import common as C  # noqa: E402

TINY_MAMBA = {"d_model": 64, "n_layer": 2, "vocab_size": 256, "d_state": 16,
              "headdim": 16, "chunk_size": 32}


def _load(path):
    return json.loads((BENCH_DIR / path).read_text())


def mamba_cell(workers: int = 1, seq: int = 64, rows: int = 2) -> C.Cell:
    cfg = copy.deepcopy(_load("configs/mamba2_370m.json"))
    cfg.update(TINY_MAMBA)
    f = cfg["program"]["fields"]
    f.update(n_layers=2, d_model=64, vocab_size=256)
    cfg["program"]["ssm"].update(d_state=16, head_dim=16, chunk_size=32)
    t = _load("traffic/train_sasg.json")
    t.update(workers=workers, rows_per_worker=rows, seq=seq, min_step_s=0.05,
             trace_steps=1)
    ref = C.load_module(BENCH_DIR / "configs" / "mamba2_370m.py", "tiny_mamba_ref")
    return C.Cell("tiny.train", workers, "mamba2_370m", cfg, "train_sasg", t,
                  _load("limits/mamba2_370m.train.sasg.json"), [], [], ref)


class Args:
    def __init__(self, seed=7, seconds=2.0, trace=0):
        self.seed, self.seconds, self.trace = seed, seconds, trace
