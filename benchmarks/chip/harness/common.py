"""Shared pieces of the chip benchmark: the cell lookup, the device check
against the peak table, the compile cache, the compile counter, host spans,
seeds and the result line.

Nothing here touches JAX at import time: ``run.py`` configures the compile
cache before the first backend call.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
import zlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]   # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                        # the checkout
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """A run that cannot produce a result: no chip, an unknown device, a
    cell that is not defined."""


_T0 = time.perf_counter()


def progress(msg: str) -> None:
    """A set-up phase done, on stderr, with the seconds since start-up."""
    print(f"[bench] +{time.perf_counter() - _T0:.1f}s {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# cell lookup
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                # configs/<name>.json
    traffic_name: str
    traffic: dict               # traffic/<name>.json
    limits: dict                # limits/<workload>.json
    end_to_end: list            # BENCHMARK.json metric entries of this cell
    per_layer: list
    reference: Any = None       # configs/<name>.py, loaded


def load_module(path: Path, name: Optional[str] = None):
    """Import a file of the benchmark by path (file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric_applies(m: dict, cell: str, reported: set) -> bool:
    if "workloads" in m:
        return cell in m["workloads"]
    return m.get("moves", m["name"]) in reported


def load_cell(workload: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_path.read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchError(f"no workload {workload!r} in {bench_path.name}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((BENCH_DIR / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _metric_applies(m, workload, reported)]
    ref = load_module(ROOT / cfg_entry["file"].replace(".json", ".py"),
                      "bench_ref_" + w["config"])
    return Cell(workload, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, limits, e2e, per_layer, ref)


# ---------------------------------------------------------------------------
# device, peaks, cache
# ---------------------------------------------------------------------------

def peak_table() -> dict:
    return json.loads((BENCH_DIR / "harness" / "peaks.json").read_text())["devices"]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins when set). Every executable is
    cached, however short its compile, so that a warm run builds none."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices_for(chips: int):
    """The first ``chips`` devices and their peaks. Raises BenchError when
    the platform is not a TPU, when fewer chips are present than the cell
    asks for, or when the device kind is not in the peak table."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform} ({kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    table = peak_table()
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in the peak table")
    return devs[:chips], table[kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Executables JAX builds (compiled, or loaded from the persistent
    cache: the ``backend_compile_duration`` event), the seconds spent on
    them, and how many of them the persistent cache served."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n = 0
        self.secs = 0.0
        self.hits = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, fun_name="", **_):
        if event == self.EVENT:
            self.n += 1
            self.secs += duration
            self.names.append(fun_name)

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.n, self.hits, self.secs


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def seed_words(seed: int, n: int = 2) -> list:
    """``n`` 31-bit words drawn from any whole-number seed."""
    import numpy as np

    return [int(w) & 0x7FFFFFFF
            for w in np.random.SeedSequence(int(seed)).generate_state(n)]


def jax_key(seed: int, tag: str = ""):
    import jax

    a, b = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(a), b)
    return jax.random.fold_in(key, zlib.crc32(tag.encode()) & 0x7FFFFFFF) if tag else key


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

class Spans:
    """Host spans around the calls into each layer. With ``on`` they are
    also written into the profiler trace (``TraceAnnotation``), where the
    trace reduction attributes idle gaps to them; every span's host
    interval is kept either way."""

    def __init__(self, on: bool):
        self.on = on
        self.records: list = []     # (name, t0_ns, t1_ns) on perf_counter_ns

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter_ns()
        if self.on:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter_ns()))


# ---------------------------------------------------------------------------
# the comparison and the result line
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared with its limit (``value <= limit`` passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Result:
    attempted: int = 0
    failed: int = 0
    checks: list = field(default_factory=list)
    metrics: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)
    breakdown: Optional[dict] = None
    notes: dict = field(default_factory=dict)
    window: dict = field(default_factory=dict)   # what the metric readers read

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) and self.failed == 0

    def line(self) -> dict:
        out = {
            "correct": self.correct,
            "attempted": int(self.attempted),
            "failed": int(self.failed),
            "metrics": self.metrics,
            "device": self.device,
        }
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                         for c in self.checks}
        return out


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def emit(result: Result) -> None:
    """Notes on earlier lines, each compared number beside its limit as the
    last lines of stderr, and the result as the last line of stdout."""
    for k, v in result.notes.items():
        print(f"[bench] {k}: {v}", file=sys.stderr)
    for c in result.checks:
        print(f"[check] {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    print(f"[check] correct = {result.correct}", file=sys.stderr, flush=True)
    print(json.dumps(result.line()), flush=True)

