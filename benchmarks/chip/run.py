#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json`` with its plain reference ``configs/<config>.py``)
and a traffic mix (``traffic/<traffic>.json``, whose ``kind`` names the
harness module that runs it, ``harness/<kind>.py``); its limits are
``limits/<workload>.json``. With
``--trace 0`` the last line of stdout carries the cell's end-to-end
metrics; with ``--trace 1`` part of the window is traced and the line
carries the per-layer metrics, each read by ``metrics/<name>.py``.

With no TPU, fewer chips than the cell asks for, or a device kind missing
from ``harness/peaks.json``, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import shutil
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parents[1] / "src"))

from harness import common as C  # noqa: E402


def per_layer(cell, res: C.Result, peaks: dict, trace_dir: Path) -> None:
    """Read the traced part of the window into the per-layer metrics, the
    device's busy time and the breakdown."""
    from harness import trace as T

    tr = T.load(str(trace_dir))
    mark = [h for h in tr.host if h.name == "bench.mark"]
    if not mark:
        raise C.BenchError("the trace holds no bench.mark event")
    offset = mark[0].start - res.window["mark_ns"]
    tr.t0 = mark[0].start
    tr.t1 = res.window["trace_end_ns"] + offset
    tr.host = [T.Ev(n, s + offset, e - s) for n, s, e in res.window["spans"]]
    ctx = {"trace": tr, "offset": offset, "window": res.window, "cell": cell,
           "peaks": peaks, "chips": cell.chips}
    for m in cell.per_layer:
        reader = C.load_module(BENCH_DIR / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            res.metrics[m["name"]] = C.metric(value, m["unit"])
    res.device["busy_s"] = T.mean_busy_s(tr)
    res.device["window_s"] = tr.window_ns / 1e9
    res.breakdown = {"device_ops": T.top_ops(tr, 0), "idle_gaps": T.idle_gaps(tr, 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = C.load_cell(args.workload)
        C.enable_compile_cache()
        devs, peaks = C.devices_for(cell.chips)
    except (C.BenchError, FileNotFoundError, ImportError) as e:
        print(f"[bench] no run: {e}", file=sys.stderr)
        return 2
    kind = importlib.import_module(f"harness.{cell.traffic['kind']}")
    counter = C.CompileCounter()
    trace_dir = C.ROOT / ".bench_trace" / cell.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    res = kind.run(cell, args, devs, peaks, counter, str(trace_dir))
    if args.trace:
        per_layer(cell, res, peaks, trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    C.emit(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
