#!/usr/bin/env python3
"""The readings each limit in ``limits/`` is set from, taken on the chip at
the cell's own size:

  python3 benchmarks/chip/tests/readings.py --workload <name> --seeds 1,2,...
      [--control-seeds 1,2,3] [--out file.json]

In one process it runs the program's set-up steps on every seed, frees
it, then runs the plain reference on every seed, uploading where the
program did. On the control seeds it also runs the control (the reference
in float8 in the program's place) and the planted fault that leaves out
half of each worker's rows, each with its own rule, and the reference
again uploading where each of them did. It prints one JSON object: each
run's compared numbers and the exact rule at the last set-up step, by
seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parents[1] / "src"))

from harness import common as C  # noqa: E402


def train_readings(cell, devs, seeds, control_seeds) -> dict:
    import jax

    from harness import train as TR

    out = {"program": {}, "control": {}, "half_batch": {}}
    prog = TR.Program(cell, devs, C.Spans(False))
    progs, batches = {}, {}
    for s in seeds:
        key = C.jax_key(s, "weights")
        hb = TR.batch_list(cell, s, TR.SETUP_STEPS)
        batches[s] = hb
        shard = prog.built.batch_sharding_fn(hb[0])
        prog.trainer.data = TR.Feed([jax.device_put(b, shard) for b in hb])
        prog.trainer.history.clear()
        state = prog.make_state(key)
        state, progs[s] = TR.readings_program(prog, state)
        del state
        print(f"program seed {s}: loss {progs[s]['loss']} sent {progs[s]['sent']}",
              file=sys.stderr, flush=True)
    del prog
    gc.collect()

    def versus(run: dict, s: int) -> dict:
        """``run`` compared with the reference uploading where it did."""
        t0 = time.perf_counter()
        ref = TR.reference_readings(cell, s, batches=batches[s], forced=run["sent"])
        nums = TR.compare(run, ref)
        nums.update(reference_s=time.perf_counter() - t0, sent=run["sent"],
                    rule=ref["rule"], window=run["window"], ref_window=ref["window"],
                    norms={k: {"program": run[k], "reference": ref[k]}
                           for k in ("update_norms", "ef_norms", "change1_norms",
                                     "change_norms", "last_norms")})
        return nums

    for s in seeds:
        out["program"][s] = versus(progs[s], s)
        if s in control_seeds:
            for name, kw in (("control", {"prec": "fp8"}), ("half_batch", {"half_batch": True})):
                out[name][s] = versus(TR.reference_readings(cell, s, batches=batches[s], **kw), s)
        print(f"seed {s}: {json.dumps({k: out[k].get(s) for k in out}, default=str)}",
              file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    control = {int(x) for x in args.control_seeds.split(",") if x}
    cell = C.load_cell(args.workload)
    C.enable_compile_cache()
    devs, _ = C.devices_for(cell.chips)
    out = train_readings(cell, devs, seeds, control)
    text = json.dumps(out, indent=1, default=str)
    if args.out:
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
