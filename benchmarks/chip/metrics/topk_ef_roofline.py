"""topk_ef_roofline: the fused top-k / error-feedback kernel's least time
over its measured time, in %. Least time: the algorithm's bytes per step
(``harness/flops.py``: float32 gradient and error buffer in, error buffer
out, payload out, at the leaf shapes) over the HBM peak, times the traced
steps. Measured time: the summed device time of the kernel's operations on
every chip, divided by the chips. The kernel is the Mosaic custom call
that returns (float32 error buffer, float32 values, int32 indices)."""
from harness import trace as T

KERNEL = (r"^%\S+ = \(f32\[\d+,\d+\]\{[^}]*\}, f32\[\d+,\d+\]\{[^}]*\}, "
          r"s32\[\d+,\d+\]\{[^}]*\}\) custom-call\(.*tpu_custom_call")


def read(ctx):
    tr, w = ctx["trace"], ctx["window"]
    ns = [T.op_ns(T.clip_events(evs, tr.t0, tr.t1), KERNEL) for evs in tr.ops.values()]
    if not ns or sum(ns) == 0:
        return None
    least = w["traced_steps"] * w["topk_ef_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(ns) / len(ns) / 1e9)
