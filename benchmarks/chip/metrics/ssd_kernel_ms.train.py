"""ssd_kernel_ms.train: device time of the SSD's Pallas kernels (the
``ssd_chunk_fwd`` and ``ssd_chunk_bwd`` custom calls of ``kernels/ssd_scan``,
forward, recompute and backward of both passes) per traced step, mean over
the cell's chips, in ms. A program without the kernels reads nothing."""
from harness import trace as T

KERNEL = r"^%\S*ssd_chunk_(fwd|bwd)\S* = .*custom-call\(.*tpu_custom_call"


def read(ctx):
    tr, w = ctx["trace"], ctx["window"]
    ns = [T.op_ns(T.clip_events(evs, tr.t0, tr.t1), KERNEL) for evs in tr.ops.values()]
    if not ns or sum(ns) == 0:
        return None
    return sum(ns) / len(ns) / 1e6 / w["traced_steps"]
