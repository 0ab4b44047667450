"""mfu.train: the whole step's share of the chip's bf16 peak, in %. Model
FLOPs of one forward and one backward per trained token (the
configuration's ``train_flops_per_token``) times the tokens of the traced
steps, over their host-clock time (from the window's mark to the step
after which the trace stops) x chips x the peak. It is the traced part of
``train_tokens_per_s`` in FLOPs, so it counts the loop's idle time with
the step's."""
from harness.readings import traced_seconds


def read(ctx):
    w = ctx["window"]
    tokens = w["traced_steps"] * w["tokens_per_step"]
    rate = w["flops_per_token"] * tokens / traced_seconds(ctx)
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
