"""Useful FLOPs of the train steps in the traced window (forward and
backward, causal attention as half, no recomputation) over the window
times the chip's bf16 peak, in %."""
from bench.harness import flops as F


def read(ctx):
    red, rec = ctx["trace"], ctx["rec"]
    if red is None or rec["kind"] != "train" or not rec["traced_steps"]:
        return None
    t = ctx["traffic"]
    total = F.train_flops(ctx["model"], t["batch"], t["seq"]) * rec["traced_steps"]
    return 100.0 * total / (red["window_s"] * ctx["peak"]["bf16_flops_per_s"])
