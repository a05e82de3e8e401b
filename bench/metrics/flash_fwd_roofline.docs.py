"""Flash attention forward kernel in prefill: the least time the useful
causal attention of the traced window's prompts needs on the chip, at
their actual lengths, over the kernel's device time, in %."""
from bench.harness import flops as F
from bench.metrics._common import traced_steps


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    sec = red["kernels"]["flash_fwd"]["seconds"]
    lens = [p for s in traced_steps(ctx) for p in s["prefill"]]
    if sec <= 0 or not lens:
        return None
    fl, by = F.flash_fwd(ctx["model"], lens)
    return F.roofline_share(fl, by, sec, ctx["peak"])
