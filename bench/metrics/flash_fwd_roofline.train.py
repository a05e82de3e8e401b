"""Flash attention forward kernel in the train step: the least time the
useful causal attention of the traced steps needs on the chip over the
kernel's device time, in %."""
from bench.harness import flops as F


def read(ctx):
    red, rec = ctx["trace"], ctx["rec"]
    if red is None or rec["kind"] != "train" or not rec["traced_steps"]:
        return None
    sec = red["kernels"]["flash_fwd"]["seconds"]
    if sec <= 0:
        return None
    t = ctx["traffic"]
    fl, by = F.flash_fwd(ctx["model"], [t["seq"]] * t["batch"] * rec["traced_steps"])
    return F.roofline_share(fl, by, sec, ctx["peak"])
