"""Paged attention kernel: the least time its useful work needs on the
chip (live slots' cached keys and values read once, or their FLOPs at
peak, whichever is larger) over its device time in the traced window,
in %."""
from bench.harness import flops as F
from bench.metrics._common import traced_steps


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    sec = red["kernels"]["paged_attn"]["seconds"]
    kv = [x for s in traced_steps(ctx) for x in s["decode_kv"]]
    if sec <= 0 or not kv:
        return None
    fl, by = F.paged_decode(ctx["model"], kv)
    return F.roofline_share(fl, by, sec, ctx["peak"])
