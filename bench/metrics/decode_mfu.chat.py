"""Useful decode FLOPs of the traced window (every live slot's token:
all layers' matrix products, attention over its cached length, the
head) over the window times the chip's bf16 peak, in %."""
from bench.harness import flops as F
from bench.metrics._common import traced_steps


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    m = ctx["model"]
    total = sum(F.decode_flops(m, kv) for s in traced_steps(ctx)
                for kv in s["decode_kv"])
    if total == 0:
        return None
    return 100.0 * total / (red["window_s"] * ctx["peak"]["bf16_flops_per_s"])
