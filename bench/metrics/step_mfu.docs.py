"""Useful model FLOPs of the traced window (every prompt prefilled at
its actual length with causal attention and the head for its last
position, every decoded token) over the window times the chip's bf16
peak, in %."""
from bench.harness import flops as F
from bench.metrics._common import traced_steps


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    m = ctx["model"]
    total = 0
    for s in traced_steps(ctx):
        total += sum(F.prefill_flops(m, p) for p in s["prefill"])
        total += sum(F.decode_flops(m, kv) for kv in s["decode_kv"])
    if total == 0:
        return None
    return 100.0 * total / (red["window_s"] * ctx["peak"]["bf16_flops_per_s"])
