"""Device time of the decode program (the module that runs the paged
attention kernel) per decode step in the traced window."""
from bench.harness.trace import module_seconds
from bench.metrics._common import traced_steps


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    n = sum(1 for s in traced_steps(ctx) if s["decode_kv"])
    sec = module_seconds(red, "paged_attn")
    if n == 0 or sec <= 0:
        return None
    return 1e3 * sec / n
