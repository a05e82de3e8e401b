"""Device time of the prefill programs (the modules that run the flash
attention kernel) per 1,000 useful prompt tokens prefilled in the
traced window (actual prompt lengths, not the padded buckets)."""
from bench.harness.trace import module_seconds
from bench.metrics._common import traced_steps


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    toks = sum(p for s in traced_steps(ctx) for p in s["prefill"])
    sec = module_seconds(red, "flash_fwd")
    if toks == 0 or sec <= 0:
        return None
    return 1e3 * sec / (toks / 1e3)
