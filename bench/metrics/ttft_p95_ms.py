"""95th percentile of time to first token over every request due in
the window, timed from when it was due (open loop).  A request that
failed, was refused or never got its first token counts as the longest
wait the run saw (until the drain gave up)."""
from bench.metrics._common import p95


def read(ctx):
    rec = ctx["rec"]
    if rec["kind"] != "serve":
        return None
    end = rec["window_s"] + rec["drain_s"]
    vals = []
    for r in rec["requests"]:
        if not r["in_window"]:
            continue
        first = r["times"][0] if (r["times"] and not r["failed"]) else end
        vals.append(1e3 * (first - r["due"]))
    return p95(vals)
