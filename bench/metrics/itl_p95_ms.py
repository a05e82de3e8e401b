"""95th percentile of the gaps between consecutive output tokens of a
request, over every gap that ends inside the window."""
from bench.metrics._common import p95


def read(ctx):
    rec = ctx["rec"]
    if rec["kind"] != "serve":
        return None
    W = rec["window_s"]
    gaps = []
    for r in rec["requests"]:
        t = r["times"]
        for a, b in zip(t, t[1:]):
            if 0.0 <= b <= W:
                gaps.append(1e3 * (b - a))
    return p95(gaps)
