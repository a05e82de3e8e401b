"""Prompt and output tokens completed in the window over its length:
a prompt counts when its first token is handed over, an output token
when it is handed over."""


def read(ctx):
    rec = ctx["rec"]
    if rec["kind"] != "serve":
        return None
    W = rec["window_s"]
    tokens = 0
    for r in rec["requests"]:
        t = r["times"]
        if t and 0.0 <= t[0] <= W:
            tokens += r["plen"]
        tokens += sum(1 for x in t if 0.0 <= x <= W)
    return tokens / W
