"""Throwaway metric of the data-driven test: requests whose first token
came in the window, per second."""


def read(ctx):
    rec = ctx["rec"]
    W = rec["window_s"]
    return sum(1 for r in rec["requests"]
               if r["times"] and 0.0 <= r["times"][0] <= W) / W
