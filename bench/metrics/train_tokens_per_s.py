"""Tokens of every step whose loss reached the host in the window, over
the window (which ends when the last of them did)."""


def read(ctx):
    rec = ctx["rec"]
    if rec["kind"] != "train":
        return None
    return rec["steps_done"] * rec["tokens_per_step"] / rec["window_s"]
