"""Seconds from the start of the process to the opening of the window:
loading, weights, compiling or loading every program from the cache,
warm-up and the pre-roll of traffic."""


def read(ctx):
    return ctx["setup_s"]
