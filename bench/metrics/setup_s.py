"""Seconds from process start to the first timed request: load, publish,
warm-up (compilation or compile-cache reads included)."""


def read(ctx):
    return ctx["setup_s"]
