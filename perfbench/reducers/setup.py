"""Seconds from process start to the window's opening: boot, weights,
compile-cache loads, correctness check, warm-up and lead-in."""


def reduce(ctx):
    return ctx["setup_s"]
