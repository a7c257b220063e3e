"""Seconds from process start to the window: JAX start, the caches,
compiles where the checkout has none, the warm-up requests."""


def read(ctx):
    return ctx["setup_s"]
