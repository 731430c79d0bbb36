"""setup_s: process start to the first measured instant (JAX and chip
start-up, weights, gateway, ladder compile or cache load, warm-up wave)."""


def read(ctx):
    return ctx.setup_s
