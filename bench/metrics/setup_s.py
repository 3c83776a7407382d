"""Set-up: process start to the start of the measured window (JAX and TPU
init, data from the seed, compile or cache load, one warm-up request)."""


def read(ctx):
    return ctx.setup_s
