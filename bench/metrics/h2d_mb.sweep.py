"""Block drivers: megabytes a sweep copies from host to device (the
``grid.h2d_bytes`` counter per ``grid.simulate`` span), from the request
``repro.obs`` records in a traced run."""
from bench import program_spans as ps


def read(ctx):
    return ps.h2d_mb(ctx, "sweep")
