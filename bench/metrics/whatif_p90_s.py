"""90th percentile latency of all the window's what-if queries."""
from bench import layers


def read(ctx):
    return layers.latency_percentile(ctx, "whatif", 90.0)
