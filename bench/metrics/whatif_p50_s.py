"""Median latency of the window's what-if queries, each timed from
``whatif.run_grid`` to its ``table2_rows``."""
from bench import layers


def read(ctx):
    return layers.latency_percentile(ctx, "whatif", 50.0)
