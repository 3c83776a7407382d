"""Block drivers: device idle time per traced sweep between its first and
last device operation, averaged over the chips."""
from bench import layers


def read(ctx):
    return layers.block_gap_s(ctx, "sweep")
