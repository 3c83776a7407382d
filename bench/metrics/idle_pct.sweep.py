"""Device: share of the traced window in which a chip ran no operation,
averaged over the cell's chips (traced sweep requests)."""
from bench import layers


def read(ctx):
    return layers.idle_pct(ctx, "sweep")
