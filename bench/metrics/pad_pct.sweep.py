"""Block drivers: the share of scenario slots run that were padding,
counting the mesh's dummy blocks (``grid.block`` / ``grid.round`` spans of
the traced sweeps)."""
from bench import layers


def read(ctx):
    return layers.pad_pct(ctx)
