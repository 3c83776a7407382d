"""Planning-sweep throughput: scenario-years simulated by every sweep the
window completed, over those sweeps' total wall time, each sweep timed
from the ``simulate_grid`` call to its ``GridSummary`` rows."""
from bench import layers


def read(ctx):
    sweeps = layers.done(ctx, "sweep")
    wall = sum(r.end - r.start for r in sweeps)
    return sum(r.work for r in sweeps) / wall if wall > 0 else None
