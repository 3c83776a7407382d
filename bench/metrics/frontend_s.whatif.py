"""What-if front end: host time per traced whatif before its first and
after its last device operation (staging, dedup, block plan, scatter,
summary, traffic curves, Table II rows)."""
from bench import layers


def read(ctx):
    return layers.frontend_s(ctx, "whatif")
