"""What-if front end: host time per traced sweep before its first and
after its last device operation (staging, dedup, block plan, scatter,
summary)."""
from bench import layers


def read(ctx):
    return layers.frontend_s(ctx, "sweep")
