"""Block drivers: host seconds of a sweep in its ``grid.upload`` spans, the
copies of the load matrix (and fault rows) to the device, from the
request ``repro.obs`` records in a traced run."""
from bench import program_spans as ps


def read(ctx):
    return ps.span_s(ctx, "sweep", ps.UPLOAD_SPANS)
