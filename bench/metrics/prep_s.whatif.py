"""What-if front end: host seconds of a what-if query in the program's spans
before the scan (``whatif.loads``, ``grid.params``, ``grid.dedup``,
``grid.plan``), from the request ``repro.obs`` records in a traced run."""
from bench import program_spans as ps


def read(ctx):
    return ps.span_s(ctx, "whatif", ps.PREP_SPANS)
