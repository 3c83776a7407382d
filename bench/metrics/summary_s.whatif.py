"""What-if front end: host seconds of a what-if query in the program's spans
after the scan (``grid.scatter``, ``grid.summarise``, ``whatif.table2``),
from the request ``repro.obs`` records in a traced run."""
from bench import program_spans as ps


def read(ctx):
    return ps.span_s(ctx, "whatif", ps.SUMMARY_SPANS)
