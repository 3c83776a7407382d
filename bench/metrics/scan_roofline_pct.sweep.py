"""Scan engines: the least time the chips need for a traced sweep's scan
(``bench.roofline``: work and bytes counted from the algorithm, the
larger of work over peak operations and bytes over peak bandwidth) over
the scan programs' device time per chip."""
from bench import layers, roofline


def read(ctx):
    scan_s = layers.scan_device_s(ctx, "sweep")
    if not scan_s or ctx.peaks is None:
        return None
    policies = ctx.cfg["policies"]
    base = ctx.mix["rows"] // ctx.mix.get("futures", 1)
    rows = {p: len(range(j, base, len(policies))) * ctx.mix.get("futures", 1)
            for j, p in enumerate(policies)}
    ops, nbytes = roofline.scan_work(rows, ctx.cfg["horizon_bins"],
                                     ctx.mix.get("futures", 1) > 1)
    least, _ = layers.least_time_s(ops, nbytes, ctx.peaks, ctx.chips)
    return 100.0 * least / scan_s
