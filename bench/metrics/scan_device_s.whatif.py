"""Scan engines: device time per traced whatif of the scan programs, per
chip, matched by the names in bench.layers.SCAN_PROGRAMS; the traced
run's breakdown shows the device time no name matched."""
from bench import layers


def read(ctx):
    return layers.scan_device_s(ctx, "whatif")
