"""Host time to take the next batch and hand it to the device, median
per step over the window.  Only traffic that crosses from the host every
step has anything to read."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Entry points", "throughput", "host_clock"

import statistics


def read(ctx):
    if ctx.cell.traffic["resident"] != "host":
        return None
    return statistics.median(ctx.main.feed_s) * 1e3
