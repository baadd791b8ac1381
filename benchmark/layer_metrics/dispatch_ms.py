"""Host time inside the call of the program's step function, median per
step over the window: what the host pays to enqueue one step."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Train-step assembly", "throughput", "host_clock"

import statistics


def read(ctx):
    return statistics.median(ctx.main.dispatch_s) * 1e3
