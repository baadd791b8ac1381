"""Share of the traced stretch in which no operation ran on the device
(one device; on several, their mean)."""
UNIT, LAYER, MOVES, SOURCE = "%", "Device", "throughput", "device_trace"


def read(ctx):
    if ctx.trace is None or not ctx.trace.window_s:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
