"""The same FLOPs over the time the device was busy in the traced
stretch, not the wall clock: what the kernels reach while they run."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"


def read(ctx):
    if ctx.trace is None or not ctx.trace.busy_s:
        return None
    flops = (len(ctx.traced.stamps) * ctx.traced.global_batch
             * ctx.flops_per_sample / ctx.traced.chips)
    return 100.0 * flops / (ctx.trace.busy_s * ctx.peaks["bf16_flops_per_s"])
