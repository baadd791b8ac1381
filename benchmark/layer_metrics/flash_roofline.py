"""The Pallas attention kernels' share of their roofline: the least time
the chip could take for one step's attention (the larger of FLOPs over
peak and bytes over HBM bandwidth, both from the configuration's
flops.flash_kernel_cost) over the device time of the kernels' events in
the traced stretch.  The earlier line says which of the two bounds."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"


def read(ctx):
    cost = getattr(ctx.flops, "flash_kernel_cost", None)
    if ctx.trace is None or cost is None or not ctx.trace.kernel_s:
        return None
    steps = len(ctx.traced.stamps)
    flops, bytes_ = cost(ctx.config, ctx.traced.global_batch // ctx.traced.chips)
    by_flops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"flash kernels: {ctx.trace.kernel_s / steps * 1e3:.3f} ms a step; "
            f"least by FLOPs {by_flops * 1e3:.3f} ms, by bytes "
            f"{by_bytes * 1e3:.3f} ms: "
            f"{'compute' if by_flops > by_bytes else 'memory'}-bound")
    return 100.0 * max(by_flops, by_bytes) * steps / ctx.trace.kernel_s
