"""The masked attention kernels' share of their roofline: the least time
the chip could take for the live (query, key) pairs of one step's
attention (the larger of FLOPs over peak and bytes over HBM bandwidth,
both from the configuration's flops.mask_flash_kernel_cost, which counts
live pairs only) over the device time of the ``hvd_flash_*`` kinds in the
traced stretch.  The earlier line says which of the two bounds, and how
much of the time each kernel took."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"


def read(ctx):
    cost = getattr(ctx.flops, "mask_flash_kernel_cost", None)
    if ctx.trace is None or cost is None:
        return None
    kinds = {k: s for k, s in ctx.trace.device_ops if k.startswith("hvd_flash")}
    if not kinds or not sum(kinds.values()):
        return None
    steps = len(ctx.traced.stamps)
    flops, bytes_ = cost(ctx.config, ctx.traced.global_batch // ctx.traced.chips)
    by_flops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.say("masked flash kernels, ms a step: " + ", ".join(
        f"{k} {s / steps * 1e3:.3f}" for k, s in sorted(kinds.items()))
        + f"; least by FLOPs {by_flops * 1e3:.3f} ms, by bytes "
        f"{by_bytes * 1e3:.3f} ms: "
        f"{'compute' if by_flops > by_bytes else 'memory'}-bound")
    return 100.0 * max(by_flops, by_bytes) * steps / sum(kinds.values())
