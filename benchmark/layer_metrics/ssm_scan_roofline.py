"""The selective-scan kernels' share of their roofline: the least time the
chip could take for one step's scans (HBM bytes over bandwidth, from the
configuration's flops.ssm_scan_kernel_cost: operands read and results
written once forward and once backward; the peaks table prices no vector
unit, so the recurrence's own operations bound nothing here and the share
reads low) over the device time of the ``hvd_ssm_scan*`` kinds in the
traced stretch.  The earlier line gives each kernel's time a step."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"


def read(ctx):
    cost = getattr(ctx.flops, "ssm_scan_kernel_cost", None)
    if ctx.trace is None or cost is None:
        return None
    kinds = {k: s for k, s in ctx.trace.device_ops
             if k.startswith("hvd_ssm_scan")}
    if not kinds or not sum(kinds.values()):
        return None
    steps = len(ctx.traced.stamps)
    ops, bytes_ = cost(ctx.config, ctx.traced.global_batch // ctx.traced.chips)
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.say("selective-scan kernels, ms a step: " + ", ".join(
        f"{k} {s / steps * 1e3:.3f}" for k, s in sorted(kinds.items()))
        + f"; least by bytes {by_bytes * 1e3:.3f} ms (bytes bound only: "
        f"{ops / 1e9:.1f} G elementwise operations a step are not priced)")
    return 100.0 * by_bytes * steps / sum(kinds.values())
