"""The chunked delta-rule scan's share of its roofline: the least time the
chip could take for one step's scans (the larger of FLOPs over peak and
bytes over HBM bandwidth, both from the configuration's
flops.kda_kernel_cost, which counts what the chunked form needs at the
least: a head and chunk's two score tiles, its pseudo-values, one more
product of a tile and the three products with the state, the forward
again where remat reruns it, two products in the backward for each forward
one; operands read and results written once a pass) over the device time
under the scope ``hvd_kda_scan`` in the traced stretch, every pass: the
kernels and what the call puts around them (the tiles of every chunk, the
running sums, the triangular inverse), whoever computes it.  The earlier
line gives each row's time a step and which bound it took."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"

from harness import scopes

SCOPE = "hvd_kda_scan"


def read(ctx):
    cost = getattr(ctx.flops, "kda_kernel_cost", None)
    if ctx.trace is None or cost is None:
        return None
    table = scopes.table(ctx)
    seconds = table.seconds(SCOPE) if table is not None else 0.0
    if not seconds:
        return None
    flops, bytes_ = cost(ctx.config, ctx.traced.global_batch // ctx.traced.chips)
    by_flops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ms = 1e3 / table.steps
    rows = sorted(((s, f"{sc.split(SCOPE)[-1].strip('/') or 'xla'} {p}")
                   for (sc, p), s in table.rows.items()
                   if SCOPE in sc.split("/")), reverse=True)
    ctx.say("chunked delta-rule scan, ms a step: " + ", ".join(
        f"{name} {s * ms:.3f}" for s, name in rows)
        + f"; least by FLOPs {by_flops * 1e3:.3f} ms, by bytes "
        f"{by_bytes * 1e3:.3f} ms: "
        f"{'compute' if by_flops > by_bytes else 'memory'}-bound")
    return 100.0 * max(by_flops, by_bytes) * table.steps / seconds
