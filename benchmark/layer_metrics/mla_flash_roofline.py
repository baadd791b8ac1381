"""Latent attention's kernels' share of their roofline: the least time
the chip could take for the causal (query, key) pairs of one step's latent
attention (the larger of FLOPs over peak and bytes over HBM bandwidth, both
from the configuration's flops.mla_flash_kernel_cost: each product once at
the model's 192 + 128, the shared rotary key read as one head) over the
device time of the ``hvd_flash_*`` kernels' rows of the scope table under
``hvd_mla_attention``.  The earlier line says which of the two bounds and
each kernel's time a step.  None without a trace, where the configuration
has no such cost or the program no such scope (the parent of PR 49)."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"

from harness import scopes

SCOPE, KERNELS = "hvd_mla_attention", "hvd_flash"


def kernel_seconds(table):
    """{kernel: seconds of the traced stretch} of the flash kernels' rows
    under the scope."""
    found = {}
    for (path, _), s in table.rows.items():
        parts = path.split("/")
        kernel = next((p for p in parts if p.startswith(KERNELS)), None)
        if SCOPE in parts and kernel:
            found[kernel] = found.get(kernel, 0.0) + s
    return found


def read(ctx):
    cost = getattr(ctx.flops, "mla_flash_kernel_cost", None)
    if ctx.trace is None or cost is None:
        return None
    table = scopes.table(ctx)
    kernels = kernel_seconds(table) if table is not None else {}
    if not sum(kernels.values()):
        return None
    flops, bytes_ = cost(ctx.config, ctx.traced.global_batch // ctx.traced.chips)
    by_flops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.say("latent attention's flash kernels, ms a step: " + ", ".join(
        f"{k} {s / table.steps * 1e3:.3f}" for k, s in sorted(kernels.items()))
        + f"; least by FLOPs {by_flops * 1e3:.3f} ms, by bytes "
        f"{by_bytes * 1e3:.3f} ms: "
        f"{'compute' if by_flops > by_bytes else 'memory'}-bound")
    return (100.0 * max(by_flops, by_bytes) * table.steps
            / sum(kernels.values()))
