"""The experts' grouped matrix products' share of their roofline: the
least time the chip could take for the (token, expert) pairs the steps
counted (the larger of FLOPs over peak and bytes over HBM bandwidth, both
from the configuration's flops.moe_kernel_cost over
``hvd_moe_routed_total``) over the device time of every grouped-product
kind in the traced stretch: the program's own Pallas kernels
(``hvd_moe_gmm*``, ``hvd_moe_tgmm*``) and XLA's (``ragged-dot*``)
together, so a program that has only XLA's reads what ``moe_experts_ms``'s
line said of it, and one that keeps a product on XLA's stays honest.  The
earlier line says which bound, and how much of the time each kind took."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"
KINDS = ("hvd_moe_gmm", "hvd_moe_tgmm", "ragged-dot")


def _routed():
    try:
        from horovod_tpu import metrics
    except ImportError:
        return {}
    family = metrics.registry().to_dict().get("hvd_moe_routed_total")
    return {s["labels"]["what"]: s["value"] for s in (family or {}).get("series", [])}


def read(ctx):
    cost = getattr(ctx.flops, "moe_kernel_cost", None)
    if ctx.trace is None or cost is None:
        return None
    kinds = {k: s for k, s in ctx.trace.device_ops if k.startswith(KINDS)}
    routed = _routed()
    if not sum(kinds.values()) or not routed.get("layers"):
        return None
    steps = len(ctx.traced.stamps)
    flops, bytes_ = cost(ctx.config, routed["pairs"] / routed["layers"])
    layers = ctx.config["num_hidden_layers"]
    by_flops = layers * flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = layers * bytes_ / ctx.peaks["hbm_bytes_per_s"]
    ctx.say("grouped products, ms a step: " + ", ".join(
        f"{k} {s / steps * 1e3:.3f}" for k, s in sorted(kinds.items()))
        + f"; least by FLOPs {by_flops * 1e3:.3f} ms, by bytes "
        f"{by_bytes * 1e3:.3f} ms: "
        f"{'compute' if by_flops > by_bytes else 'memory'}-bound")
    return 100.0 * max(by_flops, by_bytes) * steps / sum(kinds.values())
