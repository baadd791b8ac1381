"""Device time of the experts' grouped matrix products, a step: the kinds
XLA names ``ragged-dot*`` in the traced stretch (its own grouped-product
kernels and the metadata pass before each; the program wrote no kernel of
its own for them).  The earlier line gives their share of the roofline of
the pairs the steps counted (flops.moe_kernel_cost over
``hvd_moe_routed_total``), and which bound."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Kernels", "throughput", "device_trace"


def _routed():
    try:
        from horovod_tpu import metrics
    except ImportError:
        return {}
    family = metrics.registry().to_dict().get("hvd_moe_routed_total")
    return {s["labels"]["what"]: s["value"] for s in (family or {}).get("series", [])}


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = sum(s for k, s in ctx.trace.device_ops if k.startswith("ragged-dot"))
    if not seconds:
        return None
    steps = len(ctx.traced.stamps)
    routed, cost = _routed(), getattr(ctx.flops, "moe_kernel_cost", None)
    if cost is not None and routed.get("layers"):
        flops, bytes_ = cost(ctx.config, routed["pairs"] / routed["layers"])
        layers = ctx.config["num_hidden_layers"]
        by_flops = layers * flops / ctx.peaks["bf16_flops_per_s"]
        by_bytes = layers * bytes_ / ctx.peaks["hbm_bytes_per_s"]
        ctx.say(f"grouped products: {seconds / steps * 1e3:.3f} ms a step; least by "
                f"FLOPs {by_flops * 1e3:.3f} ms, by bytes {by_bytes * 1e3:.3f} ms: "
                f"{100 * max(by_flops, by_bytes) * steps / seconds:.1f}% of the "
                f"{'compute' if by_flops > by_bytes else 'memory'} bound")
    return seconds / steps * 1e3
