"""The fullest held expert's pairs over the mean held expert's, averaged
over the layer-steps recorded (``hvd_moe_routed_total``): 1.0 is even
routing; the grouped products take as long as all pairs, but a deployment
waits for its fullest chip."""
UNIT, LAYER, MOVES, SOURCE = "ratio", "Model", "throughput", "program_counter"


def read(ctx):
    try:
        from horovod_tpu import metrics
    except ImportError:
        return None
    family = metrics.registry().to_dict().get("hvd_moe_routed_total")
    routed = {s["labels"]["what"]: s["value"] for s in (family or {}).get("series", [])}
    if not routed.get("pairs"):
        return None
    return routed["fullest"] * ctx.config["num_experts"] / routed["pairs"]
