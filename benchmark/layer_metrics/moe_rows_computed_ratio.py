"""Rows the experts' grouped products computed over the (token, expert)
pairs routed to the experts held, both from the steps' own outputs
(``hvd_moe_routed_total``): 1.0 is no padding row and no dropped pair."""
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
    ctx.say(f"routed to held experts, a layer-step: {routed['pairs'] / routed['layers']:.1f} "
            f"pairs, {routed['rows'] / routed['layers']:.1f} rows computed, over "
            f"{routed['layers']:g} layer-steps")
    return routed["rows"] / routed["pairs"]
