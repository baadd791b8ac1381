"""The experts' combines' share of their roofline: the least time the
chip could take to add the rows the steps counted to their tokens (bytes
over HBM bandwidth: a combine moves no FLOP worth counting) over the
device time of the ``hvd_moe_combine*`` kinds in the traced stretch, the
program's Pallas kernels ``hvd_moe_combine_out`` (forward) and
``hvd_moe_combine_dtok`` (backward).  ``None`` where the trace names
neither: a program whose combines are XLA's scatter-adds, which sit in the
kind ``fusion`` with much else, reads nothing here.  The earlier line says
how much of the time each kernel took."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"
KINDS = ("hvd_moe_combine",)


def combine_bytes(cfg, pairs, tokens):
    """HBM bytes the two combines of one layer-step need at the least:
    each reads the float32 row of every (token, expert) pair routed here
    once and writes every token's float32 sum once (a target that starts
    at zero need not be read)."""
    return 2 * (pairs + tokens) * cfg["hidden_size"] * 4


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
    kinds = {k: s for k, s in ctx.trace.device_ops if k.startswith(KINDS)}
    routed = _routed()
    if not sum(kinds.values()) or not routed.get("layers"):
        return None
    steps = len(ctx.traced.stamps)
    # a sample is read as [xt ; x0]: 2 x seq_len positions (flops.py)
    tokens = ctx.traced.global_batch // ctx.traced.chips * 2 * ctx.config["seq_len"]
    least = (ctx.config["num_hidden_layers"]
             * combine_bytes(ctx.config, routed["pairs"] / routed["layers"], tokens)
             / ctx.peaks["hbm_bytes_per_s"])
    ctx.say("combines, ms a step: " + ", ".join(
        f"{k} {s / steps * 1e3:.3f}" for k, s in sorted(kinds.items()))
        + f"; least by bytes {least * 1e3:.3f} ms")
    return 100.0 * least * steps / sum(kinds.values())
