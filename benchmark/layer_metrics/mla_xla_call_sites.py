"""Call sites of latent attention that the timed program built on XLA's
blockwise fallback (``hvd_mla_call_total{path="xla"}``: where the shapes or
the backend keep the masked flash kernels off): 0 when every site runs
``hvd_flash_fwd`` / ``hvd_flash_dq`` / ``hvd_flash_dkv``.  The earlier line
says which form each site took (``split``: the kernels add the rotary
pair's product to the score tile; ``joined``: one 192-wide query and key).
``None`` where the program has no such counter (the parent of PR 49)."""
UNIT, LAYER, MOVES, SOURCE = "count", "Kernels", "throughput", "program_counter"


def read(ctx):
    try:
        from horovod_tpu import metrics
    except ImportError:
        return None
    family = metrics.registry().to_dict().get("hvd_mla_call_total")
    if not family:
        return None
    series = family.get("series", [])
    ctx.say("latent attention call sites: " + ", ".join(
        f"{s['labels']['path']} {s['labels']['form']} {s['value']:g}"
        for s in series))
    return sum(s["value"] for s in series if s["labels"]["path"] == "xla")
