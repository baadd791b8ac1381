"""Rotary tables the timed program traced, one a layer kind that has one
(``hvd_rope_tables_total{kind, type}``, summed): 2 where a trunk runs a
plain table under the window and a YaRN one on its full layers.  The
earlier line says which kind took which type.  ``None`` where the program
has no such counter."""
UNIT, LAYER, MOVES, SOURCE = "count", "Model", "throughput", "program_counter"


def read(ctx):
    try:
        from horovod_tpu import metrics
    except ImportError:
        return None
    family = metrics.registry().to_dict().get("hvd_rope_tables_total")
    if not family:
        return None
    series = family.get("series", [])
    ctx.say("rotary tables: " + ", ".join(
        f"{s['labels']['kind']} {s['labels']['type']} {s['value']:g}"
        for s in series))
    return sum(s["value"] for s in series)
