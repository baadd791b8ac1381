"""Programs the persistent compilation cache did not hold
(``hvd_compile_cache_total{result="miss"}`` of the program's registry): 0
on a warm checkout.  Read after the window, which compiles nothing
(checked), and before the reference, whose programs are not the
program's: so it is the count at the window's start."""
UNIT, LAYER, MOVES, SOURCE = "count", "Runtime", "setup_s", "program_counter"


def read(ctx):
    try:
        from horovod_tpu import metrics
    except ImportError:
        return None
    family = metrics.registry().to_dict().get("hvd_compile_cache_total")
    if family is None:
        return None
    ctx.say("compile cache: " + (", ".join(
        f"{s['labels']['result']} {s['value']:g}" for s in family["series"])
        or "no look-up"))
    return sum(s["value"] for s in family["series"]
               if s["labels"]["result"] == "miss")
