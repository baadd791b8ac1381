"""Share of the masked attention calls' (query tile, key tile) pairs that
hold no live pair and are skipped: no load, no product
(``hvd_flash_tiles_total{state="skipped"}`` over all states, counted from
the mask where each call is built; the same for every step)."""
UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "program_counter"


def read(ctx):
    try:
        from horovod_tpu import metrics
    except ImportError:
        return None
    family = metrics.registry().to_dict().get("hvd_flash_tiles_total")
    by_state = {}
    for s in (family or {}).get("series", []):
        state = s["labels"]["state"]
        by_state[state] = by_state.get(state, 0) + s["value"]
    if not sum(by_state.values()):
        return None
    ctx.say("masked flash tiles: " + ", ".join(
        f"{k} {v:g}" for k, v in sorted(by_state.items())))
    return 100.0 * by_state.get("skipped", 0) / sum(by_state.values())
