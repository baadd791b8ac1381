"""The masked attention kernels' share of their roofline in a trunk of
plain grouped-query attention under a window and causal: the same reading
as ``mask_flash_roofline`` (its reader, beside this file, on this cell's
trace), with this configuration's flops.mask_flash_kernel_cost: live pairs
of each layer's own ranges, over the device time of the ``hvd_flash_*``
kinds in the traced stretch.  The earlier lines say which bound, each
kernel's time a step, the window calls' and the causal call's apart (the
kernels' rows of the scope table under ``hvd_window_attention`` and under
``hvd_attention``) and, from ``flash_tiles_skipped_pct``'s reader, the
tiles by class."""
import os

from harness import registry, scopes

UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _kernels_ms(table, scope):
    """ms a step of the flash kernels' rows under ``scope``."""
    seconds = 0.0
    for (path, _), s in table.rows.items():
        parts = path.split("/")
        if scope in parts and any(p.startswith("hvd_flash") for p in parts):
            seconds += s
    return seconds / table.steps * 1e3


def read(ctx):
    value = registry.reader(_BENCH, "layer_metrics", "mask_flash_roofline")(ctx)
    if value is None:
        return None
    table = scopes.table(ctx)
    if table is not None:
        ctx.say("swa flash kernels, ms a step: window calls "
                f"{_kernels_ms(table, 'hvd_window_attention'):.3f}, causal call "
                f"{_kernels_ms(table, 'hvd_attention'):.3f}")
    skipped = registry.reader(_BENCH, "layer_metrics",
                              "flash_tiles_skipped_pct")(ctx)
    if skipped is not None:
        ctx.say(f"swa flash tiles skipped: {skipped:.1f}%")
    return value
