"""The masked attention kernels' share of their roofline in a trunk of
window, full and cross differential attention: the same reading as
``mask_flash_roofline`` (its reader, beside this file, on this cell's
trace), with this configuration's flops.mask_flash_kernel_cost: live
pairs of the window and causal ranges, values twice as wide as queries
and keys, over the device time of the ``hvd_flash_*`` kinds in the traced
stretch.  The earlier lines say which bound, each kernel's time a step
and, from ``flash_tiles_skipped_pct``'s reader, the tiles by class."""
import os

from harness import registry

UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    value = registry.reader(_BENCH, "layer_metrics", "mask_flash_roofline")(ctx)
    if value is not None:
        skipped = registry.reader(_BENCH, "layer_metrics",
                                  "flash_tiles_skipped_pct")(ctx)
        if skipped is not None:
            ctx.say(f"hybrid flash tiles skipped: {skipped:.1f}%")
    return value
