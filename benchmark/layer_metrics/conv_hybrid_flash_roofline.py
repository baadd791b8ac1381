"""The masked attention kernels' share of their roofline in a trunk of
gated short convolutions and one causal grouped-query attention layer with
q/k norm (32/8 heads of 64, two rows of 8,192): the same reading as
``mask_flash_roofline`` (its reader, beside this file, on this cell's
trace), with this configuration's flops.mask_flash_kernel_cost, the least
any implementation needs over the live pairs, over the device time of the
``hvd_flash_*`` kinds in the traced stretch.  The earlier line says which
bound and each kernel's time a step."""
import os

from harness import registry

UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    return registry.reader(_BENCH, "layer_metrics", "mask_flash_roofline")(ctx)
