"""The experts' grouped matrix products' share of their roofline at this
configuration's widths (``[2304, 2 x 896]`` / ``[896, 2304]``, about 32,768
pairs a layer over 16 experts): the same reading as
``moe_experts_roofline`` (its reader, beside this file, on this cell's
trace), with this configuration's flops.moe_kernel_cost over
``hvd_moe_routed_total``, over the device time of every grouped-product
kind in the traced stretch.  The earlier line says which bound, and how
much of the time each kind took."""
import os

from harness import registry

UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    return registry.reader(_BENCH, "layer_metrics", "moe_experts_roofline")(ctx)
