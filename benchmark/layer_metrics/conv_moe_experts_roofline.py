"""The experts' grouped matrix products' share of their roofline at this
configuration's widths (``[2048, 2 x 1792]`` / ``[1792, 2048]``, about
16,384 pairs a layer over 8 experts): the same reading as
``moe_experts_roofline`` (its reader, beside this file, on this cell's
trace), with this configuration's flops.moe_kernel_cost over
``hvd_moe_routed_total``, over the device time of every grouped-product
kind in the traced stretch.  That reader takes a layer-step's cost times
the configuration's ``num_hidden_layers``; here a kept layer is dense, so
it is handed the count of the routed ones (flops.routed_layers).  The
earlier line says which bound, and how much of the time each kind took."""
import os
import types

from harness import registry

UNIT, LAYER, MOVES, SOURCE = "%", "Kernels", "throughput", "device_trace"
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    routed = getattr(ctx.flops, "routed_layers", None)
    if routed is None:
        return None
    config = {**ctx.config, "num_hidden_layers": routed(ctx.config)}
    return registry.reader(_BENCH, "layer_metrics", "moe_experts_roofline")(
        types.SimpleNamespace(**{**vars(ctx), "config": config}))
