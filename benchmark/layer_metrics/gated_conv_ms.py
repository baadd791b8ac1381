"""Device time under ``hvd_gated_conv``, every pass, ms a step
(harness/scopes over hlo.scopes): the gated short convolution's
elementwise chain alone, ``B * x``, the taps and ``C *``, as XLA fuses it,
all the convolution layers of the trunk.  The earlier line sets it beside
the chain's bytes' bound (the configuration's flops.gated_conv_bytes over
the HBM bandwidth, a layer and forward pass).  No share is made of the
two: XLA may fuse the chain into its neighbours' products, and a share
read off a scope that lost its rows would pass 100%.  None where the
program opens no such scope (the parent of PR 53)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    value = scopes.ms(ctx, scope="hvd_gated_conv")
    least = getattr(ctx.flops, "gated_conv_bytes", None)
    if value is not None and least is not None:
        table = scopes.table(ctx)
        by_pass = {p: table.seconds("hvd_gated_conv", (p,)) / table.steps * 1e3
                   for p in ("forward", "recompute", "backward")}
        bound = (least(ctx.config, ctx.traced.global_batch // ctx.traced.chips)
                 / ctx.peaks["hbm_bytes_per_s"] * 1e3)
        ctx.say("gated convolution's chain, ms a step: " + ", ".join(
            f"{p} {ms:.3f}" for p, ms in by_pass.items())
            + f"; its bytes' bound {bound:.3f} ms a layer and forward pass")
    return value
