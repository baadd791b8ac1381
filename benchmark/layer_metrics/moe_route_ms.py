"""Device time under ``hvd_moe_route``, every pass, ms a step
(harness/scopes over hlo.scopes): the router's product, its softmax and
top-k, forward, again under remat, and their backward."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_moe_route")
