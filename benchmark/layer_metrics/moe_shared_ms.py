"""Device time under ``hvd_moe_shared``, every pass, ms a step
(harness/scopes over hlo.scopes): the expert every token passes through,
a fused gate/up product and a down product beside the routed experts,
inside ``hvd_mlp``.  None where the program opens no such scope (the
parent of PR 42, and every configuration without a shared expert)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_moe_shared")
