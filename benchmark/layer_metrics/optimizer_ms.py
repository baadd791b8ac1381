"""Device time under ``hvd_optimizer``, ms a step (harness/scopes over
hlo.scopes): the update and its application.  Where XLA fuses the update
into the weight gradients' products the fusion's time lies where its own
op_name says and ``scope_unattributed_pct`` counts it as mixed: the whole
update on one chip of ResNet (``multiply_add_fusion``: 5 us are left, so
those two cells do not list this metric), a part of it in an unscanned
trunk (Phi); on four chips the all-reduce parts them."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Train-step assembly", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, passes=("optimizer",))
