"""Device time under ``hvd_window_attention``, every pass, ms a step
(harness/scopes over hlo.scopes): the plain grouped-query attention
sublayer under the window whole, its norm and projections, the rotary
products under ``hvd_rope`` and the masked flash kernels.  None where the
program opens no such scope (the parent of PR 46)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_window_attention")
