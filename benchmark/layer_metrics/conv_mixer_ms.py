"""Device time under ``hvd_conv_mixer``, every pass, ms a step
(harness/scopes over hlo.scopes): the gated short convolution's sublayer
whole, its norm, ``in_proj``, the elementwise chain (``hvd_gated_conv``
inside it) and ``out_proj``, all the convolution layers of the trunk.
None where the program opens no such scope (the parent of PR 53)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_conv_mixer")
