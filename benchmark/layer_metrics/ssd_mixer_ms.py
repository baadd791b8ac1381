"""Device time under ``hvd_ssd_mixer``, every pass, ms a step
(harness/scopes over hlo.scopes): the Mamba-2 mixer whole, its
projections, the convolution, the chunked scan under ``hvd_ssd_scan``
(the kernels and the transposes and running sums around them), the gate
and its norm."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_ssd_mixer")
