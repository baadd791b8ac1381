"""Device time under ``hvd_ssm_mixer``, every pass, ms a step
(harness/scopes over hlo.scopes): the Mamba mixer whole, its projections,
the convolution, the scan kernels and the casts and lane spreads before
each call."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_ssm_mixer")
