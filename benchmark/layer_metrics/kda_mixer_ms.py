"""Device time under ``hvd_kda_mixer``, every pass, ms a step
(harness/scopes over hlo.scopes): the Kimi Delta Attention mixer whole,
its norm and projections, the convolution, the chunked delta-rule scan
under ``hvd_kda_scan`` (the kernels and the tiles, running sums and
triangular inverse around them), the output's norm and gate.  None where
the program opens no such scope (the parent of PR 42)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_kda_mixer")
