"""Device time under ``hvd_mla_attention``, every pass, ms a step
(harness/scopes over hlo.scopes): the latent-attention sublayer whole, its
norm, the query's and the latent's projections (``hvd_mla_latent``), the
rotary products under ``hvd_rope``, the masked flash kernels and ``wo``.
None where the program opens no such scope (the parent of PR 49)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_mla_attention")
