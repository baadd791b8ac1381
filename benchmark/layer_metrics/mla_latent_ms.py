"""Device time under ``hvd_mla_latent``, every pass, ms a step
(harness/scopes over hlo.scopes): ``u Wkv_a``, the split, the latent's
RMSNorm and ``c Wkv_b`` (keys and values made from the latent: what an
absorbed form or a saved latent would move).  None where the program opens
no such scope (the parent of PR 49)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_mla_latent")
