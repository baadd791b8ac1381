"""Device time under ``hvd_rope``, every pass, ms a step (harness/scopes
over hlo.scopes): both attention kinds' rotary tables, made once a kind a
step, and their products with ``q`` and ``k`` in every layer, forward,
rerun under remat and transposed in the backward pass.  None where the
program opens no such scope (the parent of PR 46)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_rope")
