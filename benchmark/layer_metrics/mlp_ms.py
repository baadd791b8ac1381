"""Device time under ``hvd_mlp``, every pass, ms a step (harness/scopes
over hlo.scopes): the feed-forward sublayer with its norm, whoever computes
it.  ``models/llama.py::block`` opens it around ``mlp_norm`` and ``ffn``,
``models/hybrid.py`` around ``norm2`` and the fused gate/up MLP,
``models/bert.py::block`` around the MLP and its residual's LayerNorm.
Routed experts nest inside it, so in the SDAR cell it is ``moe_route_ms``
+ ``moe_experts_ms`` + the norm and the casts around them, each row
counted once.  None where the program opens no such scope (the parent of
PR 36)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_mlp")
