"""Device time under ``hvd_moe_experts``, every pass, ms a step
(harness/scopes over hlo.scopes): the sort of the pairs, the gathers, the
grouped products, the combines and the passes between them, whoever
computes them (the repo's kernels since PRs 30 and 31, XLA's ``ragged-dot``
before).  Until PR 35 this name summed the ``ragged-dot*`` kinds alone and
read nothing once the step had none."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_moe_experts")
