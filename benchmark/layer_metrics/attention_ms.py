"""Device time under the attention's scope, every pass, ms a step
(harness/scopes over hlo.scopes): ``hvd_diff_attention`` (models/hybrid.py:
the projections, the kernels' calls, lambda and the sub-layer norm) and
``hvd_attention``, which the llama trunk and BERT's encoder do not open
yet (PERF.md, open questions): the kernels *and* what stands between XLA's
arrays and theirs."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    parts = [scopes.ms(ctx, scope=s)
             for s in ("hvd_attention", "hvd_diff_attention")]
    return sum(p for p in parts if p) or None
