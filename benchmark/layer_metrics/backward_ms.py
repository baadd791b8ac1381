"""Device time of the backward pass, ms a step: the instructions whose
op_name holds autodiff's ``transpose(...)`` outside any recomputed forward
(harness/scopes over hlo.scopes): the transposed products, the kernels'
backward calls, and what a custom backward rule computes again itself."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, passes=("backward",))
