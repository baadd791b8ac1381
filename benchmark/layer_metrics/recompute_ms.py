"""Device time of remat's second forward, ms a step: the instructions
under ``jax.checkpoint``'s ``rematted_computation`` (harness/scopes over
hlo.scopes).  What a remat policy saves leaves this number; what a kernel's
own backward rule recomputes is the backward's."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, passes=("recompute",))
