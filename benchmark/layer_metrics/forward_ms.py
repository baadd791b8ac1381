"""Device time of the forward pass, ms a step: every instruction of the
traced stretch whose op_name lies under a scope of the program's and is
neither autodiff's transpose, nor remat's second forward, nor the
optimizer's or the gradient exchange's (harness/scopes over hlo.scopes)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, passes=("forward",))
