"""How far the times by scope can be trusted: the share of the step's
device time in instructions that carry no scope of the program's (copies
and buffers XLA made itself) or that are fusions whose computing
instructions lie in more than one (scope, pass), which the table puts
down whole to the fusion's own op_name (harness/scopes)."""
UNIT, LAYER, MOVES, SOURCE = "%", "Train-step assembly", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    t = scopes.table(ctx)
    if t is None or not t.total_s:
        return None
    return 100.0 * (t.unattributed_s + t.mixed_s) / t.total_s
