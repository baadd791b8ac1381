"""Host time inside the jitted call of the program's step, median over the
main window's ``step`` spans: the call alone, where ``dispatch_ms`` also
holds what the configuration's adapter does around it."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Train-step assembly", "throughput", "program_span"

import statistics

from harness import steploop


def read(ctx):
    w = steploop.window(ctx)
    if w is None:
        return None
    return statistics.median(s["t1"] - s["t0"] for s in w.steps) * 1e3
