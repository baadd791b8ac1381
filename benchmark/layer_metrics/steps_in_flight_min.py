"""Fewest earlier steps not yet ready when the host dispatched the next,
over the main window's calls past the first ``window.IN_FLIGHT``, while the
queue fills: one less than that where the device never ran short of work
(the one the loop waited for is done); lower where the loop was held (in
its wait, its feed or the jitted call) while the device worked its queue
off; 0 where the device had run dry before the host was back."""
UNIT, LAYER, MOVES, SOURCE = "count", "Train-step assembly", "throughput", "program_span"

from harness import steploop, window


def read(ctx):
    w = steploop.window(ctx)
    if w is None or len(w.steps) <= window.IN_FLIGHT:
        return None
    return min(s["args"]["in_flight"] for s in w.steps[window.IN_FLIGHT:])
