"""Milliseconds of the main window inside Python's cyclic collector: the
summed length of the program's ``gc`` spans there (a collection that took
1 ms or more, or a full one).  ``run.py`` freezes the heap before the
window so that full collections stay away; this reads that they did."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Runtime", "throughput", "program_span"

from harness import steploop


def read(ctx):
    w = steploop.window(ctx)
    if w is None:
        return None
    return steploop.inside(w.gc, w.start, w.end) * 1e3
