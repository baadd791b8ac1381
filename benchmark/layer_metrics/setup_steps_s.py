"""Seconds of set-up from the start of the first ``step`` span to the
start of the first window, less the self time of jax's tracing, lowering
and compiling inside that stretch: the checked steps and the warm-up
running on the device, with what the harness does between its drives.
The part of ``setup_other_s`` that is steps run before the window."""
UNIT, LAYER, MOVES, SOURCE = "s", "Entry points", "setup_s", "program_span"

import collections

from harness import startup, steploop


def read(ctx):
    got = steploop.ring()
    if got is None or got["dropped"]:       # what is gone is the oldest
        return None
    split = startup.read(ctx)
    if split is None:
        return None
    cut = min(p.start for p in ctx.phases.values())
    before = [s for s in got["step"] if s["t0"] < cut]
    if not before:
        return None
    first = before[0]["t0"]
    seconds = startup.self_seconds(split.spans, first, cut)
    compiling = seconds["compile"] + seconds["lower"]
    by_name = collections.Counter(s["name"] for s in before)
    ctx.say("steps before the window: "
            + ", ".join(f"{n} of {name}" for name, n in by_name.items())
            + f" in {cut - first:.3f} s, of which {compiling:.3f} s "
              "tracing, lowering and compiling")
    return cut - first - compiling
