"""Seconds of set-up in which jax traced a function to a jaxpr or lowered
one to MLIR (the program's ``compile`` spans with stage ``trace`` or
``lower``, union of intervals, less what a compile inside them took):
paid on every start, before the cache can be asked.  The five functions
with the most of it go on an earlier line."""
UNIT, LAYER, MOVES, SOURCE = "s", "Train-step assembly", "setup_s", "program_span"

import collections

from harness import startup


def read(ctx):
    split = startup.read(ctx)
    if split is None:
        return None
    by_fun = collections.Counter()
    for s in split.spans:
        if startup.kind(s) == "lower":
            # a trace is named ``step``, its lowering ``jit(step)``
            name = s["name"]
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            by_fun[name] += s["t1"] - s["t0"]
    ctx.say("traced and lowered, the five largest: " + ", ".join(
        f"{name} {sec:.3f}" for name, sec in by_fun.most_common(5)))
    return split.seconds["lower"]
