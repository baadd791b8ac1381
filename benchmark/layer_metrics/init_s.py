"""Seconds of set-up inside ``hvd.init()`` (the program's ``init`` span,
self time: what jax compiled inside it counts to ``lower_s`` and
``compile_s``).  Its parts (``init.rendezvous``, ``init.backend``, ...)
go on an earlier line, one ``name seconds`` each."""
UNIT, LAYER, MOVES, SOURCE = "s", "Runtime", "setup_s", "program_span"

from harness import startup


def read(ctx):
    split = startup.read(ctx)
    if split is None:
        return None
    ctx.say("hvd.init() by part: " + ", ".join(
        f"{s['name']} {s['t1'] - s['t0']:.3f}" for s in split.spans
        if s["cat"] == "setup" and s["name"].startswith("init.")))
    return split.seconds["init"]
