"""Seconds of set-up inside the backend's compile (the program's
``compile`` spans with stage ``backend``, union of intervals): a compile
where the persistent cache missed, the load from it where it hit."""
UNIT, LAYER, MOVES, SOURCE = "s", "Runtime", "setup_s", "program_span"

from harness import startup


def read(ctx):
    split = startup.read(ctx)
    return split and split.seconds["compile"]
