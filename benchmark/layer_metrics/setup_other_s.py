"""``setup_s`` less ``import_s``, ``init_s``, ``lower_s`` and
``compile_s``: what no span of the program covers.  Python's and jax's
own import, the weights, the first steps' execution, the warm-up."""
UNIT, LAYER, MOVES, SOURCE = "s", "Entry points", "setup_s", "program_span"

from harness import startup


def read(ctx):
    split = startup.read(ctx)
    if split is None:
        return None
    ctx.say(f"span buffer: {len(split.spans)} spans before the window, "
            "dropped 0")      # a ring that dropped any is not split at all
    return ctx.setup_s - sum(split.seconds.values())
