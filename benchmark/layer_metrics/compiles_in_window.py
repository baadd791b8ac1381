"""Programs jax lowered while the window ran (cache hits included).
Anything but 0 also fails the run's checks."""
UNIT, LAYER, MOVES, SOURCE = "count", "Runtime", "step_ms.p95", "program_counter"


def read(ctx):
    return ctx.compiles_in_window
