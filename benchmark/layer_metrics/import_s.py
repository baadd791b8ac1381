"""Seconds of set-up inside ``import horovod_tpu`` (the program's
``import`` span, self time): the package's own modules, and jax's where
the caller had not imported it."""
UNIT, LAYER, MOVES, SOURCE = "s", "Runtime", "setup_s", "program_span"

from harness import startup


def read(ctx):
    split = startup.read(ctx)
    return split and split.seconds["import"]
