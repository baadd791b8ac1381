"""Samples trained per second per chip: every step completed in the
window's main phase, over all of that phase's time."""


def read(ctx):
    return ctx.main.samples_per_s_per_chip
