"""Process start to the first timed step: init, weights, build, the
checked first steps, warm-up, and compilation where the cache is cold."""


def read(ctx):
    return ctx.setup_s
