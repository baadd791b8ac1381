"""Device time in all-reduce operations per step, on one device."""
# The program's all-reduces are synchronous ops in the TensorCore's stream
# today (traced dp4 run, PR 23); against asynchronous -start/-done pairs the
# reduction is tested on a made-up trace only.  PERF.md, open question 11.
UNIT, LAYER, MOVES, SOURCE = "ms", "Gradient plane", "scaling_eff", "device_trace"


def read(ctx):
    if ctx.trace is None or ctx.trace.collective_s is None:
        return None
    return ctx.trace.collective_s / len(ctx.traced.stamps) * 1e3
