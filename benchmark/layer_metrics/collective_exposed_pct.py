"""Share of the all-reduce time during which nothing else ran on that
device: the part of the exchange that compute does not hide."""
# The program's all-reduces are synchronous ops in the TensorCore's stream
# today (traced dp4 run, PR 23); against asynchronous -start/-done pairs the
# reduction is tested on a made-up trace only.  PERF.md, open question 11.
UNIT, LAYER, MOVES, SOURCE = "%", "Gradient plane", "scaling_eff", "device_trace"


def read(ctx):
    if ctx.trace is None or not ctx.trace.collective_s:
        return None
    return 100.0 * ctx.trace.collective_exposed_s / ctx.trace.collective_s
