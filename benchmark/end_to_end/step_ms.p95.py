"""95th percentile of the time a step takes, read over every run of
consecutive steps that spans a quarter of a second (the host's clock is
off by some half a millisecond, so no single short step is timed): stalls
and host hiccups that a mean hides."""

import math

import numpy as np

SPAN_S = 0.25


def read(ctx):
    stamps = np.asarray(ctx.main.stamps)
    step = float(np.median(np.diff(stamps)))
    g = max(1, math.ceil(SPAN_S / step))
    if len(stamps) <= g:
        return None
    spans = (stamps[g:] - stamps[:-g]) / g
    return float(np.percentile(spans, 95) * 1e3)
