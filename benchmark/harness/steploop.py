"""The step loop, read from the program's own record (PR 51: one ``step``
span a call of a builder's step, with the call's number ``n``, the steps
still ``in_flight`` when it began and those first seen ``done``; a ``gc``
span a pause of the collector that took 1 ms or more or was a full one).

The spans are read from ``horovod_tpu.tracing.steps()``, a ring apart from
the one ``startup.py`` cuts, and cut to the untraced main window; the
ring's clock (``time.monotonic``) and the harness's (``time.perf_counter``)
are the same clock on Linux.  A program without that ring (the parent of
PR 51) or with ``HOROVOD_TRACE=0`` reads as None.
"""

import types


def ring():
    """{"step": [...], "gc": [...], "dropped": n} as the program holds
    them, oldest first, or None where it holds no step."""
    try:
        from horovod_tpu import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "steps"):
        return None
    snap = tracing.steps().snapshot()
    by_cat = {cat: [s for s in snap["spans"] if s["cat"] == cat]
              for cat in ("step", "gc")}
    if not by_cat["step"]:
        return None
    return dict(by_cat, dropped=snap["dropped"])


def compiles():
    """The ``compile`` spans of the ring that ``startup.py`` cuts."""
    from horovod_tpu import tracing
    return [s for s in tracing.buffer().snapshot()["spans"]
            if s["cat"] == "compile"]


def inside(spans, lo, hi):
    """Seconds of [lo, hi] that ``spans`` cover, each span for itself."""
    return sum(max(0.0, min(s["t1"], hi) - max(s["t0"], lo)) for s in spans)


def window(ctx):
    """The main window's calls of the step (those that began inside it)
    and the collector's pauses that touch it; None without any call."""
    got = ring()
    if got is None:
        return None
    lo, hi = ctx.main.start, ctx.main.stamps[-1]
    steps = [s for s in got["step"] if lo <= s["t0"] <= hi]
    if not steps:
        return None
    return types.SimpleNamespace(
        start=lo, end=hi, steps=steps,
        gc=[s for s in got["gc"] if s["t1"] > lo and s["t0"] < hi])
