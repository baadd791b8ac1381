"""Start-up, split by the program's own spans (PR 24: ``tracing.scope``
around ``hvd.init()`` and its parts, one ``compile`` span per function jax
traced, lowered or compiled, the ``import`` span of the package).

The spans are read from ``horovod_tpu.tracing.buffer()`` and cut at the
start of the first phase's window; the buffer's clock (``time.monotonic``)
and the harness's (``time.perf_counter``) are the same clock on Linux.
Time is self time: every instant of set-up goes to the innermost kind of
span that covers it, so a compile inside ``hvd.init()`` counts as a
compile and not as init, nested traces of inner functions count once, and
the kinds never add up to more than ``setup_s``.  A program without these
spans (the parent of PR 24, or ``HOROVOD_TRACE=0``) reads as None.
"""

import types

from harness.xplane import _length, _overlap, _union

KINDS = ("compile", "lower", "init", "import")      # innermost first


def kind(span):
    """Which of KINDS a span of the buffer counts to, or None."""
    if span["cat"] == "compile":
        return "compile" if span["args"].get("stage") == "backend" else "lower"
    if span["cat"] == "setup" and span["name"] in ("init", "import"):
        return span["name"]
    return None


def self_seconds(spans, start, cut):
    """{kind: seconds of [start, cut] that are this kind's and no inner
    kind's}."""
    by_kind = {k: [] for k in KINDS}
    for s in spans:
        lo, hi = max(s["t0"], start), min(s["t1"], cut)
        if hi > lo and kind(s) is not None:
            by_kind[kind(s)].append((lo, hi))
    covered, out = [], {}
    for k in KINDS:
        mine = _union(by_kind[k])
        out[k] = _length(mine) - _overlap(mine, covered)
        covered = _union(covered + mine)
    return out


def read(ctx):
    """The buffer cut at the window's start: ``seconds`` by kind and
    ``spans`` (those that began before the cut).  None where the program
    left no start-up span, or the ring dropped some: what is gone is the
    oldest, which is start-up."""
    try:
        from horovod_tpu import tracing
    except ImportError:
        return None
    snap = tracing.buffer().snapshot()
    cut = min(p.start for p in ctx.phases.values())
    spans = [s for s in snap["spans"] if s["t0"] < cut]
    if not any(kind(s) == "import" for s in spans):
        return None
    if snap["dropped"]:
        ctx.say(f"span buffer dropped {snap['dropped']} spans: start-up "
                "not split")
        return None
    return types.SimpleNamespace(
        seconds=self_seconds(spans, cut - ctx.setup_s, cut), spans=spans)
