"""hvdtracing: job-wide distributed tracing with clock-aligned merged
timelines and critical-path attribution.

The per-worker ``HOROVOD_TIMELINE`` (timeline.py) answers "what did MY
process do"; this package answers the multi-host question OptiReduce
(arXiv:2310.06993) says dominates DCN throughput — *which host's which
phase gated each round*:

* every worker keeps a bounded ring of span records
  (:class:`~.span.SpanBuffer`) for its engine cycles, negotiation
  rounds, fusion planning, per-bucket dispatches, DCN tail rounds
  (deadline + excluded hosts), and trace-time overlap staging — each
  tagged with the negotiation round id and elastic epoch, the
  correlation key that works without a global clock;
* the elastic driver's ``GET /trace/job`` scrapes every worker's
  buffer over the keep-alive RPC pool, estimates per-host clock
  offsets from RPC request/response timestamps (midpoint method,
  RTT-bounded error recorded on every span) and emits ONE
  Chrome-trace/Perfetto JSON with one ``pid`` per host
  (:mod:`.merge`);
* ``tools/hvdtrace`` (:mod:`.critical`) walks each round's span DAG
  (submit → negotiate → fuse → dispatch → dcn) and attributes the
  round's duration to the gating (host, phase, bucket), producing the
  per-host gating-fraction table that cross-checks the stall
  inspector's straggler EWMA with evidence.

A jitted job never enters the engine; what it has is start-up's spans
in the same ring and, in a second ring of their own (:func:`steps`), one
``step`` span a call of a builder's step with the steps still in flight
and a ``gc`` span a pause of the collector (:mod:`.step`).  A scrape
carries both rings.

Hot-path discipline (hvdmetrics/hvdchaos precedent): every
instrumented site guards on ``tracing.ACTIVE`` — one attribute load
and a false branch under ``HOROVOD_TRACE=0``.  Env table: docs/env.md;
span schema and offset method: docs/observability.md.
"""

from __future__ import annotations

import contextlib
import gc
import os
import threading
from typing import Optional

from . import critical, merge  # noqa: F401  (re-export for driver/tools)
from .span import DEFAULT_CAPACITY, PHASES, SpanBuffer  # noqa: F401

ENV_ENABLE = "HOROVOD_TRACE"
ENV_CAPACITY = "HOROVOD_TRACE_BUFFER"
ENV_PROBES = "HOROVOD_TRACE_PROBES"


def _env_on(name: str, default: bool = True, environ=os.environ) -> bool:
    from ..config import _env_bool  # one truthy grammar codebase-wide
    return _env_bool(name, default, environ)


def _env_capacity(environ=os.environ) -> int:
    try:
        return int(environ.get(ENV_CAPACITY, "") or DEFAULT_CAPACITY)
    except ValueError:
        return DEFAULT_CAPACITY


def probes(environ=os.environ) -> int:
    """Clock probes per scrape (``HOROVOD_TRACE_PROBES``, default 3;
    more probes tighten the min-RTT offset bound at scrape cost)."""
    try:
        return max(int(environ.get(ENV_PROBES, "3")), 1)
    except ValueError:
        return 3


#: Hot-path guard (one false branch when HOROVOD_TRACE=0).
ACTIVE = _env_on(ENV_ENABLE)

_BUFFER = SpanBuffer(capacity=_env_capacity())
_STEPS = SpanBuffer(capacity=_env_capacity())


def buffer() -> SpanBuffer:
    """The process-wide default span buffer (what ``trace_pull``
    serves)."""
    return _BUFFER


def steps() -> SpanBuffer:
    """The ring of the step loop (``step`` and ``gc`` spans: ``step.py``),
    of the same class and capacity as :func:`buffer` and apart from it:
    a job dispatches millions of steps, and they must never push a
    start-up span out of the default ring.  The collector's closed spans
    enter it here: ``GcWatch`` cannot take the ring's lock itself."""
    _GC_WATCH.hand_on(_STEPS)
    return _STEPS


def swap_buffer(buf: SpanBuffer) -> SpanBuffer:
    """Replace the default buffer, returning the old one (tests only:
    isolates a scenario's spans; the engine reads the module default
    per call, so the swap takes effect immediately)."""
    global _BUFFER
    old, _BUFFER = _BUFFER, buf
    return old


def swap_steps(buf: SpanBuffer) -> SpanBuffer:
    """:func:`swap_buffer` for the step loop's ring (tests only)."""
    global _STEPS
    old, _STEPS = _STEPS, buf
    return old


def now() -> float:
    """The default buffer's clock (instrumentation sites stamp spans
    with this so tests can inject skewed clocks)."""
    return _BUFFER.now()


def span(cat: str, name: str, t0: float, t1: float,
         round: Optional[int] = None, group: Optional[str] = None,
         **args):
    """Record one closed span into the default buffer (call sites
    guard on ``tracing.ACTIVE``)."""
    if ACTIVE:
        _BUFFER.add(cat, name, t0, t1, round=round, group=group, **args)


class _OpenScopes(threading.local):
    """``seqs``: this thread's open scopes, outermost first."""

    def __init__(self):
        self.seqs = []


_OPEN = _OpenScopes()


@contextlib.contextmanager
def scope(cat: str, name: str, **args):
    """One closed span around the block, for host code off the hot path
    (start-up: ``hvd.init()`` and its phases).  The span carries
    ``parent``, the ``seq`` of the scope that encloses it on this thread
    (None at the top), and a ``jax.profiler.TraceAnnotation`` named
    ``hvd.<name>`` stays open for the same interval, so under
    ``hvd.start_profiler()`` the span also sits on the host's line of
    the device trace, on that trace's clock."""
    import jax.profiler
    seqs = _OPEN.seqs
    parent = seqs[-1] if seqs else None
    seq = _BUFFER.reserve() if ACTIVE else None
    seqs.append(seq)
    t0 = now()
    try:
        with jax.profiler.TraceAnnotation(f"hvd.{name}"):
            yield
    finally:
        seqs.pop()
        span(cat, name, t0, now(), seq=seq, parent=parent, **args)


def set_context(round: Optional[int] = None, cycle: Optional[int] = None,
                epoch: Optional[int] = None,
                group: Optional[str] = None):
    _BUFFER.set_context(round=round, cycle=cycle, epoch=epoch,
                        group=group)


def set_identity(process: Optional[int] = None, host: Optional[str] = None,
                 epoch: Optional[int] = None):
    for ring in (_BUFFER, _STEPS):
        ring.set_identity(process=process, host=host, epoch=epoch)


def _snapshot() -> dict:
    """Both rings as one scrape payload: the default buffer's identity
    and clock sample, the step loop's spans after its own (``seq`` counts
    within a ring), ``dropped`` summed."""
    snap = _BUFFER.snapshot()
    loop = steps().snapshot()
    snap["spans"] += loop["spans"]
    snap["dropped"] += loop["dropped"]
    return snap


def pull_handler(payload):
    """``JsonRpcServer`` POST handler over the CURRENT rings (resolved
    per call so ``swap_buffer`` takes effect): a probe is the default
    buffer's, the scrape carries both rings' spans."""
    if isinstance(payload, dict) and payload.get("probe"):
        return _BUFFER.pull_handler()(payload)
    return _snapshot()


def local_trace() -> dict:
    """This process's two rings as a Chrome trace (``GET /trace``)."""
    return merge.local_trace(_snapshot())


def enable():
    global ACTIVE
    ACTIVE = True


def disable():
    global ACTIVE
    ACTIVE = False


def init_from_env(environ=os.environ):
    """Apply the HOROVOD_TRACE* contract (called from ``hvd.init()``;
    idempotent across elastic re-inits): refresh the ACTIVE flag and
    resize the default buffer if the capacity changed (newest spans are
    kept — a re-init mid-job must not drop the history a post-mortem
    scrape wants), and watch the collector's pauses (``step.GcWatch``)."""
    global ACTIVE
    ACTIVE = _env_on(ENV_ENABLE, environ=environ)
    for ring in (_BUFFER, _STEPS):
        ring.set_capacity(_env_capacity(environ))
    if _GC_WATCH not in gc.callbacks:
        gc.callbacks.append(_GC_WATCH)


def shutdown():
    """Take out of the interpreter what :func:`init_from_env` put there
    (``hvd.shutdown()`` calls it; the rings and their spans stay)."""
    if _GC_WATCH in gc.callbacks:
        gc.callbacks.remove(_GC_WATCH)


from .step import GcWatch, TracedStep  # noqa: E402,F401  (reads this module)

_GC_WATCH = GcWatch()
