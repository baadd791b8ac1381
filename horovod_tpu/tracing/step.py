"""The jitted step loop in the program's own record: one ``step`` span a
call of a builder's step, the collector's pauses beside it.

A jitted job never enters the eager engine, so the spans of ``span.py``'s
phases are none of its own.  What it does every step is call one
``jax.jit`` object, and :class:`TracedStep` is that object with a record
around the call: when the host dispatched step ``n``, how long the call
held it, and how many of the earlier steps the device still had queued.
A window that took longer than its steps then says which step it was and
whether the device had run dry by then (``in_flight`` 0: the loop alone
was late) or its queue was still there (the process was away as a whole,
or its runtime did not answer: what is in flight is queued in the
process, and a stopped process reads 3 of 5); :class:`GcWatch` says
whether the collector lay in the gap, a ``compile`` span of
``runtime.py`` whether a recompile did.

Both record into ``tracing.steps()``, a ring of their own: a job
dispatches millions of steps and the default ring's few dozen start-up
spans must never be pushed out by them.  docs/observability.md "Start-up
and the jitted step".
"""

from __future__ import annotations

import collections

import jax.profiler

from .. import metrics as _metrics
from .. import tracing as _tracing

#: How many earlier calls' results a call looks at, at most: with more
#: than this in flight ``in_flight`` reads this many.
MAX_WATCHED = 8

#: A collection shorter than this leaves a span only if it is a full one.
GC_SPAN_SECONDS = 1e-3

_m_steps = _metrics.counter(
    "hvd_steps_total",
    "Calls of a step builder's jitted step (dispatched, not completed)",
    labels=("step",))

_m_gc_pause = _metrics.counter(
    "hvd_gc_pause_seconds_total",
    "Seconds the process spent inside Python's cyclic collector",
    labels=("generation",))


class TracedStep:
    """A builder's ``jax.jit`` object, called through one record.

    ``lower`` and every other attribute are the jitted function's, and a
    call returns what the jitted call returned, untouched.  ``ready_index``
    names the output that is never donated to a later call (the loss): the
    record keeps the last :data:`MAX_WATCHED` of them and asks each
    ``is_ready()``, which never blocks.
    """

    def __init__(self, jitted, ready_index: int):
        self._jitted = jitted
        self._ready_index = ready_index
        self._name = getattr(jitted, "__name__", type(jitted).__name__)
        self._n = 0
        self._watched = collections.deque(maxlen=MAX_WATCHED)  # (n, loss)

    def __getattr__(self, name):
        # reached only for what this class does not define
        return getattr(object.__getattribute__(self, "_jitted"), name)

    def __call__(self, *args, **kwargs):
        if _metrics.ACTIVE:
            _m_steps.inc(step=self._name)
        if not _tracing.ACTIVE:
            return self._jitted(*args, **kwargs)
        watched, done = self._watched, []
        # a step consumes the one before it, so results become ready in
        # order: the first that is not ends the look
        while watched and (watched[0][1].is_deleted()
                           or watched[0][1].is_ready()):
            done.append(watched.popleft()[0])
        in_flight = len(watched)
        t0 = _tracing.now()
        with jax.profiler.TraceAnnotation("hvd.step"):
            out = self._jitted(*args, **kwargs)
        t1 = _tracing.now()
        n, self._n = self._n, self._n + 1
        ready = out[self._ready_index]
        if hasattr(ready, "is_ready"):      # a tracer under an outer trace has none
            watched.append((n, ready))
        _tracing.steps().add("step", self._name, t0, t1, round=-1, n=n,
                             in_flight=in_flight, done=done)
        return out


class GcWatch:
    """The function ``tracing.init_from_env()`` appends to ``gc.callbacks``.

    A collection starts between any two bytecodes of whichever thread
    crossed the threshold, so this runs inside code that may hold the
    step ring's lock or the counter's, and waits for neither: a closed
    span is kept here until :meth:`hand_on` is called from outside a
    collection (``tracing.steps()`` does, so every reader and every step
    does), seconds the counter could not take are offered again at the
    next collection.
    """

    def __init__(self):
        self._t0 = None
        self._spans = collections.deque(maxlen=64)     # closed, not yet in the ring
        self._seconds = [0.0, 0.0, 0.0]                # not yet in the counter

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = _tracing.now()
            return
        t0, self._t0 = self._t0, None
        if t0 is None:                  # appended while a collection ran
            return
        t1 = _tracing.now()
        gen = info["generation"]
        if _tracing.ACTIVE and (gen == 2 or t1 - t0 >= GC_SPAN_SECONDS):
            self._spans.append((gen, t0, t1, info["collected"]))
        if _metrics.ACTIVE:
            self._seconds[gen] += t1 - t0
            for g, seconds in enumerate(self._seconds):
                if seconds and _m_gc_pause.inc_unless_held(
                        seconds, generation=str(g)):
                    self._seconds[g] = 0.0

    def hand_on(self, ring):
        """The spans closed since the last call, into ``ring``."""
        try:
            while True:                 # several threads may hand on at once
                gen, t0, t1, collected = self._spans.popleft()
                ring.add("gc", f"gen{gen}", t0, t1, round=-1,
                         collected=collected)
        except IndexError:
            pass
