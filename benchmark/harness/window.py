"""The feed and the one loop that every step of a run goes through: the
checked first steps, the warm-up, the measured window and the traced
stretch.  Steps are dispatched with six in flight (dispatch step k, then
wait on the loss of step k-5 and stamp the clock), so every step has a
completion time and the device has work queued through a stall of the
host: with two in flight, stalls of 100-150 ms inside dispatch or wait,
none to four in a 20 s window, each drained the queue and moved a run's
throughput by up to a percent (my chip runs, PR 23).  The examples users
copy keep as many or more in flight (resnet50_synthetic_benchmark.py
blocks every fifth step, bert_finetune.py never).
"""

import collections
import time

import jax
import numpy as np

IN_FLIGHT = 6


class Feed:
    """The general generator.  A traffic file gives ``per_chip_batch``,
    ``resident`` ("device": the pool is placed once and stays; "host": a
    batch crosses from the host every step) and ``pool_batches`` distinct
    global batches, cycled.  Rows come from the configuration's
    ``make_samples`` and a key folded from the seed, so every seed gives
    the same shapes and the same amount of work."""

    def __init__(self, traffic, cfg, reference, program, data_key):
        self._make = lambda j: reference.make_samples(
            cfg, jax.random.fold_in(data_key, j), program.global_batch)
        self._on_device = traffic["resident"] == "device"
        self._place = program.place
        pool = [self._make(j) for j in range(traffic["pool_batches"])]
        self.batches = ([program.place(s) for s in pool] if self._on_device
                        else [tuple(np.asarray(a) for a in s) for s in pool])
        self._k = 0

    def samples(self, k):
        """Step k's rows again, unplaced, for the reference."""
        return self._make(k % len(self.batches))

    def next(self):
        batch = self.batches[self._k % len(self.batches)]
        self._k += 1
        return batch if self._on_device else self._place(batch)


class Stretch:
    """What one pass through the loop recorded, on the host's clock."""

    def __init__(self, program):
        self.chips, self.global_batch = program.chips, program.global_batch
        self.start = None
        self.stamps, self.feed_s, self.dispatch_s, self.losses = [], [], [], []

    @property
    def seconds(self):
        return self.stamps[-1] - self.start

    @property
    def samples_per_s_per_chip(self):
        return len(self.stamps) * self.global_batch / self.seconds / self.chips

    def step_intervals(self):
        return np.diff(np.asarray(self.stamps))

    def host_summary(self):
        """Where a slow run lost its time: the host's spans and the five
        longest gaps between completions, with the step they ended at."""
        gaps = self.step_intervals()
        slow = sorted(range(len(gaps)), key=gaps.__getitem__)[-5:]
        ms = lambda xs: f"median {np.median(xs) * 1e3:.3f} max {np.max(xs) * 1e3:.3f} ms"
        return (f"feed {ms(self.feed_s)}; dispatch {ms(self.dispatch_s)}; "
                f"step {ms(gaps)}; longest at "
                + ", ".join(f"#{i + 1}: {gaps[i] * 1e3:.1f}" for i in slow))

    def finish(self):
        self.losses = [float(x) for x in jax.device_get(self.losses)]
        return self


def drive(program, feed, state, seconds=None, steps=None, after_step=None):
    """Run steps until ``seconds`` have passed or ``steps`` are dispatched,
    then drain.  ``after_step(k, state)`` runs between dispatches (the
    check reads the optimizer's state there).  Returns (state, Stretch)."""
    s = Stretch(program)
    pending = collections.deque()
    annotate = jax.profiler.TraceAnnotation
    clock = time.perf_counter
    s.start = clock()
    k = 0
    while True:
        with jax.profiler.StepTraceAnnotation("bench_step", step_num=k):
            t0 = clock()
            with annotate("bench_feed"):
                batch = feed.next()
            t1 = clock()
            with annotate("bench_dispatch"):
                state, loss = program.step(state, batch)
            t2 = clock()
            s.feed_s.append(t1 - t0)
            s.dispatch_s.append(t2 - t1)
            s.losses.append(loss)
            pending.append(loss)
            if after_step is not None:
                after_step(k, state)
            if len(pending) == IN_FLIGHT:
                with annotate("bench_wait"):
                    pending.popleft().block_until_ready()
                s.stamps.append(clock())
        k += 1
        if (steps is not None and k >= steps) or (
                seconds is not None and clock() - s.start >= seconds):
            break
    with annotate("bench_wait"):
        for loss in pending:
            loss.block_until_ready()
            s.stamps.append(clock())
    return state, s.finish()
