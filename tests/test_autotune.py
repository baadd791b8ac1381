"""Autotuner (parameter_manager.cc analog) + profiler-range tests.

Reference parity: the reference tunes fusion threshold AND cycle time
with a GP/EI loop through warmup → sample → tuned phases, logging to
HOROVOD_AUTOTUNE_LOG (SURVEY.md §2.1).  These tests drive the 2-D
manager directly and through a live engine.
"""

import glob
import math
import os

import numpy as np
import pytest

from _helpers import free_port

from horovod_tpu.autotune import _CYCLE_GRID_MS, ParameterManager
from horovod_tpu.config import Config


def _cfg(**kw):
    c = Config()
    c.autotune = True
    c.autotune_warmup_samples = kw.pop("warmup", 1)
    c.autotune_steps_per_sample = kw.pop("steps", 2)
    c.autotune_max_samples = kw.pop("max_samples", 6)
    for k, v in kw.items():
        setattr(c, k, v)
    return c


def _feed(pm, score_fn, n_cycles=400):
    """Drive record_cycle with a synthetic throughput model until tuned."""
    for _ in range(n_cycles):
        if pm.tuned:
            break
        thr = pm.current_fusion_threshold()
        cyc = pm.current_cycle_time_ms()
        bps = score_fn(thr, cyc)
        pm.record_cycle(nbytes=int(bps), elapsed_s=1.0)
    return pm


def test_tunes_both_dimensions_and_converges():
    pm = ParameterManager(_cfg())
    # synthetic optimum: 64 MiB threshold, 1.0 ms cycle
    def score(thr, cyc):
        t = -abs(math.log2(thr) - 26)
        c = -abs(cyc - 1.0)
        return 1e9 * math.exp(t + c)
    _feed(pm, score)
    assert pm.tuned
    # converged point must be one of the sampled grid points, and the
    # numeric dims must have been explored
    xs = pm._gp.xs
    assert len({x[0] for x in xs}) > 1 or len({x[1] for x in xs}) > 1
    assert pm.current_cycle_time_ms() in _CYCLE_GRID_MS
    assert pm._current in set(xs)


def test_converges_at_sample_budget():
    pm = ParameterManager(_cfg(max_samples=4))
    _feed(pm, lambda thr, cyc: 1.0)
    assert pm.tuned
    assert len(pm._gp.xs) == 4


def test_autotune_log_schema(tmp_path):
    log = str(tmp_path / "autotune.csv")
    pm = ParameterManager(_cfg(autotune_log=log, max_samples=3))
    _feed(pm, lambda thr, cyc: thr)
    pm._log_file.flush()
    lines = open(log).read().strip().splitlines()
    assert lines[0] == ("timestamp,fusion_threshold_bytes,cycle_time_ms,"
                        "cache,hierarchical,compression,"
                        "score_bytes_per_sec,phase")
    assert any(line.endswith("tuned") for line in lines[1:])
    # every row carries a cycle time from the grid and binary flags
    for line in lines[1:]:
        cols = line.split(",")
        assert float(cols[2]) in _CYCLE_GRID_MS
        assert cols[3] in ("0", "1") and cols[4] in ("0", "1")
        assert cols[5] in ("0", "1")


def test_engine_reads_tuned_cycle_time(hvd):
    """A live engine re-reads the autotuner's cycle time every loop."""
    from horovod_tpu import runtime
    eng = runtime._state().engine
    pm = ParameterManager(_cfg())
    old = eng.autotuner
    eng.autotuner = pm
    try:
        pm._current = (pm._current[0], float(_CYCLE_GRID_MS.index(5.0)))
        assert eng._cycle_time_s() == pytest.approx(0.005)
        pm._current = (pm._current[0], 0.0)
        assert eng._cycle_time_s() == 0.0
    finally:
        eng.autotuner = old


def test_profiler_ranges_capture_dispatch(hvd, tmp_path):
    """start_profiler/stop_profiler wrap jax.profiler; engine dispatches
    inside TraceAnnotation ranges land in the trace (NVTX analog)."""
    logdir = str(tmp_path / "prof")
    hvd.start_profiler(logdir)
    hvd.allreduce(np.ones((4,), np.float32), name="prof_t")
    hvd.stop_profiler()
    traces = glob.glob(os.path.join(logdir, "**", "*.pb"), recursive=True) \
        + glob.glob(os.path.join(logdir, "**", "*.json.gz"), recursive=True) \
        + glob.glob(os.path.join(logdir, "**", "*.trace.json*"),
                    recursive=True)
    assert traces, f"no trace files under {logdir}"


def test_configured_cycle_time_honored_before_tuning():
    """Enabling autotune must not snap the configured cycle time to the
    default grid (review regression): 0.2 ms stays 0.2 ms at start."""
    pm = ParameterManager(_cfg(cycle_time_ms=0.2))
    assert pm.current_cycle_time_ms() == pytest.approx(0.2)
    assert 0.2 in pm._cycle_grid


def test_retune_on_sustained_regression():
    """VERDICT r3 #8: a sustained score drop after convergence re-enters
    sampling (reference: parameter_manager re-tunes on regression) and
    converges again on the shifted workload."""
    pm = ParameterManager(_cfg(max_samples=3))
    _feed(pm, lambda thr, cyc: 1e6)
    assert pm.tuned
    # >20% drop for retune_windows consecutive windows
    for _ in range(pm.retune_windows * pm.steps_per_sample):
        pm.record_cycle(nbytes=int(0.5e6), elapsed_s=1.0)
    assert not pm.tuned
    assert pm.retunes == 1
    assert pm._best is None            # stale surrogate discarded
    _feed(pm, lambda thr, cyc: 0.5e6)  # converges on the new workload
    assert pm.tuned


def test_transient_dip_does_not_retune():
    """A recovery window resets the consecutive-regression count."""
    pm = ParameterManager(_cfg(max_samples=3))
    _feed(pm, lambda thr, cyc: 1e6)
    assert pm.tuned
    for _ in range(2 * pm.steps_per_sample):
        pm.record_cycle(int(0.5e6), 1.0)     # 2 bad windows
    for _ in range(pm.steps_per_sample):
        pm.record_cycle(int(1e6), 1.0)       # recovery
    for _ in range(2 * pm.steps_per_sample):
        pm.record_cycle(int(0.5e6), 1.0)     # 2 more bad windows
    assert pm.tuned
    assert pm.retunes == 0


def test_retune_disabled_with_zero_drop():
    pm = ParameterManager(_cfg(max_samples=3, autotune_retune_drop=0.0))
    _feed(pm, lambda thr, cyc: 1e6)
    assert pm.tuned
    for _ in range(10 * pm.steps_per_sample):
        pm.record_cycle(int(1e3), 1.0)
    assert pm.tuned and pm.retunes == 0


def test_negotiated_autotune_identical_across_processes():
    """VERDICT r3 #3: multi-process jobs TUNE (instead of pinning to
    config): tuned parameters ride the negotiation round and both
    processes apply identical values (rank-0 sync, cycle-exact)."""
    import helpers_runner
    from horovod_tpu.runner import run

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = run(
        helpers_runner.negotiated_autotune_fn, np=2,
        env={
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "HOROVOD_CYCLE_TIME": "0.2",
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "0",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
            "HOROVOD_AUTOTUNE_MAX_SAMPLES": "3",
            "HOROVOD_AUTOTUNE_RETUNE_DROP": "0",
        },
        port=free_port())
    by_rank = {r["rank"]: r for r in results}
    assert by_rank[0]["negotiated"] and by_rank[1]["negotiated"]
    assert by_rank[0]["thr"] == by_rank[1]["thr"]
    assert by_rank[0]["cyc"] == by_rank[1]["cyc"]


def test_tunes_cache_dimension():
    """The categorical response-cache dim is part of the search
    (reference: parameter_manager tunes cache on/off): a workload where
    cache-off scores higher converges with the cache disabled."""
    pm = ParameterManager(_cfg(max_samples=60))
    for _ in range(800):
        if pm.tuned:
            break
        bps = 1e9 if not pm.current_cache_enabled() else 1e5
        pm.record_cycle(nbytes=int(bps), elapsed_s=1.0)
    assert pm.tuned
    assert pm.current_cache_enabled() is False


def test_engine_applies_cache_and_hier_toggles(hvd):
    """The live engine honors the tuner's cache/hierarchical dims each
    cycle: cache-off cycles never touch the plan cache, and the applied
    values surface in engine.stats()['autotune']."""
    from horovod_tpu import runtime

    eng = runtime._state().engine
    pm = ParameterManager(_cfg())
    old_tuner = eng.autotuner
    old_hier = eng.cfg.hierarchical_allreduce
    eng.autotuner = pm
    try:
        pm._current = (pm._current[0], pm._current[1], 0.0, 0.0, 0.0)
        before = eng.stats()["cache"]["entries"]
        hvd.allreduce(np.ones((4,), np.float32), name="ca_off_t")
        st = eng.stats()
        assert st["cache"]["entries"] == before   # cache bypassed
        assert st["autotune"]["cache_enabled"] is False
        assert st["autotune"]["hierarchical"] is False
        pm._current = (pm._current[0], pm._current[1], 1.0, 0.0, 0.0)
        hvd.allreduce(np.ones((4,), np.float32), name="ca_on_t")
        st = eng.stats()
        assert st["cache"]["entries"] > before    # cache back on
        assert st["autotune"]["cache_enabled"] is True
    finally:
        eng.autotuner = old_tuner
        eng.cfg.hierarchical_allreduce = old_hier


def test_cache_dim_pinned_when_capacity_zero():
    """HOROVOD_CACHE_CAPACITY=0 hard-disables the plan cache, so the
    tuner must not explore (or converge to) cache-on candidates that
    cannot take effect."""
    pm = ParameterManager(_cfg(cache_capacity=0, max_samples=10))
    assert pm.current_cache_enabled() is False
    assert all(p[2] == 0.0 for p in pm._grid)
    _feed(pm, lambda thr, cyc: 1e6)
    assert pm.tuned and pm.current_cache_enabled() is False


def test_negotiated_autotune_survives_leader_join():
    """After the publishing leader joins, followers keep the last agreed
    parameters (frozen, not replaced by an untrained tuner's view) and
    the job completes."""
    import helpers_runner
    from horovod_tpu.runner import run

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = run(
        helpers_runner.autotune_leader_join_fn, np=2,
        env={
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "HOROVOD_CYCLE_TIME": "0.2",
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "0",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
            "HOROVOD_AUTOTUNE_RETUNE_DROP": "0",
        },
        port=free_port())
    by_rank = {r["rank"]: r for r in results}
    assert by_rank[1]["neg"]                  # params were negotiated
    assert by_rank[0]["last"] == 1            # rank 1 joined last
    assert by_rank[1]["thr"] > 0
