"""Parity tests: native C++ core (_hvd_core) vs the pure-Python control plane.

SURVEY.md §2.1: the reference implements the fusion planner
(controller.cc FuseResponses), response cache (response_cache.cc), timeline
writer (timeline.cc) and stall inspector (stall_inspector.cc) in C++.  Our
native core reimplements the same algorithms; these tests pin native output
to the Python reference implementation on randomized inputs.
"""

import json
import random

import pytest

from horovod_tpu.ops import fusion
from horovod_tpu.native import loader

core = loader.load()
pytestmark = pytest.mark.skipif(
    core is None, reason="native core not built (no C++ toolchain)")


from _helpers import random_entry_sigs as _random_sigs


@pytest.mark.parametrize("seed", range(20))
def test_planner_parity_randomized(seed):
    rng = random.Random(seed)
    sigs = _random_sigs(rng, rng.randint(0, 40))
    threshold = rng.choice([1, 1024, 64 * 1024, 64 * 1024 * 1024])
    assert core.plan_fusion_sigs(sigs, threshold) == \
        fusion.plan_fusion(sigs, threshold)


def test_planner_groups_exceed_threshold():
    sigs = [fusion.EntrySig(name=f"g{i}", op_type="allreduce",
                            reduce_op="average", dtype="float32",
                            shape=(1024,), process_set_id=0, stacked=False,
                            group_id=7)
            for i in range(4)]
    # group fuses atomically even though 4*4KiB > 1-byte threshold
    assert core.plan_fusion_sigs(sigs, 1) == [[0, 1, 2, 3]]
    assert fusion.plan_fusion(sigs, 1) == [[0, 1, 2, 3]]


def test_planner_empty():
    assert core.plan_fusion_sigs([], 1024) == []


def _sigs(names, **kw):
    defaults = dict(op_type="allreduce", reduce_op="average",
                    dtype="float32", shape=(16,), process_set_id=0,
                    stacked=False)
    defaults.update(kw)
    return [fusion.EntrySig(name=n, **defaults) for n in names]


class TestNativeResponseCache:
    def test_hit_miss_and_stats(self):
        c = core.ResponseCache(8)
        s = _sigs(["a", "b"])
        assert c.get(s) is None
        c.put(s, [[0, 1]])
        assert c.get(s) == [[0, 1]]
        st = c.stats()
        assert st["hits"] == 1 and st["misses"] == 1 and st["entries"] == 1

    def test_distinct_keys(self):
        c = core.ResponseCache(8)
        c.put(_sigs(["a", "b"]), [[0, 1]])
        # different name list must not collide
        assert c.get(_sigs(["a", "c"])) is None
        # different dtype must not collide
        assert c.get(_sigs(["a", "b"], dtype="bfloat16")) is None
        # prescale None vs 1.0 are distinct keys (matches the Python cache,
        # which keys on dataclasses.astuple)
        assert c.get(_sigs(["a", "b"], prescale=1.0)) is None

    def test_lru_eviction(self):
        c = core.ResponseCache(2)
        a, b, d = _sigs(["a"]), _sigs(["b"]), _sigs(["d"])
        c.put(a, [[0]])
        c.put(b, [[0]])
        assert c.get(a) == [[0]]   # refresh a
        c.put(d, [[0]])            # evicts b (least recent)
        assert c.get(b) is None
        assert c.get(a) == [[0]]
        assert c.get(d) == [[0]]

    def test_zero_capacity_disabled(self):
        c = core.ResponseCache(0)
        s = _sigs(["a"])
        c.put(s, [[0]])
        assert c.get(s) is None


class TestNativeTimelineWriter:
    def test_valid_chrome_trace(self, tmp_path):
        path = str(tmp_path / "trace.json")
        w = core.TimelineWriter(path)
        for i in range(100):
            w.write(json.dumps({"name": f"ev{i}", "ph": "B", "pid": 0,
                                "tid": 1, "ts": i * 1.0}))
        w.close()
        events = json.load(open(path))
        assert len(events) == 100
        assert events[0]["name"] == "ev0" and events[99]["name"] == "ev99"

    def test_write_after_close_is_noop(self, tmp_path):
        path = str(tmp_path / "trace.json")
        w = core.TimelineWriter(path)
        w.write("{}")
        w.close()
        w.write("{}")  # must not crash or corrupt
        w.close()      # idempotent
        assert json.load(open(path)) == [{}]

    def test_timeline_class_uses_native(self, tmp_path):
        from horovod_tpu.timeline import Timeline
        path = str(tmp_path / "t.json")
        tl = Timeline(path, mark_cycles=True)
        assert tl._native is not None
        tl.negotiate_start("grad.0", "allreduce")
        tl.activity_start(["grad.0"], "MEMCPY_IN_FUSION_BUFFER")
        tl.activity_transition(["grad.0"], "XLA_ALLREDUCE")
        tl.activity_end(["grad.0"])
        tl.cycle_mark(1)
        tl.close()
        events = json.load(open(path))
        names = [e["name"] for e in events]
        assert "NEGOTIATE_ALLREDUCE" in names
        assert "XLA_ALLREDUCE" in names
        assert "CYCLE_START" in names


class TestNativeStallTracker:
    def test_warn_once_then_clear(self):
        t = core.StallTracker(check_time=10.0, shutdown_time=0.0)
        t.record_enqueue("x", 100.0)
        t.record_enqueue("y", 105.0)
        stalled, shutdown = t.check(111.0)
        assert stalled == [("x", 11.0)] and shutdown is None
        # already warned: not reported again
        stalled, _ = t.check(112.0)
        assert stalled == []
        # y crosses the bar later
        stalled, _ = t.check(116.0)
        assert stalled == [("y", 11.0)]
        t.record_complete("x")
        t.record_complete("y")
        assert t.pending_count() == 0

    def test_shutdown_offender(self):
        t = core.StallTracker(check_time=1.0, shutdown_time=5.0)
        t.record_enqueue("x", 0.0)
        _, shutdown = t.check(6.0)
        assert shutdown == ("x", 6.0)

    def test_inspector_native_shutdown_raises(self):
        from horovod_tpu.stall import StallInspector
        from horovod_tpu.exceptions import StallError
        ins = StallInspector(check_time=1.0, shutdown_time=5.0)
        assert ins._native is not None
        ins.record_enqueue("x", 0.0)
        with pytest.raises(StallError):
            ins.check(now=10.0)

    def test_earliest_enqueue_wins(self):
        t = core.StallTracker(check_time=10.0)
        t.record_enqueue("x", 100.0)
        t.record_enqueue("x", 200.0)  # setdefault semantics
        stalled, _ = t.check(111.0)
        assert stalled == [("x", 11.0)]


def test_kill_switch_env(monkeypatch):
    """HOROVOD_TPU_NATIVE_CORE=0 must disable every native call site."""
    from horovod_tpu.stall import StallInspector
    from horovod_tpu.timeline import Timeline
    monkeypatch.setenv("HOROVOD_TPU_NATIVE_CORE", "0")
    assert loader.load() is None
    ins = StallInspector(check_time=1.0)
    assert ins._native is None
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        tl = Timeline(os.path.join(d, "t.json"))
        assert tl._native is None
        tl.close()
    monkeypatch.delenv("HOROVOD_TPU_NATIVE_CORE")
    assert loader.load() is not None


def test_stale_artefact_is_not_imported(monkeypatch):
    """An artefact older than core.cpp is rebuilt, or — where building
    is not allowed — left alone: never loaded in the source's place."""
    from horovod_tpu.native import build
    monkeypatch.setattr(loader, "_core", None)
    monkeypatch.setattr(loader, "_attempted", False)
    monkeypatch.setattr(build, "built", lambda: False)
    assert loader.load(auto_build=False) is None
    monkeypatch.setenv("HOROVOD_TPU_NATIVE_BUILD", "0")
    assert loader.load() is None
    assert loader._attempted is False  # a later caller may still build


def test_negotiate_decide_parity():
    """Native negotiate_decide matches the Python decision loop on random
    announcement multisets (reference: controller.cc ComputeResponseList
    intersection)."""
    core = pytest.importorskip("horovod_tpu.native.loader").load()
    if core is None or not hasattr(core, "negotiate_decide"):
        pytest.skip("native core unavailable")
    import random
    from collections import Counter
    rng = random.Random(7)
    tokens = [f"tok{i}" for i in range(6)]
    for _ in range(25):
        nprocs = rng.randint(2, 5)
        full = {p: [rng.choice(tokens)
                    for _ in range(rng.randint(0, 8))]
                for p in range(nprocs)}
        active = sorted(rng.sample(range(nprocs),
                                   rng.randint(1, nprocs)))
        counters = {p: Counter(full[p]) for p in full}
        all_tokens = sorted(set().union(*[set(c)
                                          for c in counters.values()]))
        # python reference
        want_counts, want_lag, want_def = Counter(), {}, 0
        for t in all_tokens:
            k = min(counters[q][t] for q in active)
            if k > 0:
                want_counts[t] = k
            peak = max(counters[q][t] for q in active)
            lag = [q for q in active if counters[q][t] < peak]
            if lag:
                want_lag[t] = lag
            want_def += max(counters[q][t] for q in counters) - k
        counts, lagging, deferred = core.negotiate_decide(full, active)
        assert Counter(counts) == want_counts
        assert {k: sorted(v) for k, v in lagging.items()} == want_lag
        assert deferred == want_def
