"""Tail-tolerant collectives (ISSUE 11, OptiReduce arXiv:2310.06993):
negotiated per-bucket straggler policies for the DCN stage.

Covers the full per-bucket-property stack: planner/negotiation units
(mixed policies never fuse, native parity, token field 11 with
old-token synthesis), the in-jit policy arithmetic at mesh 2 and 4
(n/k scale correction, bounded-staleness substitution and its cap,
one-program strict/bounded bit-exactness), the eager deadline gate
against pinned chaos seeds, the stall inspector's arrival-timestamp
bookkeeping + straggler EWMA, and the straggler-report → elastic
blacklist soft-failure path.
"""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import free_port, mesh_map

import horovod_tpu.chaos as chaos
from horovod_tpu.ops import collectives
from horovod_tpu.ops.collectives import (TAIL_POLICIES, plan_tail_round,
                                         tail_allreduce_p, tail_round)
from horovod_tpu.ops.engine import TensorTableEntry
from horovod_tpu.ops.fusion import EntrySig, ResponseCache, plan_fusion
from horovod_tpu.stall import EWMA_ALPHA, StallInspector

CROSS, LOCAL = "tstc", "tstl"


def _sig(name, tail="strict", dtype="float32", **kw):
    return EntrySig(name=name, op_type="allreduce", reduce_op="average",
                    dtype=dtype, shape=(4,), process_set_id=0,
                    stacked=False, tail_policy=tail, **kw)


def _pmap2(fn, G, L, in_axes):
    return mesh_map(fn, (G, L), (CROSS, LOCAL), in_axes)


# ---------------------------------------------------------------------------
# planner / negotiation units
# ---------------------------------------------------------------------------

def test_mixed_tail_policies_never_fuse():
    sigs = [_sig("a", "bounded"), _sig("b", "strict"), _sig("c", "bounded")]
    buckets = plan_fusion(sigs, 1 << 20)
    by_pol = [{sigs[i].tail_policy for i in b} for b in buckets]
    assert all(len(s) == 1 for s in by_pol)
    assert len(buckets) == 2
    assert plan_fusion([_sig("a", "stale"), _sig("b", "stale")],
                       1 << 20) == [[0, 1]]


def test_response_cache_key_includes_tail_policy():
    cache = ResponseCache(capacity=8)
    cache.put([_sig("a", "strict")], [[0]])
    assert cache.get([_sig("a", "strict")]) == [[0]]
    # a policy flip is a plan-identity change: the cached plan must miss
    assert cache.get([_sig("a", "bounded")]) is None


def test_native_planner_parity_with_tail_policies():
    from horovod_tpu.native import loader
    core = loader.load()
    if core is None:
        pytest.skip("native core unavailable")
    sigs = [_sig("a", "bounded"), _sig("b", "strict"),
            _sig("c", "bounded"), _sig("d", "stale", dtype="bfloat16")]
    assert core.plan_fusion_sigs(sigs, 1 << 20) == \
        plan_fusion(sigs, 1 << 20)


def test_native_cache_key_includes_tail_policy():
    from horovod_tpu.native import loader
    core = loader.load()
    if core is None:
        pytest.skip("native core unavailable")
    cache = core.ResponseCache(8)
    cache.put([_sig("a", "strict")], [[0]])
    assert cache.get([_sig("a", "strict")]) is not None
    assert cache.get([_sig("a", "stale")]) is None


def _entry(op_type="allreduce", reduce_op="average", tail="bounded"):
    ps = types.SimpleNamespace(process_set_id=0)
    return TensorTableEntry(
        "t", op_type, [np.zeros((4,), np.float32)], ps,
        reduce_op=reduce_op, stacked=False, tail_policy=tail)


def test_entry_token_carries_tail_policy_as_field_11():
    from horovod_tpu.ops.controller import entry_token
    tok = json.loads(entry_token(_entry()))
    assert tok["s"][0][10] == "none"        # field 10: wire_format
    assert tok["s"][0][11] == "bounded"     # field 11: tail_policy


def test_sigs_narrow_tail_policy_to_summable_allreduce():
    assert _entry().sigs()[0].tail_policy == "bounded"
    assert _entry(reduce_op="min").sigs()[0].tail_policy == "strict"
    assert _entry(op_type="allgather").sigs()[0].tail_policy == "strict"


def test_synthesize_tolerates_old_tokens_without_field_11(hvd):
    from horovod_tpu import runtime
    eng = runtime._state().engine
    base = ["t_tail_syn", "allreduce", "average", "float32", [3], 0,
            False, -1, None, None, "none"]
    old = json.dumps({"s": [base], "r": 0, "sp": None},
                     separators=(",", ":"), sort_keys=True)
    entry = eng._synthesize(old)
    assert entry.tail_policy == "strict"      # pre-tail peer: strict
    new = json.dumps({"s": [base + ["stale"]], "r": 0, "sp": None},
                     separators=(",", ":"), sort_keys=True)
    entry = eng._synthesize(new)
    assert entry.tail_policy == "stale"


def test_config_tail_env_parsing(monkeypatch):
    from horovod_tpu.config import Config
    monkeypatch.setenv("HOROVOD_TAIL_POLICY", "Bounded")
    monkeypatch.setenv("HOROVOD_TAIL_DEADLINE_MS", "120")
    monkeypatch.setenv("HOROVOD_TAIL_MAX_STALENESS", "2")
    monkeypatch.setenv("HOROVOD_TAIL_BLACKLIST_SCORE", "1.5")
    c = Config.from_env()
    assert c.tail_policy == "bounded"
    assert c.tail_deadline_ms == 120.0
    assert c.tail_max_staleness == 2
    assert c.tail_blacklist_score == 1.5
    monkeypatch.setenv("HOROVOD_TAIL_POLICY", "lossy")
    with pytest.raises(ValueError, match="HOROVOD_TAIL_POLICY"):
        Config.from_env()
    monkeypatch.setenv("HOROVOD_TAIL_POLICY", "strict")
    monkeypatch.setenv("HOROVOD_TAIL_DEADLINE_MS", "0")
    with pytest.raises(ValueError, match="HOROVOD_TAIL_DEADLINE_MS"):
        Config.from_env()


def test_tail_policy_validation():
    with pytest.raises(ValueError, match="tail_policy"):
        tail_allreduce_p(jnp.zeros((4,)), CROSS, "lossy")
    assert set(TAIL_POLICIES) == {"strict", "bounded", "stale"}


def test_tail_state_required_for_stale():
    def f(x):
        return tail_allreduce_p(x, CROSS, "stale",
                                present=jnp.ones((2,)))[0]
    with pytest.raises(ValueError, match="state"):
        jax.make_jaxpr(f, axis_env=[(CROSS, 2)])(
            jax.ShapeDtypeStruct((4,), jnp.float32))
    with pytest.raises(ValueError, match="participation mask"):
        jax.make_jaxpr(
            lambda x: tail_allreduce_p(x, CROSS, "bounded")[0],
            axis_env=[(CROSS, 2)])(
            jax.ShapeDtypeStruct((4,), jnp.float32))


# ---------------------------------------------------------------------------
# in-jit policy arithmetic (a 2-D mesh of the virtual devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G,L", [(2, 4), (4, 2)])
def test_bounded_scale_correction_numerics(G, L):
    """The n/k correction: excluding one group multiplies the partial
    sum by G/k, exactly."""
    x = np.arange(G * L * 6, dtype=np.float32).reshape(G, L, 6) + 1.0

    def f(xs, present):
        red, _, _ = tail_allreduce_p(xs, CROSS, "bounded",
                                     present=present,
                                     agree_axes=(LOCAL,))
        return red

    g = _pmap2(f, G, L, in_axes=(0, None))
    present = np.ones(G, np.float32)
    present[G - 1] = 0.0
    out = np.asarray(g(x, jnp.asarray(present)))[0, 0]
    # device (0,0) sums its cross peers (g, local=0) over the present
    # groups, scaled G/k with k = G-1
    want = x[:G - 1, 0].sum(0) * (G / (G - 1))
    np.testing.assert_allclose(out, want, rtol=1e-6)
    # cross-replicas agree per local slice (the pmin membership
    # agreement; the local axis is deliberately not reduced here)
    full = np.asarray(g(x, jnp.asarray(present)))
    assert (full[0] == full).all()


def test_bounded_all_present_bit_identical_to_strict_one_program():
    """The bench_tail gate-2 shape at unit scale: ONE compiled program,
    runtime fire gate; with an all-ones mask the bounded branch must be
    BIT-identical to the strict branch."""
    G, L = 2, 4
    rng = np.random.default_rng(3)
    x = rng.standard_normal((G, L, 33)).astype(np.float32)

    def f(xs, fire, present):
        def armed(c):
            return tail_allreduce_p(c, CROSS, "bounded",
                                    present=present,
                                    agree_axes=(LOCAL,))[0]

        def strictly(c):
            return tail_allreduce_p(c, CROSS, "strict")[0]
        return jax.lax.cond(fire, armed, strictly, xs)

    g = _pmap2(f, G, L, in_axes=(0, None, None))
    ones = jnp.ones((G,), jnp.float32)
    a = np.asarray(g(x, jnp.asarray(True), ones))
    b = np.asarray(g(x, jnp.asarray(False), ones))
    assert (a == b).all()


def test_stale_substitution_and_staleness_counters():
    """Round 1 (all present) records contributions; round 2 (group 1
    absent) substitutes group 1's round-1 chunk and bumps its counter;
    round 3 at the staleness cap forces group 1 fresh again."""
    G, L = 2, 2
    C = 4

    def f(xs, present, prev, stal):
        red, np_, ns_ = tail_allreduce_p(
            xs, CROSS, "stale", present=present, prev=prev,
            staleness=stal, max_staleness=1, agree_axes=(LOCAL,))
        return red, np_, ns_

    g = _pmap2(f, G, L, in_axes=(0, None, 0, None))

    def run(x, present, prev, stal):
        r, p2, s2 = g(x, jnp.asarray(present), jnp.asarray(prev),
                      jnp.asarray(stal))
        return (np.asarray(r)[0, 0], np.asarray(p2),
                np.asarray(s2)[0, 0])

    # per-device chunks: psum_scatter is not involved here, each device
    # contributes its own xs; gathered over CROSS -> [G, C] per device
    x1 = np.arange(G * L * C, dtype=np.float32).reshape(G, L, C)
    prev0 = np.zeros((G, L, G, C), np.float32)
    stal0 = np.zeros((G,), np.int32)
    ones = np.ones(G, np.float32)

    r1, prev1, stal1 = run(x1, ones, prev0, stal0)
    # device (0,0)'s cross peers are (g, local=0): sum of x1[:, 0]
    np.testing.assert_allclose(r1, x1[:, 0].sum(0), rtol=1e-6)
    assert (stal1 == 0).all()

    x2 = x1 + 100.0
    mask = np.array([1.0, 0.0], np.float32)
    r2, prev2, stal2 = run(x2, mask, prev1, stal1)
    # group 1's slot substituted from round 1
    np.testing.assert_allclose(r2, x2[0, 0] + x1[1, 0], rtol=1e-6)
    assert list(stal2) == [0, 1]

    # at the cap (max_staleness=1) the mask is overridden: fresh data
    x3 = x1 + 1000.0
    r3, _prev3, stal3 = run(x3, mask, prev2, stal2)
    np.testing.assert_allclose(r3, x3[0, 0] + x3[1, 0], rtol=1e-6)
    assert list(stal3) == [0, 0]


def test_tail_strict_matches_psum():
    G, L = 2, 4
    x = np.arange(G * L * 5, dtype=np.float32).reshape(G, L, 5)

    def f(xs):
        return tail_allreduce_p(xs, CROSS, "strict")[0]

    out = np.asarray(_pmap2(f, G, L, in_axes=(0,))(x))[0, 0]
    np.testing.assert_allclose(out, x.sum(0)[0], rtol=1e-6)


def test_fused_tail_reduce_tree_matches_plain_reduce():
    """fused_tail_reduce_tree (strict and bounded/all-present) equals a
    plain hierarchical average, bucket structure and all."""
    from horovod_tpu.optim.distributed import fused_tail_reduce_tree
    G, L = 2, 2
    tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones((5,), np.float32)}
    stacked = {
        k: np.stack([np.stack([v * (1 + g * L + l) for l in range(L)])
                     for g in range(G)])
        for k, v in tree.items()}
    want = {k: np.mean(stacked[k], axis=(0, 1)) for k in tree}

    for policy in ("strict", "bounded"):
        def step(g):
            red, _ = fused_tail_reduce_tree(
                g, CROSS, LOCAL, op="average", threshold_bytes=32,
                tail_policy=policy,
                present=(jnp.ones((G,), jnp.float32)
                         if policy != "strict" else None))
            return red

        out = _pmap2(step, G, L, in_axes=(0,))(stacked)
        for k in tree:
            np.testing.assert_allclose(np.asarray(out[k])[0, 0],
                                       want[k], rtol=1e-5)


# ---------------------------------------------------------------------------
# eager deadline gate (plan_tail_round; chaos-seeded, deterministic)
# ---------------------------------------------------------------------------

@pytest.fixture
def dcn_chaos():
    def install(rule, seed=7):
        sched = chaos.FaultSchedule.parse(rule, seed=seed)
        chaos.install(sched)
        return sched
    yield install
    chaos.uninstall()


def test_plan_strict_waits_out_the_straggler(dcn_chaos):
    sched = dcn_chaos("collective.dcn group=1 nth=1 action=delay:0.8")
    present, wait, lateness = plan_tail_round("t", "strict", 2, 0.25)
    assert wait == pytest.approx(0.8)
    assert present.tolist() == [1.0, 1.0]
    assert lateness == [0.0, 0.8]
    assert sched.fired_at("collective.dcn")


def test_plan_bounded_excludes_past_deadline(dcn_chaos):
    dcn_chaos("collective.dcn group=1 nth=1 action=delay:0.8")
    insp = StallInspector(check_time=1e9, use_native=False)
    present, wait, _ = plan_tail_round("t", "bounded", 2, 0.25,
                                       stall=insp)
    assert present.tolist() == [1.0, 0.0]
    assert wait == pytest.approx(0.25)     # the deadline, not the delay
    scores = insp.straggler_scores()
    assert scores[1] == pytest.approx(0.8 * EWMA_ALPHA)
    assert scores[0] == 0.0


def test_plan_bounded_fast_round_pays_no_deadline(dcn_chaos):
    dcn_chaos("collective.dcn group=0 nth=1 action=delay:0.05")
    present, wait, _ = plan_tail_round("t", "bounded", 2, 0.25)
    assert present.tolist() == [1.0, 1.0]
    assert wait == pytest.approx(0.05)     # slowest arrival, sub-deadline


def test_plan_stale_cap_refuses_exclusion(dcn_chaos):
    dcn_chaos("collective.dcn group=1 nth=1 action=delay:0.8")
    present, wait, _ = plan_tail_round(
        "t", "stale", 2, 0.25, max_staleness=2,
        staleness=np.array([0, 2], np.int32))
    # group 1 is at the cap: waited out instead of substituted
    assert present.tolist() == [1.0, 1.0]
    assert wait == pytest.approx(0.8)


def test_plan_drop_raises_strict_excludes_bounded(dcn_chaos):
    dcn_chaos("collective.dcn group=0 times=2 action=drop")
    with pytest.raises(chaos.ChaosConnectionError):
        plan_tail_round("t", "strict", 2, 0.25)
    insp = StallInspector(check_time=1e9, use_native=False)
    present, wait, _ = plan_tail_round("t", "bounded", 2, 0.25,
                                       stall=insp)
    assert present.tolist() == [0.0, 1.0]
    assert wait == pytest.approx(0.25)
    # a DROPPED contribution scores as a censored >= deadline
    # observation — a host dropping every round must not look on-time
    assert insp.straggler_scores()[0] == pytest.approx(
        0.25 * EWMA_ALPHA)


def test_tail_round_counts_metric(dcn_chaos):
    from horovod_tpu import metrics as _metrics
    if not _metrics.ACTIVE:
        pytest.skip("metrics disabled")
    tail_round("t", "bounded", 2, 0.0)
    text = _metrics.render_prometheus()
    assert 'hvd_tail_rounds_total{policy="bounded"}' in text


# ---------------------------------------------------------------------------
# stall inspector: arrival timestamps + straggler EWMA
# ---------------------------------------------------------------------------

def test_record_missing_stamps_arrival_timestamps():
    si = StallInspector(check_time=1e9, use_native=False)
    si.record_missing("t", [1, 2], now=100.0)
    assert si.missing_since("t", 1) == 100.0
    assert si.missing_since("t", 2) == 100.0
    # process 1 catches up at 101.5: lateness observed, stamp cleared
    si.record_missing("t", [2], now=101.5)
    assert si.missing_since("t", 1) is None
    assert si.straggler_scores()[1] == pytest.approx(1.5 * EWMA_ALPHA)
    # completion clears the rest, crediting the full gap
    si.record_complete("t", now=102.0)
    assert si.missing_since("t", 2) is None
    assert si.straggler_scores()[2] == pytest.approx(2.0 * EWMA_ALPHA)
    assert si.missing_processes("t") == []


def test_straggler_score_ewma_decays_on_on_time_rounds():
    si = StallInspector(check_time=1e9, use_native=False)
    si.note_lateness(3, 1.0)
    peak = si.straggler_scores()[3]
    for _ in range(20):
        si.note_lateness(3, 0.0)
    assert si.straggler_scores()[3] < peak / 10


def test_on_straggler_fires_edge_triggered_and_rearms():
    fired = []
    si = StallInspector(check_time=1e9, use_native=False,
                        blacklist_score=0.5,
                        on_straggler=lambda p, s: fired.append((p, s)))
    for _ in range(8):
        si.note_lateness(1, 3.0)
    assert len(fired) == 1 and fired[0][0] == 1
    assert fired[0][1] >= 0.5
    # decay below half the bar re-arms the trigger
    for _ in range(30):
        si.note_lateness(1, 0.0)
    for _ in range(8):
        si.note_lateness(1, 3.0)
    assert len(fired) == 2


def test_disabled_inspector_scores_nothing():
    si = StallInspector(check_time=1e9, disabled=True, use_native=False)
    si.note_lateness(1, 5.0)
    si.record_missing("t", [1], now=1.0)
    assert si.straggler_scores() == {}


def test_straggler_scores_in_engine_stats(hvd):
    from horovod_tpu import runtime
    st = runtime._state()
    if st.stall_inspector is None or st.stall_inspector.disabled:
        pytest.skip("stall inspector disabled in this run")
    st.stall_inspector.note_lateness(0, 0.0)
    stats = st.engine.stats()
    assert "straggler_scores" in stats["stall"]
    assert 0 in stats["stall"]["straggler_scores"]


# ---------------------------------------------------------------------------
# straggler reports -> elastic blacklist (soft failures)
# ---------------------------------------------------------------------------

from horovod_tpu.elastic import discovery, registration  # noqa: E402
from horovod_tpu.elastic.driver import ElasticDriver  # noqa: E402
from horovod_tpu.elastic.worker import HostUpdateResult  # noqa: E402


class _StubProc:
    class _Popen:
        def poll(self):
            return None

        def terminate(self):
            pass

    def __init__(self):
        self.popen = self._Popen()


class _NoSpawnDriver(ElasticDriver):
    def _launch(self, slot, coord_addr, coord_port, env):
        return _StubProc()

    def _notify_workers(self, targets, update_res):
        pass


def test_registry_soft_failures_feed_blacklist():
    reg = registration.WorkerStateRegistry(blacklist_threshold=2)
    reg.record_soft_failure("hostA")
    assert reg.failure_count("hostA") == 1
    assert reg.soft_failure_count("hostA") == 1
    assert not reg.is_blacklisted("hostA")
    reg.record_result(3, registration.FAILURE, "hostA")
    # soft + hard failures share one threshold
    assert reg.is_blacklisted("hostA")


def test_straggler_reports_blacklist_before_a_crash():
    d = _NoSpawnDriver(
        discovery.FixedHostDiscovery({"hostA": 1}), ["true"],
        min_np=1, port=free_port(), blacklist_threshold=2,
        straggler_blacklist_score=0.5)
    try:
        d._apply_hosts({"hostA": 1}, HostUpdateResult.ADDED)
        r = d._handle_straggler(
            {"worker_id": 0, "process": 0, "score": 0.9})
        assert r["ok"] and r["counted"] and not r["blacklisted"]
        assert d.registry.failure_count("hostA") == 1
        # same epoch: debounced — many peers reporting one straggler
        # must count ONE soft failure
        r = d._handle_straggler(
            {"worker_id": 0, "process": 0, "score": 2.0})
        assert r["ok"] and not r["counted"]
        # below the bar: ignored
        r = d._handle_straggler(
            {"worker_id": 0, "process": 0, "score": 0.2})
        assert r["ok"] and not r["counted"]
        # unknown rank: rejected
        r = d._handle_straggler(
            {"worker_id": 0, "process": 9, "score": 2.0})
        assert not r["ok"]
        # a new epoch re-opens the debounce; threshold 2 blacklists the
        # host WITHOUT it ever crashing
        d._apply_hosts({"hostA": 1}, HostUpdateResult.MIXED)
        r = d._handle_straggler(
            {"worker_id": 0, "process": 0, "score": 1.1})
        assert r["counted"] and r["blacklisted"]
        assert d.registry.is_blacklisted("hostA")
        assert d.registry.soft_failure_count("hostA") == 2
        assert d._discover() == {}
        events = [e for e, _ in d._events if e == "straggler_reported"]
        assert len(events) == 2
    finally:
        d._server.close()


def test_straggler_reports_ignored_when_bar_disabled():
    """HOROVOD_TAIL_BLACKLIST_SCORE unset/0 on the DRIVER disables
    counting entirely — a worker launched with the var set must not
    feed a blacklist its driver disabled."""
    d = _NoSpawnDriver(
        discovery.FixedHostDiscovery({"hostA": 1}), ["true"],
        min_np=1, port=free_port(), blacklist_threshold=1,
        straggler_blacklist_score=0.0)
    try:
        d._apply_hosts({"hostA": 1}, HostUpdateResult.ADDED)
        r = d._handle_straggler(
            {"worker_id": 0, "process": 0, "score": 99.0})
        assert r["ok"] and not r["counted"]
        assert d.registry.failure_count("hostA") == 0
        assert not d.registry.is_blacklisted("hostA")
    finally:
        d._server.close()


# ---------------------------------------------------------------------------
# schedule pins: the tail entry's rewritten DCN stage
# ---------------------------------------------------------------------------

def test_tail_distopt_schedule_shape():
    """The committed tail_distopt_step snapshot's claim, re-asserted
    structurally: per bucket, a pmin membership agreement + a cross-axis
    all_gather (the substitutable per-host exchange) and NO cross-axis
    psum; bucket ids attributable throughout."""
    from horovod_tpu.analysis.schedule import builtin_schedule
    sched = builtin_schedule("tail_distopt_step", 2)
    assert all(r.bucket is not None for r in sched.records)
    cross = [r for r in sched.records if "workers" in r.axes]
    assert cross and all(r.prim in ("pmin", "all_gather") for r in cross)
    buckets = {r.bucket for r in sched.records}
    for b in buckets:
        prims = [r.prim for r in sched.records if r.bucket == b]
        assert prims == ["reduce_scatter", "pmin", "pmin",
                         "all_gather", "all_gather"], prims


def test_bounded_schedule_keeps_psum_adds_agreement():
    from horovod_tpu.analysis.schedule import trace_schedule
    from horovod_tpu.analysis.wire import prim_counts
    from horovod_tpu.optim.distributed import fused_tail_reduce_tree
    spec = {"w": jax.ShapeDtypeStruct((16,), jnp.float32)}
    env = [(CROSS, 2), (LOCAL, 2)]

    def step(g):
        red, _ = fused_tail_reduce_tree(
            g, CROSS, LOCAL, op="average", threshold_bytes=1 << 20,
            tail_policy="bounded",
            present=jnp.ones((2,), jnp.float32))
        return red

    counts = prim_counts(trace_schedule(step, (spec,), axis_env=env))
    assert counts == {"reduce_scatter": 1, "pmin": 2, "psum": 1,
                      "all_gather": 1}


def test_lateness_histogram_family_observes_per_process():
    """ISSUE 12 satellite: every lateness observation the EWMA ingests
    also lands in hvd_tail_lateness_seconds{process} — the EWMA alone
    cannot distinguish a chronic 100 ms host from a rare 2 s one; the
    fixed-edge histogram merges bucket-wise in /metrics/job."""
    from horovod_tpu import metrics as _metrics
    from horovod_tpu.stall import _m_lateness
    if not _metrics.ACTIVE:
        pytest.skip("metrics disabled")
    si = StallInspector(check_time=1e9, use_native=False)
    before = _m_lateness.child(process="91")
    n0 = before.count if before is not None else 0
    s0 = before.sum if before is not None else 0.0
    si.note_lateness(91, 0.1)
    si.note_lateness(91, 2.0)
    si.note_lateness(91, 0.0)   # on-time rounds observe too (the decay)
    child = _m_lateness.child(process="91")
    assert child.count == n0 + 3
    assert child.sum == pytest.approx(s0 + 2.1)
    # fixed log2 edges, so per-worker series merge bucket-wise: the
    # 2.0 s observation sits in a strictly higher bucket than 0.1 s
    import bisect
    assert (bisect.bisect_left(_m_lateness.edges, 2.0)
            > bisect.bisect_left(_m_lateness.edges, 0.1))
    text = _metrics.render_prometheus()
    assert 'hvd_tail_lateness_seconds_count{process="91"}' in text
