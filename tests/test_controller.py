"""Cross-process controller / negotiation tests.

Reference parity: the behaviors of ``horovod/common/controller.cc``
``ComputeResponseList`` (SURVEY.md §2.1, §3.2) — intersection dispatch,
steady-state cache fast path, stall diagnosis with tensor + rank names,
and ``join()`` with uneven inputs — exercised through REAL 2-process
launches on localhost (the reference's test/parallel style).
"""

import os

import numpy as np
import pytest

from _helpers import free_port

import helpers_runner
from horovod_tpu.runner import run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(extra=None):
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + ":" + os.path.join(REPO, "tests"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    if extra:
        env.update(extra)
    return env


def test_eager_cross_process_allreduce():
    """The engine's eager path does a REAL cross-process reduction:
    rank-dependent inputs, negotiated dispatch, lifted onto the mesh."""
    results = run(helpers_runner.eager_allreduce_fn, np=2, env=_env(),
                  port=free_port())
    by_rank = {r["rank"]: r for r in results}
    # sum: (r0+1) + (r1+1) = 3 everywhere
    assert by_rank[0]["sum"] == [3.0] * 4
    assert by_rank[1]["sum"] == [3.0] * 4
    # average: (10 + 20) / 2 = 15 everywhere
    assert by_rank[0]["avg"] == [15.0] * 2
    assert by_rank[1]["avg"] == [15.0] * 2
    assert all(r["rounds"] >= 1 for r in results)


def test_steady_state_hash_fast_path():
    """After the first full negotiation of a cycle signature, identical
    cycles take the hash-only round (response-cache bit-vector analog)."""
    results = run(helpers_runner.steady_state_fast_path_fn, np=2,
                  env=_env(), port=free_port())
    for r in results:
        assert r["fast"] >= 1, r
        assert r["full"] >= 1, r  # the first round was a full one


def test_late_tensor_waits_and_dispatches():
    """A tensor submitted 1.5s late on one process must not error or hang:
    the peer's entry is requeued until both are ready."""
    results = run(helpers_runner.late_tensor_fn, np=2, env=_env(),
                  port=free_port())
    for r in results:
        assert r["sum"] == [1.0] * 3  # 0 + 1


def test_divergent_tensor_diagnosed_not_hung():
    """One tensor per process that the peer never submits: the job must
    DIAGNOSE (error naming tensor and missing process) instead of hanging
    — the reference's defining stall-inspector behavior (SURVEY §5.2)."""
    results = run(
        helpers_runner.divergent_tensor_fn, np=2,
        env=_env({
            "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "4",
        }),
        port=free_port())
    by_rank = {r["rank"]: r for r in results}
    # the common tensor dispatched fine on both
    assert by_rank[0]["common"] == [2.0] * 2
    assert by_rank[1]["common"] == [2.0] * 2
    # each divergent tensor was diagnosed with its name + missing process
    assert by_rank[0]["error"] is not None
    assert "only0" in by_rank[0]["error"]
    assert "1" in by_rank[0]["error"]          # names the missing process
    assert by_rank[1]["error"] is not None
    assert "only1" in by_rank[1]["error"]


def test_shape_mismatch_is_divergence_error():
    """Same name, incompatible shapes → immediate, consistent error on all
    processes (reference: controller.cc mismatched-request status)."""
    results = run(
        helpers_runner.shape_mismatch_fn, np=2,
        env=_env({"HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "10"}),
        port=free_port())
    for r in results:
        assert r["error"] is not None
        assert "bad_tensor" in r["error"]
        assert "mismatched" in r["error"]


def test_join_uneven_batches():
    """Reference join() semantics: process 1 exhausts its 2 batches and
    joins; process 0's 3rd allreduce proceeds with a zero contribution
    from the joined process; join() returns the last joiner's rank."""
    results = run(helpers_runner.join_uneven_fn, np=2, env=_env(),
                  port=free_port())
    by_rank = {r["rank"]: r for r in results}
    # batches 1-2: sum of (r0+1)*i + (r1+1)*i = 3i
    assert by_rank[0]["sums"][:2] == [3.0, 6.0]
    assert by_rank[1]["sums"] == [3.0, 6.0]
    # batch 3 on rank 0 only: 3 + 0 (zero contribution from joined rank 1)
    assert by_rank[0]["sums"][2] == 3.0
    # rank 0 joined last
    assert by_rank[0]["last_joiner"] == 0
    assert by_rank[1]["last_joiner"] == 0


def test_subset_process_set_does_not_wait_on_non_members():
    """Per-group rounds (reference: per-process-set controllers): a
    collective on a [0]-only process set completes while process 1 is
    idle, instead of stalling on the global round."""
    results = run(
        helpers_runner.subset_process_set_fn, np=2,
        env=_env({"HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "20"}),
        port=free_port())
    by_rank = {r["rank"]: r for r in results}
    assert by_rank[0]["sub"] == [1.0, 1.0]  # single-member sum
    assert by_rank[1]["sub"] is None
    assert by_rank[0]["done"] == 2.0 and by_rank[1]["done"] == 2.0


def test_reinit_cycle_negotiation_isolated():
    """init → shutdown → init: the new incarnation's negotiation must not
    read the previous incarnation's keys or leave markers."""
    results = run(helpers_runner.reinit_cycle_fn, np=2, env=_env(),
                  port=free_port())
    for r in results:
        assert r["vals"] == [[3.0, 3.0], [3.0, 3.0]]


def test_response_cache_hits_on_auto_named_tensors(hvd):
    """VERDICT #6: call-site-derived auto names make the response cache
    hit across a loop of unnamed allreduces (reference: response_cache.cc
    steady state)."""
    from horovod_tpu import runtime
    eng = runtime._state().engine
    before = eng.stats()["cache"]["hits"]
    for _ in range(5):
        hvd.allreduce(np.ones((3,), np.float32))  # no name given
    after = eng.stats()["cache"]["hits"]
    assert after > before


def test_single_process_join_returns_size_minus_one(hvd):
    assert hvd.join() == hvd.size() - 1


def test_barrier_holds_early_process():
    """The engine barrier is a real member rendezvous: the on-time process
    waits ~the straggler's delay before proceeding."""
    results = run(helpers_runner.barrier_fn, np=2, env=_env(), port=free_port())
    by_rank = {r["rank"]: r for r in results}
    assert by_rank[0]["waited"] > 0.5   # held for the late process
    assert by_rank[1]["waited"] < 0.5   # straggler passes straight through
    assert all(r["sum"] == 1.0 for r in results)


def test_hash_cache_lru_eviction_cross_process():
    """VERDICT r3 #4: the controller's steady-state hash cache is an LRU
    bounded by HOROVOD_CACHE_CAPACITY (reference: response_cache.cc);
    driving more distinct cycle signatures than capacity keeps the cache
    bounded, counts evictions, and an evicted signature still reduces
    correctly when it recurs."""
    results = run(helpers_runner.cache_eviction_fn, np=2,
                  env=_env({"HOROVOD_CACHE_CAPACITY": "2"}), port=free_port())
    for r in results:
        assert r["sum"] == [3.0, 3.0]          # (1)+(2) both times
        assert r["capacity"] == 2
        assert r["cached"] <= 2                # bounded
        assert r["evictions"] >= 1             # sig_a (at least) evicted


def test_hash_cache_lru_bounds_and_recency():
    """Unit-level LRU semantics: capacity enforced, eviction counter
    advances, and recency (not insertion order) decides the victim."""
    from horovod_tpu.ops.controller import Controller

    class Cfg:
        cache_capacity = 3

    ctl = Controller(Cfg())
    with ctl._lock:
        for i in range(10):
            ctl._cache_put("g", f"h{i}")
    assert len(ctl._hash_cache) == 3
    assert ctl.stats()["cache_evictions"] == 7
    with ctl._lock:
        assert ctl._cache_touch("g", "h7")     # refresh oldest survivor
        ctl._cache_put("g", "hx")              # evicts h8, not h7
        assert ctl._cache_touch("g", "h7")
        assert not ctl._cache_touch("g", "h8")

    class Cfg0:
        cache_capacity = 0                     # disables the fast path

    ctl0 = Controller(Cfg0())
    with ctl0._lock:
        ctl0._cache_put("g", "h0")
        assert not ctl0._cache_touch("g", "h0")
    assert len(ctl0._hash_cache) == 0


def test_stats_and_set_joined_responsive_during_slow_round():
    """VERDICT r3 #9: the state lock is not held across blocking peer
    waits — set_joined() and stats() return promptly while negotiate()
    is waiting on a slow peer, and the round still completes once the
    peer answers."""
    import json
    import threading
    import time

    from horovod_tpu.ops import controller as ctl_mod

    release = threading.Event()

    class FakeClient:
        def __init__(self):
            self.kv = {}

        def key_value_set(self, k, v, allow_overwrite=True):
            self.kv[k] = v

        def blocking_key_value_get(self, k, timeout_ms):
            if "/a/1" in k:
                if release.is_set():
                    mine = next(v for kk, v in self.kv.items()
                                if "/a/0" in kk)
                    mine = json.loads(mine)
                    return json.dumps({"h": mine["h"],
                                       "e": mine.get("e", [])})
                time.sleep(timeout_ms / 1000.0)
            raise TimeoutError("deadline exceeded")

        def key_value_delete(self, k):
            self.kv.pop(k, None)

    fake = FakeClient()
    orig_client = ctl_mod._client
    orig_pi = ctl_mod.jax.process_index
    ctl_mod._client = lambda: fake
    ctl_mod.jax.process_index = lambda: 0
    try:
        ctl = ctl_mod.Controller()
        tok = json.dumps({"s": [["t", "allreduce", "sum", "float32", [2],
                                 0, False, -1, 1.0, 1.0]],
                          "r": -1, "sp": None},
                         separators=(",", ":"), sort_keys=True)
        out = {}

        def round_thread():
            out["res"] = ctl.negotiate([tok], (0, 1))

        t = threading.Thread(target=round_thread, daemon=True)
        t.start()
        time.sleep(0.4)                 # round is now polling the peer
        assert t.is_alive()
        t0 = time.monotonic()
        ctl.set_joined(False)
        st = ctl.stats()
        assert time.monotonic() - t0 < 0.2, \
            "user-thread entry points blocked behind a negotiation round"
        assert st["rounds"] == 0        # round not finished yet
        release.set()
        t.join(timeout=10)
        assert not t.is_alive()
        assert out["res"].counts[tok] == 1
    finally:
        ctl_mod._client = orig_client
        ctl_mod.jax.process_index = orig_pi


def test_allgather_object_cross_process():
    """hvd.allgather_object returns every process's object, ordered by
    process index, on all processes (reference: allgather_object)."""
    results = run(helpers_runner.allgather_object_fn, np=2, env=_env(),
                  port=free_port())
    expected = [{"rank": 0, "payload": [0]}, {"rank": 1, "payload": [1, 1]}]
    for r in results:
        assert r["objs"] == expected


def test_uneven_allgather_cross_process():
    """Reference parity: hvd.allgather is Allgatherv — ranks may
    contribute different dim-0 sizes (controller.cc gathers tensor
    sizes).  Both processes receive the concatenation of every worker's
    true rows, and the async submit stays non-blocking."""
    results = run(helpers_runner.uneven_allgather_fn, np=2, env=_env(),
                  port=free_port())
    expected = [[0.0, 1.0], [2.0, 3.0],
                [100.0, 101.0], [102.0, 103.0], [104.0, 105.0]]
    expected2 = [[0.0], [1.0], [1.0]]
    for r in results:
        assert r["out"] == expected
        assert r["out2"] == expected2


def test_join_with_float64_collective():
    """x64-exact synthesis: a joined process zero-fills a float64 token
    with float64 (not a silently-downcast float32), so the two
    processes execute the same SPMD program."""
    results = run(helpers_runner.join_uneven_f64_fn, np=2, env=_env(),
                  port=free_port())
    by_rank = {r["rank"]: r for r in results}
    assert by_rank[0]["sums"][0] == [3.0, 3.0, 3.0]
    assert by_rank[1]["sums"] == [[3.0, 3.0, 3.0]]
    assert by_rank[0]["sums"][1] == [1.0, 1.0, 1.0]  # zero from joined
    assert by_rank[0]["last"] == 0


def test_four_process_controller():
    """Scale the cross-process protocol past np=2: global + overlapping
    subset groups, 4-way ragged allgather, and a 3-early-joiner join —
    all on one round-trip ordering (reference: test/parallel at -np 4)."""
    results = run(helpers_runner.four_process_fn, np=4, env=_env(),
                  port=free_port())
    assert len(results) == 4
    expected_ag = [0.0] + [1.0] * 2 + [2.0] * 3 + [3.0] * 4
    for r in results:
        assert r["sum"] == [10.0, 10.0]            # 1+2+3+4
        assert r["ag"] == expected_ag
        assert r["last"] == 0                      # rank 0 joined last
    by_rank = {r["rank"]: r for r in results}
    assert by_rank[0]["sub"] == [4.0, 4.0]         # 1+3
    assert by_rank[2]["sub"] == [4.0, 4.0]
    assert by_rank[1]["sub"] is None
    assert by_rank[0]["extra"] == 1.0              # zeros from 3 joined


def test_mixed_op_storm_cross_process():
    """30 mixed collectives (allreduce / RAGGED allgather / broadcast)
    in one seeded order across 2 processes: every cycle's dispatch must
    agree and every value must be exact; the steady-state fast path must
    engage at least once across repeated signatures."""
    results = run(helpers_runner.mixed_op_storm_fn, np=2, env=_env(),
                  port=free_port())
    for r in results:
        assert r["ok"] == 30
        assert r["rounds"] >= 30


def test_negotiation_kv_ops_per_round_bounded():
    """VERDICT r4 #3 + ISSUE 5: rounds are O(N) per process AND
    event-driven — in a 4-process job launched through the runner (which
    hosts the RPC KV), 10 steady-state rounds cost exactly 10
    key_value_sets plus 10 key_value_dir_watch long polls, ZERO polled
    dir-gets, ZERO leave-marker gets (markers ride the watch reply), and
    ZERO per-peer blocking gets.  The pre-watch transport paid dir-get
    polls bounded by the 250 ms tick; the original one paid (N-1) polled
    gets per round plus (N-1) leave-marker gets per tick."""
    results = run(helpers_runner.kv_ops_per_round_fn, np=4, env=_env(),
                  port=free_port())
    assert len(results) == 4
    for r in results:
        assert r["rounds"] == 10, r
        assert r["kv_sets"] == 10, r                 # ONE publish per round
        assert r["kv_blocking_gets"] == 0, r         # never per-peer gets
        assert r["watch_fallbacks"] == 0, r          # watch stayed up
        # steady state: ONE held watch per round, woken at last arrival
        # (min_entries), so the count is exactly the round count
        assert r["kv_dir_watches"] == 10, r
        assert r["kv_dir_gets"] == 0, r              # ZERO polled dir-gets
        assert r["kv_left_gets"] == 0, r             # folded into watch


def test_steady_state_watch_costs_one_set_one_watch():
    """ISSUE 5 transport-cost pin, runnable without a multi-process
    launch: a Controller over the REAL RpcKvClient + KvServer, with the
    peer simulated by direct store writes.  A steady-state fast round at
    "4 processes" costs exactly one key_value_set plus one
    key_value_dir_watch and ZERO polled dir-gets / leave-marker gets."""
    import hashlib
    import json as _json
    import threading
    import time

    from horovod_tpu.ops import controller as ctl_mod
    from horovod_tpu.runner.kv import KvServer, RpcKvClient

    srv = KvServer(secret=None)
    cli = RpcKvClient("127.0.0.1", srv.port, secret=None)
    orig_client, orig_pi = ctl_mod._client, ctl_mod.jax.process_index
    ctl_mod._client = lambda: cli
    ctl_mod.jax.process_index = lambda: 0
    try:
        ctl = ctl_mod.Controller()
        tok = _json.dumps(
            {"s": [["t", "allreduce", "sum", "float32", [2], 0, False,
                    -1, 1.0, 1.0]], "r": -1, "sp": None},
            separators=(",", ":"), sort_keys=True)
        procs = (0, 1, 2, 3)
        gk = "g" + hashlib.sha1(
            ",".join(map(str, procs)).encode()).hexdigest()[:12]
        h = hashlib.sha1(tok.encode()).hexdigest()

        def peers(seq, full):
            time.sleep(0.03)
            val = {"h": h, "e": [tok]} if full else {"h": h}
            for q in (1, 2, 3):
                srv.store.set(f"hvdctl/0/{gk}/{seq}/a/{q}",
                              _json.dumps(val, separators=(",", ":")))

        for seq in range(6):
            threading.Thread(target=peers, args=(seq, seq == 0),
                             daemon=True).start()
            res = ctl.negotiate([tok], procs)
            assert res.counts[tok] == 1
            assert res.fast == (seq > 0)      # hash-only from round 1 on
        st = ctl.stats()
        assert st["kv_sets"] == 6, st          # one publish per round
        assert st["kv_dir_watches"] == 6, st   # ONE watch per round
        assert st["kv_dir_gets"] == 0, st      # ZERO polled dir-gets
        assert st["kv_left_gets"] == 0, st     # markers ride the watch
        assert st["kv_blocking_gets"] == 0, st
        assert st["watch_fallbacks"] == 0, st
        assert st["fast_rounds"] == 5 and st["full_rounds"] == 1, st
    finally:
        ctl_mod._client = orig_client
        ctl_mod.jax.process_index = orig_pi
        srv.close()


def test_controller_keys_cleaned_at_shutdown():
    """VERDICT r4 #9: after leave() + cleanup_keys() on every process, no
    hvdctl/ keys for the incarnation survive on the coordination service
    (the last process out subtree-deletes the namespace)."""
    results = run(helpers_runner.controller_shutdown_clean_fn, np=2,
                  env=_env(), port=free_port())
    for r in results:
        assert r["pre"] >= 1          # rounds really published keys
        assert r["leftover"] == [], r


def test_profiler_trace_contains_framework_spans(tmp_path):
    """VERDICT r4 #5: one jax.profiler capture holds the framework spans
    (hvd.NEGOTIATE / hvd.cycle) AND the fused-dispatch annotation, so
    framework phases correlate with XLA ops in a single Perfetto view."""
    results = run(helpers_runner.profiler_merged_trace_fn, np=2,
                  env=_env({"TEST_PROF_DIR": str(tmp_path)}), port=free_port())
    for r in results:
        assert r["negotiate"], r
        assert r["cycle"], r
        assert r["dispatch"], r
