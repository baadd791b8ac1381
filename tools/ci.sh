#!/bin/bash
# Build/test matrix (reference: the superbuild's framework x feature CI
# matrix, SURVEY.md §2.1 "Build system" + §4 test strategy).
#
#   bash tools/ci.sh [--quick]
#
# Stages:
#   1. package: wheel + sdist build (no isolation - deps are baked in).
#      dist/ artifacts are BUILD OUTPUTS, rebuilt fresh here every run —
#      they are not committed to git (they went stale against planner
#      fixes once; see CHANGES.md).
#   2. wheel install smoke: install the wheel into a scratch --target dir
#      and run an eager-collectives smoke from OUTSIDE the repo (catches
#      wheels that build but don't ship runnable code)
#   3. sdist install smoke: same, building from source (skipped --quick)
#   4. native:  build the C++ core in place, run its parity tests
#   5. purepy:  the HOROVOD_TPU_NATIVE_CORE=0 fallback paths
#   6. noctl:   single-process semantics with the controller disabled
#   7. full:    the whole suite (skipped with --quick)
#   8. hvdlint: static collective-consistency, lock-order, guarded-by
#      race and SPMD rank-divergence dataflow analysis (HVD200–HVD205)
#      over the framework and examples, gated on the findings baseline
#      (docs/analysis.md)
#   9. chaos:   the elastic join path under pinned fault-injection seeds
#      must converge, and the leader-join regression stays pinned
#      (docs/env.md "Chaos engineering")
#  10. bench:   tools/bench_control.py --smoke — real multi-process
#      negotiation over the RPC KV; watch-transport invariants (one
#      set + one watch per round, zero polled dir-gets) stay pinned —
#      tools/bench_zero.py --smoke — CPU-mesh A/B of the ZeRO
#      sharded update (1/N state bytes, no full-gradient psum in the
#      sharded schedule, sharded == replicated weights) — and
#      tools/bench_compression.py --smoke — quantized-wire invariants
#      (>=3.5x DCN bytes at int8, no overflow, error-feedback parity
#      with bit-identical replicas) — and tools/bench_overlap.py
#      --smoke — overlapped-dispatch invariants (per-layer buckets
#      inside the backward scan, boundary/overlapped weights
#      bit-identical incl. sharded x int8) — and tools/bench_tail.py
#      --smoke — tail-tolerant-collective invariants (chaos-seeded
#      p99 bound, strict/bounded one-program bit-exactness,
#      convergence gate, byte conservation) — and tools/bench_fsdp.py
#      --smoke — mesh-axis-aware gradient-plane invariants (exact
#      model-shard-fraction per-chip bytes, data-hop wire bytes with
#      int8 on the 2-D mesh, one-program fire-gated A/B bit-identical
#      weights across plain/zero/int8/int8+zero, replicated parity)
#      — and tools/hvdtrace
#      --smoke — merged-trace critical-path attribution over the
#      recorded chaos-seeded 4-host fixture (the injected straggler
#      must be the verdict) — and tools/hvddoctor --smoke —
#      training-health verdict under a pinned collective.corrupt seed
#      (the evaluator must name the injected rank+bucket via
#      GET /health/job; the clean run must stay verdict-free) — and
#      tools/bench_serve.py --smoke — serving-plane invariants
#      (batched >= 3x sequential throughput at equal p50, chaos-seeded
#      straggler rotated out with post-rotation p99 bounded,
#      kill-worker-mid-lease re-forms with zero lost requests, zero
#      post-warmup recompiles across the shape buckets)
#  11. hvdsched: re-trace the builtin step entries to jaxprs on CPU and
#      diff their collective schedules against tests/schedules/
#      (HVD211 drift; incl. the sharded_distopt_step reduce_scatter →
#      all_gather plan and the tail_distopt_step rewritten DCN stage) +
#      the cross-mesh-size consistency check
#      (HVD210); any fusion-plan change is an explicit snapshot update
#      in review (docs/analysis.md "Schedule snapshots"); incl. the
#      EMPTY serve_forward_step entry (a serving forward must never
#      negotiate a gradient collective)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== 1/11 package: wheel + sdist =="
rm -rf dist/
python -m build --no-isolation --outdir dist/ . > /tmp/ci_build.log 2>&1 \
  || { tail -30 /tmp/ci_build.log; exit 1; }
ls -l dist/

echo "== 2/11 wheel install smoke (scratch target, run from /tmp) =="
WHEEL_TGT=$(mktemp -d)
trap 'rm -rf "$WHEEL_TGT"' EXIT
REPO_DIR="$(pwd)"

dist_smoke() {  # $1 = a wheel or sdist under dist/ (exactly one)
  if [ "$#" -ne 1 ]; then
    # the caller passes a glob: more than one match means stale
    # artifacts are lying around and we could smoke-test the wrong one
    echo "dist_smoke: expected exactly one artifact, got $#: $*" >&2
    exit 1
  fi
  if [ ! -f "$1" ]; then
    echo "dist_smoke: no such artifact: $1" >&2
    exit 1
  fi
  rm -rf "$WHEEL_TGT"/*
  pip install --no-deps --no-build-isolation --quiet \
    --target "$WHEEL_TGT" "$1"
  (cd /tmp && JAX_PLATFORMS=cpu PYTHONPATH="$WHEEL_TGT" \
    REPO_DIR="$REPO_DIR" python - <<'PYEOF'
import os, sys
repo = os.environ["REPO_DIR"]
assert not any(p == repo for p in sys.path)
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ["HOROVOD_CYCLE_TIME"] = "0.2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
assert "horovod_tpu" in hvd.__file__ and not hvd.__file__.startswith(repo)
hvd.init()
assert hvd.size() == 8
x = hvd.worker_values(lambda r: np.full((3,), float(r)))
np.testing.assert_allclose(
    np.asarray(hvd.allreduce(x, op=hvd.Sum)), np.full((3,), 28.0))

# hvdmetrics smoke: scrape /metrics + /healthz from a live server in the
# installed process; the core families must be present and the body must
# parse as Prometheus text format (docs/metrics.md)
import json
from horovod_tpu.metrics import aggregate
from horovod_tpu.runner.rpc import JsonRpcServer
srv = JsonRpcServer({}, secret=None)
health = json.loads(aggregate.scrape("127.0.0.1", srv.port,
                                     route="healthz"))
assert health["status"] == "ok", health
fams = aggregate.parse_prometheus(aggregate.scrape("127.0.0.1", srv.port))
for fam in ("hvd_engine_cycles_total", "hvd_cycle_duration_seconds",
            "hvd_negotiation_duration_seconds",
            "hvd_rpc_request_duration_seconds",
            "hvd_response_cache_total", "hvd_wire_bytes_total"):
    assert fam in fams, f"missing metric family {fam}"
# wire accounting (quantized collectives): the uncompressed allreduce
# above must have recorded its payload under format="float32"
wire = [(lbl, v) for _, lbl, v in fams["hvd_wire_bytes_total"]["samples"]
        if lbl.get("format") == "float32"]
assert wire and wire[0][1] >= 12, fams["hvd_wire_bytes_total"]["samples"]
assert fams["hvd_cycle_duration_seconds"]["type"] == "histogram"
cycles = [v for n, _, v in fams["hvd_engine_cycles_total"]["samples"]]
assert cycles and cycles[0] >= 1, cycles

# event-driven control plane smoke (ISSUE 5): one negotiation round over
# the installed RpcKvClient/KvServer must ride the long-poll watch and
# the keep-alive pool, and both must be visible on /metrics
import hashlib, threading, time
from horovod_tpu.ops import controller as ctl_mod
from horovod_tpu.runner.kv import KvServer, RpcKvClient
kv_srv = KvServer(secret=None)
kv_cli = RpcKvClient("127.0.0.1", kv_srv.port, secret=None)
orig_client, orig_pi = ctl_mod._client, ctl_mod.jax.process_index
ctl_mod._client = lambda: kv_cli
ctl_mod.jax.process_index = lambda: 0
try:
    ctl = ctl_mod.Controller(namespace="cismoke")
    tok = json.dumps(
        {"s": [["t", "allreduce", "sum", "float32", [2], 0, False, -1,
                1.0, 1.0]], "r": -1, "sp": None},
        separators=(",", ":"), sort_keys=True)
    gk = "g" + hashlib.sha1(b"0,1").hexdigest()[:12]
    h = hashlib.sha1(tok.encode()).hexdigest()
    for seq in range(2):
        threading.Timer(0.05, kv_srv.store.set,
                        (f"hvdctl/cismoke/{gk}/{seq}/a/1",
                         json.dumps({"h": h, "e": [tok]},
                                    separators=(",", ":")))).start()
        res = ctl.negotiate([tok], (0, 1))
        assert res.counts[tok] == 1, res
    st = ctl.stats()
    assert st["kv_dir_watches"] >= 2 and st["kv_dir_gets"] == 0, st
finally:
    ctl_mod._client = orig_client
    ctl_mod.jax.process_index = orig_pi
    kv_srv.close()
# overlapped-dispatch accounting (ROADMAP item 3): arm a toy grad tap
# and assert the trace-time bucket counter rides /metrics
import jax.numpy as jnp
import optax
from horovod_tpu.optim import overlap as ovl
from horovod_tpu.optim.distributed import DistributedOptimizer
otx = DistributedOptimizer(optax.sgd(1e-2), axis_name="smk",
                           threshold_bytes=1024, overlap=True)
def _ov_step(g):
    with ovl.overlapped_backprop(otx):
        _, gr = jax.value_and_grad(
            lambda p: (ovl.grad_tap(p)["a"] ** 2).sum())({"a": g})
    return gr
jax.make_jaxpr(_ov_step, axis_env=[("smk", 2)])(jnp.zeros((8,)))

# tail-tolerant collectives (ISSUE 11): one chaos-seeded bounded DCN
# round through the eager deadline gate — the straggler misses the
# deadline, is excluded from the mask, and both the round counter and
# its straggler score must land on /metrics
import horovod_tpu.chaos as hvchaos
from horovod_tpu.ops import collectives as hvcoll
from horovod_tpu.stall import StallInspector
insp = StallInspector(check_time=1e9, use_native=False)
hvchaos.install(hvchaos.FaultSchedule.parse(
    "collective.dcn group=1 nth=1 action=delay:0.3", seed=5))
try:
    present = hvcoll.tail_round("ci_smoke", "bounded", 2, 0.05,
                                stall=insp)
finally:
    hvchaos.uninstall()
assert list(present) == [1.0, 0.0], present
assert insp.straggler_scores()[1] > 0, insp.straggler_scores()

# training-health verdict plane (ISSUE 13): the fused dispatches above
# fed the eager numerics taps; the local GET /health route serves this
# worker's slice, and a driver-shaped GET /health/job merges >=2
# workers into one job verdict (healthy here — the corrupt-seeded
# unhealthy path is stage 10's hvddoctor smoke)
import horovod_tpu.health as hhealth
from horovod_tpu.health.evaluate import HealthEvaluator
assert hhealth.ACTIVE
hlocal = json.loads(aggregate.scrape("127.0.0.1", srv.port,
                                     route="health"))
assert hlocal["enabled"] and hlocal["healthy"], hlocal
assert hlocal["checks"]["stats_ingested"] >= 1, hlocal["checks"]
hevB = HealthEvaluator()
hevB.process, hevB.host = 1, "cismoke-hostB"
hsrvA = JsonRpcServer({"health_pull": hhealth.pull_handler}, secret=None)
hsrvB = JsonRpcServer({"health_pull": lambda p: hevB.snapshot()},
                      secret=None)
h_endpoints = {"0": ("127.0.0.1", hsrvA.port),
               "1": ("127.0.0.1", hsrvB.port)}
def _health_job_route():
    return (200, "application/json",
            json.dumps(hhealth.scrape_job_health(h_endpoints,
                                                 secret=None)))
hjsrv = JsonRpcServer({}, secret=None,
                      get_routes={"health/job": _health_job_route})
hjob = json.loads(aggregate.scrape("127.0.0.1", hjsrv.port,
                                   route="health/job"))
assert hjob["verdict"] == "healthy", hjob
assert hjob["scraped"] >= 2, hjob
assert not hjob["verdicts"], hjob["verdicts"]
for _s in (hsrvA, hsrvB, hjsrv):
    _s.close()

# job-wide distributed trace (ISSUE 12): the negotiation rounds above
# recorded spans into the installed tracer; serve them plus a second
# simulated host's buffer and scrape GET /trace/job (the driver-shaped
# merged route) — the result must be valid Chrome-trace JSON with one
# pid per host (>=2 distinct) and >=1 negotiation-round span per worker
import horovod_tpu.tracing as htrace
assert htrace.ACTIVE
neg_local = [s for s in htrace.buffer().snapshot()["spans"]
             if s["cat"] == "negotiate" and s["round"] >= 0]
assert len(neg_local) >= 2, neg_local
trbufB = htrace.SpanBuffer(host="cismoke-hostB", process=1)
trbufB.set_context(round=0, epoch=0)
_tB = trbufB.now()
trbufB.add("negotiate", "round0", _tB - 0.01, _tB, kind="full")
wsrvA = JsonRpcServer({"trace_pull": htrace.pull_handler}, secret=None)
wsrvB = JsonRpcServer({"trace_pull": trbufB.pull_handler()}, secret=None)
tr_endpoints = {"0": ("127.0.0.1", wsrvA.port),
                "1": ("127.0.0.1", wsrvB.port)}
def _trace_job_route():
    tr = htrace.merge.scrape_job_trace(tr_endpoints, probes=2,
                                       secret=None)
    return (200, "application/json", json.dumps(tr))
tsrv = JsonRpcServer({}, secret=None,
                     get_routes={"trace/job": _trace_job_route})
trace = json.loads(aggregate.scrape("127.0.0.1", tsrv.port,
                                    route="trace/job"))
host_pids = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
assert len(host_pids) >= 2, host_pids
tr_rounds = {}
for e in trace["traceEvents"]:
    if (e.get("ph") == "X" and e.get("cat") == "negotiate"
            and e["args"].get("round", -1) >= 0):
        tr_rounds[e["args"]["process"]] = \
            tr_rounds.get(e["args"]["process"], 0) + 1
assert tr_rounds.get(0, 0) >= 1 and tr_rounds.get(1, 0) >= 1, tr_rounds
from horovod_tpu.tracing import critical as htrace_critical
htrace_critical.analyze(trace)   # analyzable, not just parseable
for _s in (wsrvA, wsrvB, tsrv):
    _s.close()

# serving plane (ISSUE 15): an in-process plane + worker serve a small
# request burst end to end; hvd_serve_requests_total and a computable
# p99 from the request-latency histogram must ride a /metrics/job-shaped
# scrape-and-merge, and engine.stats() must grow a "serving" section
from horovod_tpu.serving.models import toy_echo_forward
from horovod_tpu.serving.plane import ServingPlane
from horovod_tpu.serving.worker import ServingWorker
splane = ServingPlane(tick_ms=2.0, max_batch=8, seq_buckets="8,16",
                      deadline_ms=0)
ssrv = JsonRpcServer(splane.rpc_handlers(), secret=None)
sworker = ServingWorker("127.0.0.1", ssrv.port,
                        toy_echo_forward(splane.buckets, burn_dim=32,
                                         burn_iters=1),
                        worker_id="0", wait_s=2.0, secret=None)
sworker.start()
# continuous telemetry plane (ISSUE 18): two explicit on-worker rings
# window the serve burst below (baseline at construction, so each
# window holds exactly the burst's deltas), then a driver-shaped
# GET /timeseries/job merges >=2 workers with a computable windowed
# serve p99 (docs/metrics.md "Time series")
from horovod_tpu.metrics import timeseries as hts
ts_ring_a = hts.TimeSeriesRing(window=8, every_s=60.0)
ts_ring_b = hts.TimeSeriesRing(window=8, every_s=60.0)
from horovod_tpu.runner.rpc import json_request as _jr
sids = []
for i in range(12):
    toks = [i, i + 1, i + 2]
    _jr("127.0.0.1", ssrv.port, "serve_submit",
        {"id": f"smoke{i}", "tokens": toks}, secret=None)
    sids.append((f"smoke{i}", toks))
for rid, toks in sids:
    res = _jr("127.0.0.1", ssrv.port, "serve_result",
              {"id": rid, "wait_s": 20.0}, secret=None)
    assert res.get("done") and res["output"][:3] == [t * 2 + 1
                                                    for t in toks], res
from horovod_tpu.runtime import _state as _hvd_state
est = _hvd_state().engine.stats()
assert est.get("serving", {}).get("plane", {})["completed"] == 12, \
    est.get("serving")
# job-shaped merge over this worker's /metrics: the serve families
# must merge and the latency histogram must yield a p99
merged = aggregate.parse_prometheus(aggregate.scrape_and_merge(
    {"0": ("127.0.0.1", srv.port)}))
sreq = sum(v for _, lbl, v
           in merged["hvd_serve_requests_total"]["samples"]
           if lbl.get("outcome") == "completed")
assert sreq >= 12, merged["hvd_serve_requests_total"]["samples"]
slat = [(lbl.get("le"), v) for nm, lbl, v
        in merged["hvd_serve_request_latency_seconds"]["samples"]
        if nm.endswith("_bucket")]
scount = sum(v for nm, _, v
             in merged["hvd_serve_request_latency_seconds"]["samples"]
             if nm.endswith("_count"))
assert scount >= 12, scount
sp99 = next(float(le) for le, cum in slat
            if le != "+Inf" and cum >= 0.99 * scount)
assert sp99 < 128.0, sp99   # inside the histogram's finite edges
ts_ring_a.sample()
ts_ring_b.sample()
def _ts_route(ring):
    def route():
        return (200, "application/json",
                json.dumps({"enabled": True, "windows": ring.windows()}))
    return route
tssrvA = JsonRpcServer({}, secret=None,
                       get_routes={"timeseries": _ts_route(ts_ring_a)})
tssrvB = JsonRpcServer({}, secret=None,
                       get_routes={"timeseries": _ts_route(ts_ring_b)})
tsjob = hts.scrape_job_timeseries(
    {"0": ("127.0.0.1", tssrvA.port), "1": ("127.0.0.1", tssrvB.port)})
assert tsjob["scraped"] >= 2, tsjob
assert not tsjob["unreachable"], tsjob["unreachable"]
ts_hist = tsjob["merged"]["histograms"][
    "hvd_serve_request_latency_seconds"]
# both rings windowed the same 12-request burst: 24 merged deltas and
# a finite windowed p99 (NaN would mean the window missed the burst)
assert ts_hist["count"] >= 24, ts_hist
assert ts_hist["p99"] == ts_hist["p99"], ts_hist
for _s in (tssrvA, tssrvB):
    _s.close()
splane.close()
sworker.stop(); sworker.join(10)
ssrv.close()

# checkpointless recovery (ISSUE 17): one push/rebuild pair over real
# loopback RPC in the installed process; the rebuilt frame must be
# bit-identical and the hvd_recovery_* families must carry samples on
# the same /metrics scrape every other plane rides
from horovod_tpu.elastic import recovery as hvrec
rec_a = hvrec.RecoveryAgent(rank=0, size=2, mode="neighbor", every=1,
                            pull_deadline_s=5.0, register=False)
rec_b = hvrec.RecoveryAgent(rank=1, size=2, mode="neighbor", every=1,
                            pull_deadline_s=5.0, register=False)
rsrvA = JsonRpcServer(rec_a.worker_handlers(), secret=None)
rsrvB = JsonRpcServer(rec_b.worker_handlers(), secret=None)
rpeers = {0: ("127.0.0.1", rsrvA.port), 1: ("127.0.0.1", rsrvB.port)}
rec_a.update_plan(0, rpeers)
rec_b.update_plan(0, rpeers)
rstate = np.arange(512, dtype=np.float32)
assert rec_b.note_boundary(0, {"tiles": rstate})
# worker 1 'dies'; a fresh agent (empty store) rebuilds from worker 0
rec_b2 = hvrec.RecoveryAgent(rank=1, size=2, mode="neighbor", every=1,
                             pull_deadline_s=5.0, register=False)
rec_b2.update_plan(0, {0: ("127.0.0.1", rsrvA.port)}, size=2)
rgot = rec_b2.rebuild(min_epoch=0)
assert rgot["tiles"].tobytes() == rstate.tobytes(), "rebuild not bit-exact"
rsrvA.close(); rsrvB.close()

fams = aggregate.parse_prometheus(aggregate.scrape("127.0.0.1", srv.port))
def _family_count(fam, **want):
    return sum(v for _, lbl, v in fams[fam]["samples"]
               if all(lbl.get(k) == w for k, w in want.items()))
overlap_buckets = _family_count("hvd_overlap_buckets_dispatched_total",
                                phase="bwd")
assert overlap_buckets >= 1, \
    fams["hvd_overlap_buckets_dispatched_total"]["samples"]
watch_rounds = _family_count("hvd_negotiation_rounds_total", kind="watch")
assert watch_rounds >= 2, fams["hvd_negotiation_rounds_total"]["samples"]
reuse_hits = _family_count("hvd_rpc_conn_reuse_total", result="hit")
assert reuse_hits >= 1, fams["hvd_rpc_conn_reuse_total"]["samples"]
tail_rounds = _family_count("hvd_tail_rounds_total", policy="bounded")
assert tail_rounds >= 1, fams["hvd_tail_rounds_total"]["samples"]
straggler = _family_count("hvd_straggler_score", process="1")
assert straggler > 0, fams["hvd_straggler_score"]["samples"]
rec_rebuilds = _family_count("hvd_recovery_rebuilds_total",
                             source="neighbor")
assert rec_rebuilds >= 1, fams["hvd_recovery_rebuilds_total"]["samples"]
rec_time = sum(v for nm, _, v
               in fams["hvd_recovery_time_seconds"]["samples"]
               if nm.endswith("_count"))
assert rec_time >= 1, fams["hvd_recovery_time_seconds"]["samples"]
assert _family_count("hvd_recovery_snapshots_total",
                     mode="neighbor") >= 1
# eager numerics taps fed the health gauge family on this process
assert "hvd_health_grad_norm" in fams, sorted(fams)
srv.close()

hvd.shutdown()
print(f"dist smoke OK (incl. /metrics + /healthz + /trace/job + "
      f"/health/job scrape, {int(watch_rounds)} watch rounds, "
      f"{int(reuse_hits)} keep-alive hits, {int(overlap_buckets)} "
      f"overlap buckets, {len(host_pids)} trace host pids, job health "
      f"{hjob['verdict']}, {int(sreq)} served requests @ p99<="
      f"{sp99:g}s, {int(rec_rebuilds)} fleet rebuild(s)), imported from",
      os.path.dirname(hvd.__file__))
PYEOF
  )
}

dist_smoke dist/*.whl
if [ "${1:-}" != "--quick" ]; then
  echo "== 3/11 sdist install smoke (builds from source) =="
  dist_smoke dist/*.tar.gz
fi

echo "== 4/11 native core build + parity tests =="
python setup.py build_ext --inplace > /tmp/ci_native.log 2>&1 \
  || { tail -30 /tmp/ci_native.log; exit 1; }
python -m pytest tests/test_native_core.py -q

echo "== 5/11 pure-python fallback (native core disabled) =="
HOROVOD_TPU_NATIVE_CORE=0 python -m pytest \
  tests/test_basics.py tests/test_fusion.py -q

echo "== 6/11 controller disabled (single-process semantics) =="
HOROVOD_TPU_CONTROLLER=0 python -m pytest tests/test_basics.py -q

if [ "${1:-}" != "--quick" ]; then
  echo "== 7/11 full suite =="
  python -m pytest tests/ -q
fi

echo "== 8/11 hvdlint static analysis =="
# all six engines (user rules, lock-order, guarded-by race detector,
# HVD200–HVD205 SPMD divergence dataflow, HVD400–HVD407 concurrency
# lifecycle, HVD300–HVD307 cross-layer contracts); --baseline: fail
# only on NEW findings vs the checked-in ratchet (EMPTY by policy, and
# refused outright if its analyzer_version is stale — docs/analysis.md
# "Baseline workflow").  One parse per file feeds every engine (the
# repo-wide contracts pass rides the same AST cache); the wall-time
# assert pins the whole run under 25 s (2x the ~12.3 s six-engine
# measurement on the CI runner, PR-16 convention) — so engine 6 can
# never quietly double the lint stage.
t_lint0=$(date +%s%N)
python -m horovod_tpu.analysis \
  --baseline tools/hvdlint_baseline.json horovod_tpu/ examples/
t_lint_ms=$(( ($(date +%s%N) - t_lint0) / 1000000 ))
echo "hvdlint wall: ${t_lint_ms} ms"
if [ "${t_lint_ms}" -gt 25000 ]; then
  echo "FAIL: hvdlint took ${t_lint_ms} ms (> 25000 ms budget)"; exit 1
fi
# SARIF export must stay wired for CI diff annotation: smoke-run it on
# the teaching fixture (findings guaranteed, exit 1 expected) and
# validate the log parses as SARIF 2.1.0 with results present.
python -m horovod_tpu.analysis --engine lifecycle --include-skipped \
  --sarif /tmp/ci_hvdlint.sarif examples/antipatterns.py >/dev/null || true
python - <<'PYEOF'
import json
log = json.load(open("/tmp/ci_hvdlint.sarif"))
assert log["version"] == "2.1.0", log.get("version")
results = log["runs"][0]["results"]
assert results, "SARIF smoke produced no results"
rules = {r["id"] for r in log["runs"][0]["tool"]["driver"]["rules"]}
assert {f"HVD{n}" for n in range(400, 408)} <= rules
print(f"hvdlint SARIF: {len(results)} result(s), schema ok")
PYEOF

echo "== 9/11 chaos smoke: elastic join under fixed fault seeds =="
python -m pytest tests/test_chaos.py -q \
  -k "converges_under_fault_seed or leader_join"

echo "== 10/11 control-plane bench smoke (watch transport invariants) =="
# fast correctness run of tools/bench_control.py: real multi-process
# negotiation over the RPC KV; asserts ZERO polled dir-gets and one
# set + one watch per steady-state round (docs/performance.md)
python tools/bench_control.py --smoke > /tmp/ci_bench_control.log 2>&1 \
  || { tail -30 /tmp/ci_bench_control.log; exit 1; }
tail -1 /tmp/ci_bench_control.log
# ZeRO sharded-update A/B: per-worker optimizer state must be 1/N-sized,
# the sharded schedule must contain NO full-gradient psum, and sharded
# and replicated steps must land on the same weights (docs/performance.md
# "Sharded weight update")
python tools/bench_zero.py --smoke > /tmp/ci_bench_zero.log 2>&1 \
  || { tail -30 /tmp/ci_bench_zero.log; exit 1; }
tail -1 /tmp/ci_bench_zero.log
# quantized collectives: the DCN-stage wire-bytes ratio must hold
# (>=3.5x for fp32 gradients at int8), a quantized SUM far outside int8
# range must not overflow, and error-feedback training must keep every
# replica bit-identical with final loss at parity (docs/performance.md
# "Quantized collectives")
python tools/bench_compression.py --smoke > /tmp/ci_bench_comp.log 2>&1 \
  || { tail -30 /tmp/ci_bench_comp.log; exit 1; }
tail -1 /tmp/ci_bench_comp.log
# overlapped dispatch: every per-layer fusion bucket must sit INSIDE
# the backward scan of the armed step (boundary step: none), the
# updates all-gather stays at the step boundary, and the one-program
# fire-gated A/B must land on bit-identical weights for plain /
# sharded / int8 / int8+sharded (docs/performance.md "Overlapped
# dispatch")
python tools/bench_overlap.py --smoke > /tmp/ci_bench_overlap.log 2>&1 \
  || { tail -30 /tmp/ci_bench_overlap.log; exit 1; }
tail -1 /tmp/ci_bench_overlap.log
# tail-tolerant collectives: under the fixed collective.dcn 800ms delay
# seed, bounded-policy round p99 must stay <= deadline + eps while
# strict p99 tracks the injected delay; strict/bounded one-program A/B
# bit-identical across plain/sharded/int8 with no deadline firing; the
# bounded/stale toy-training rel-loss delta inside the documented gate;
# ring bytes conserved up to the pmin agreement round (strict
# accounting — unmodeled prims fail loudly).  (docs/performance.md
# "Tail-tolerant collectives")
python tools/bench_tail.py --smoke > /tmp/ci_bench_tail.log 2>&1 \
  || { tail -30 /tmp/ci_bench_tail.log; exit 1; }
tail -1 /tmp/ci_bench_tail.log
# mesh-axis-aware gradient plane: on the 2x2 (data x model) CPU mesh,
# per-chip param+opt-state bytes must sit at the EXACT model-shard
# fraction (tree_nbytes vs the planner's tile layout), the data-hop
# wire bytes must shrink with shard operands and >=3.5x further under
# int8 (strict ring accounting), the one-program fire-gated A/B must
# land on bit-identical weights across plain/zero/int8/int8+zero, and
# the spec-aware trajectory must match the flat replicated reference
# (docs/performance.md "Mesh-axis-aware sharding")
python tools/bench_fsdp.py --smoke > /tmp/ci_bench_fsdp.log 2>&1 \
  || { tail -30 /tmp/ci_bench_fsdp.log; exit 1; }
tail -1 /tmp/ci_bench_fsdp.log
# merged-trace critical path: replay the recorded chaos-seeded 4-host
# fixture (collective.dcn group=1 every=3 delay:0.8) through
# tools/hvdtrace — the injected straggler host must come out as the top
# critical-path contributor (docs/observability.md "Distributed trace")
bash tools/hvdtrace --smoke > /tmp/ci_hvdtrace.log 2>&1 \
  || { tail -30 /tmp/ci_hvdtrace.log; exit 1; }
tail -1 /tmp/ci_hvdtrace.log
# training-health doctor: under the pinned collective.corrupt seed on a
# 4-way CPU mesh, the evaluator must name the injected (rank, bucket),
# the verdict must surface through a driver-shaped GET /health/job
# scrape, and the clean run must stay verdict-free
# (docs/observability.md "Training health")
bash tools/hvddoctor --smoke > /tmp/ci_hvddoctor.log 2>&1 \
  || { tail -30 /tmp/ci_hvddoctor.log; exit 1; }
tail -1 /tmp/ci_hvddoctor.log
# SLO watchdog + hvdtop: under the pinned serve.batch delay seed the
# watchdog must name the injected serve_p99_s breach within one window
# over a real loopback serving plane and surface it through a
# driver-shaped GET /timeseries/job; the clean run must stay
# breach-free and the seed must be proven non-inert
# (docs/metrics.md "Time series")
bash tools/hvdtop --smoke > /tmp/ci_hvdtop.log 2>&1 \
  || { tail -30 /tmp/ci_hvdtop.log; exit 1; }
tail -1 /tmp/ci_hvdtop.log
# serving plane: real worker processes against a real ServingPlane on
# loopback — all four tail-latency gates must hold every run (batched
# >= 3x sequential at equal p50, chaos straggler rotated with p99
# bounded, SIGKILL-mid-lease loses zero requests, zero post-warmup
# recompiles), plus the paged-KV phase (allocator bytes == tree_nbytes
# exactly, per-row blocks beat bucket-max, prefix reuse cuts blocks,
# paged == dense outputs) and the model-parallel phase (per-chip param
# bytes == the exact 1/mp fraction on the 2x2 CPU mesh).
# (docs/serving.md)
python tools/bench_serve.py --smoke --paged --mp \
  > /tmp/ci_bench_serve.log 2>&1 \
  || { tail -30 /tmp/ci_bench_serve.log; exit 1; }
tail -1 /tmp/ci_bench_serve.log
# checkpointless recovery: a lost worker's ZeRO frame rebuilt from its
# surviving replica must be bit-identical AND faster than the pinned
# blob-store re-read model, steady-state redundancy bytes must stay
# under the gradient-wire fraction gate, and the pinned recovery.push
# chaos seed must prove itself live (injections + requeue counters on a
# driver-shaped GET /metrics/job).  (docs/elastic.md "Checkpointless
# recovery")
python tools/bench_recovery.py --smoke > /tmp/ci_bench_recovery.log 2>&1 \
  || { tail -30 /tmp/ci_bench_recovery.log; exit 1; }
tail -1 /tmp/ci_bench_recovery.log

echo "== 11/11 hvdsched: collective-schedule snapshots + consistency =="
# re-trace every builtin step entry to a jaxpr on CPU, diff against the
# committed tests/schedules/*.json (HVD211 — any fusion-plan change is
# an explicit `tools/hvdsched --update` in review) and require identical
# canonical schedules across mesh sizes (HVD210); incl. the
# overlapped_distopt_step entry whose per-layer collectives must sit
# inside the backward-scan sub-jaxpr, the health_distopt_step entry
# whose ONLY delta vs distopt_step is the divergence sentinel's
# checksum all_gather under its cadence cond, and the fsdp_distopt_step
# entry whose model-sharded buckets reduce-scatter shard-sized operands
# over the data axis alone (HVD210 sweeps the data axis: mesh shapes
# 2x2 and 4x2), and the serve_mp_forward_step entry whose schedule must
# be ONLY the spec all_gather hops over the serving model axis (the
# serve_forward_step empty-schedule pin, generalized).  The explicit
# entry-count assertion pins snapshot coverage: a deleted
# tests/schedules/*.json would otherwise let --check pass vacuously on
# the entries that remain.
n_sched=$(ls tests/schedules/*.json | wc -l)
if [ "${n_sched}" -ne 11 ]; then
  echo "FAIL: expected 11 schedule snapshots, found ${n_sched}"; exit 1
fi
sched_out=$(bash tools/hvdsched --check)
echo "${sched_out}"
case "${sched_out}" in
  *"11 entries clean"*) ;;
  *) echo "FAIL: hvdsched --check did not trace all 11 pinned entries"
     exit 1 ;;
esac
bash tools/hvdsched --check --consistency

echo "CI matrix: all stages green"
