"""hvdmetrics: registry, exposition, aggregation, flight recorder.

Covers the ISSUE 3 acceptance surface: typed metric families with fixed
log2 bucket edges (bucket-mergeable across workers), Prometheus text
exposition + /healthz GET routes on JsonRpcServer, driver-side
aggregation (histograms summed bucket-wise, gauges per-worker
min/max/sum), the chaos→metrics bridge (injections counted per rule),
stall-inspector bookkeeping unification, and the crash flight recorder
(StallError / SIGUSR1 dumps, FAILURE-report attachment).  The 2-process
integration scrapes /metrics on both workers and merges them.
"""

import json
import os
import signal
import time
import urllib.request

import pytest

from _helpers import free_port

import horovod_tpu.metrics as metrics
from horovod_tpu.metrics import aggregate
from horovod_tpu.metrics.flight import FlightRecorder
from horovod_tpu.metrics.registry import (MetricRegistry, MAX_SERIES,
                                          log2_edges)


# --- registry ----------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricRegistry()
    c = reg.counter("t_total", "help text", labels=("method",))
    c.inc(method="a")
    c.inc(2, method="a")
    c.inc(method="b")
    assert c.value(method="a") == 3
    assert c.value(method="b") == 1
    assert c.value(method="nope") == 0
    with pytest.raises(ValueError):
        c.inc(-1, method="a")
    g = reg.gauge("t_gauge")
    g.set(7.5)
    g.inc(0.5)
    assert g.value() == 8.0


def test_registry_redeclare_is_idempotent_but_typed():
    reg = MetricRegistry()
    c1 = reg.counter("x_total", labels=("a",))
    c2 = reg.counter("x_total", labels=("a",))
    assert c1 is c2
    with pytest.raises(ValueError, match="re-declared"):
        reg.gauge("x_total", labels=("a",))
    with pytest.raises(ValueError, match="re-declared"):
        reg.counter("x_total", labels=("b",))
    # histogram bucket edges are part of the family identity too
    h1 = reg.histogram("x_seconds", lo=-3, hi=3)
    assert reg.histogram("x_seconds", lo=-3, hi=3) is h1
    with pytest.raises(ValueError, match="edges"):
        reg.histogram("x_seconds", lo=-4, hi=4)


def test_histogram_log2_buckets():
    reg = MetricRegistry()
    h = reg.histogram("lat_seconds", lo=-3, hi=3)
    assert h.edges == (0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0)
    h.observe(0.1)     # first bucket (<= 0.125)
    h.observe(0.125)   # boundary lands in its own bucket (le= inclusive)
    h.observe(3.0)     # <= 4.0
    h.observe(100.0)   # +Inf overflow
    child = h.child()
    assert child.counts[0] == 2
    assert child.counts[5] == 1
    assert child.counts[-1] == 1
    assert child.count == 4
    assert child.sum == pytest.approx(103.225)
    with pytest.raises(ValueError):
        log2_edges(3, 3)


def test_label_series_bounded():
    reg = MetricRegistry()
    c = reg.counter("b_total", labels=("k",))
    for i in range(MAX_SERIES + 10):
        c.inc(k=f"v{i}")
    series = c.series()
    # everything past the bound collapses into one overflow series
    assert len(series) == MAX_SERIES + 1
    assert c.value(k="other") == 10


# --- Prometheus exposition ---------------------------------------------------

def _two_worker_registries():
    regs = []
    for vals in ([0.1, 0.3, 5.0], [0.2, 64.0]):
        reg = MetricRegistry()
        c = reg.counter("w_reqs_total", "reqs", labels=("method",))
        c.inc(3, method="run")
        h = reg.histogram("w_lat_seconds", "latency", lo=-4, hi=8)
        for v in vals:
            h.observe(v)
        g = reg.gauge("w_queue_depth")
        g.set(10 * (len(regs) + 1))
        regs.append(reg)
    return regs


def test_render_parse_roundtrip():
    reg = _two_worker_registries()[0]
    text = reg.render_prometheus()
    assert "# TYPE w_lat_seconds histogram" in text
    assert 'w_lat_seconds_bucket{le="+Inf"} 3' in text
    fams = aggregate.parse_prometheus(text)
    assert fams["w_reqs_total"]["type"] == "counter"
    assert fams["w_lat_seconds"]["type"] == "histogram"
    buckets = [(lbl.get("le"), v) for n, lbl, v
               in fams["w_lat_seconds"]["samples"]
               if n.endswith("_bucket")]
    # cumulative and ending at the total count
    assert buckets[-1] == ("+Inf", 3.0)
    values = [v for _, v in buckets]
    assert values == sorted(values)
    with pytest.raises(ValueError, match="malformed"):
        aggregate.parse_prometheus("not a metric line at all } {")


def test_merge_histograms_bucketwise_and_gauges_minmax():
    r0, r1 = _two_worker_registries()
    per_worker = {
        "0": aggregate.parse_prometheus(r0.render_prometheus()),
        "1": aggregate.parse_prometheus(r1.render_prometheus()),
    }
    merged = aggregate.merge(per_worker)
    # counters sum across workers per label set
    creqs = {tuple(sorted(lbl.items())): v for _, lbl, v
             in merged["w_reqs_total"]["samples"]}
    assert creqs[(("method", "run"),)] == 6.0
    # histograms sum bucket-wise: total count = 3 + 2
    hsamples = merged["w_lat_seconds"]["samples"]
    count = [v for n, _, v in hsamples if n == "w_lat_seconds_count"]
    assert count == [5.0]
    inf = [v for n, lbl, v in hsamples
           if n == "w_lat_seconds_bucket" and lbl.get("le") == "+Inf"]
    assert inf == [5.0]
    # bucket series stay cumulative after the merge
    bucketvals = [v for n, _, v in hsamples if n == "w_lat_seconds_bucket"]
    assert bucketvals == sorted(bucketvals)
    # gauges: per-worker spread, min/max attributed to the owning worker
    gs = {(lbl.get("agg"), lbl.get("worker")): v for _, lbl, v
          in merged["w_queue_depth"]["samples"]}
    assert gs[("min", "0")] == 10.0
    assert gs[("max", "1")] == 20.0
    assert gs[("sum", None)] == 30.0
    # the merged view renders back to valid exposition text
    assert aggregate.parse_prometheus(aggregate.render(merged))


def test_merge_render_escapes_label_values():
    """Label values with quotes/backslashes (e.g. HVD_CHAOS rule text)
    must survive the parse → merge → render round trip."""
    reg = MetricRegistry()
    # includes literal-backslash-before-'n' (the sequential-replace
    # unescape corruption case) and quotes
    for i, rule in enumerate(['say "hi" \\ twice', "C:\\network\\share"]):
        reg.counter(f"esc{i}_total", labels=("rule",)).inc(rule=rule)
        text = reg.render_prometheus()
        per_worker = {"0": aggregate.parse_prometheus(text)}
        out = aggregate.render(aggregate.merge(per_worker))
        reparsed = aggregate.parse_prometheus(out)
        samples = [s for s in reparsed[f"esc{i}_total"]["samples"]]
        (name, labels, value), = samples
        assert labels["rule"] == rule and value == 1.0


def test_scrape_and_merge_unreachable_worker_gauge_attribution():
    """The /metrics/job degrade path with a worker unreachable
    MID-merge (ISSUE 13 satellite): the merged gauge min/max must be
    recomputed over — and attributed to — the SURVIVING workers only,
    and the dead worker becomes a comment line, never a failed scrape
    or a phantom series."""
    from _helpers import free_port
    from horovod_tpu.runner.rpc import JsonRpcServer

    def reg_with_gauge(value):
        reg = MetricRegistry()
        reg.gauge("w_depth", "queue depth").set(value)
        return reg

    def route_for(reg):
        return lambda: (200, "text/plain; version=0.0.4",
                        reg.render_prometheus())

    srv_a = JsonRpcServer({}, secret=None,
                          get_routes={"metrics":
                                      route_for(reg_with_gauge(10.0))})
    srv_b = JsonRpcServer({}, secret=None,
                          get_routes={"metrics":
                                      route_for(reg_with_gauge(30.0))})
    dead = free_port()   # worker "2" held the (hypothetical) max; gone
    try:
        text = aggregate.scrape_and_merge(
            {"0": ("127.0.0.1", srv_a.port),
             "1": ("127.0.0.1", srv_b.port),
             "2": ("127.0.0.1", dead)}, timeout=1.0)
    finally:
        srv_a.close()
        srv_b.close()
    assert "aggregated over 2 worker(s)" in text
    assert any(line.startswith("# worker 2 unreachable")
               for line in text.splitlines()), text
    fams = aggregate.parse_prometheus(text)
    gs = {(lbl.get("agg"), lbl.get("worker")): v
          for _, lbl, v in fams["w_depth"]["samples"]}
    # attribution over the survivors only — and the sum excludes the
    # corpse instead of double-counting stale values
    assert gs == {("min", "0"): 10.0, ("max", "1"): 30.0,
                  ("sum", None): 40.0}


def test_merge_single_surviving_worker_owns_min_and_max():
    """Degenerate degrade: every peer unreachable but one — min AND
    max both attribute to the lone survivor (the attribution must not
    assume two distinct owners)."""
    reg = MetricRegistry()
    reg.gauge("w_depth", "queue depth", labels=("lane",)).set(
        7.0, lane="rx")
    per_worker = {"3": aggregate.parse_prometheus(
        reg.render_prometheus())}
    merged = aggregate.merge(per_worker)
    gs = {(lbl.get("agg"), lbl.get("worker"), lbl.get("lane")): v
          for _, lbl, v in merged["w_depth"]["samples"]}
    assert gs == {("min", "3", "rx"): 7.0, ("max", "3", "rx"): 7.0,
                  ("sum", None, "rx"): 7.0}


def test_merge_rejects_mismatched_bucket_edges():
    reg_a = MetricRegistry()
    reg_a.histogram("h_seconds", lo=-2, hi=2).observe(1.0)
    reg_b = MetricRegistry()
    reg_b.histogram("h_seconds", lo=-3, hi=3).observe(1.0)
    per_worker = {
        "0": aggregate.parse_prometheus(reg_a.render_prometheus()),
        "1": aggregate.parse_prometheus(reg_b.render_prometheus()),
    }
    with pytest.raises(ValueError, match="mismatched bucket edges"):
        aggregate.merge(per_worker)


# --- GET routes on JsonRpcServer ---------------------------------------------

def test_rpc_server_serves_metrics_and_healthz():
    from horovod_tpu.runner.rpc import JsonRpcServer
    srv = JsonRpcServer({}, secret=None)
    try:
        text = aggregate.scrape("127.0.0.1", srv.port)
        fams = aggregate.parse_prometheus(text)
        # core families declared by the instrumented modules are present
        for fam in ("hvd_rpc_request_duration_seconds",
                    "hvd_rpc_server_requests_total",
                    "hvd_cycle_duration_seconds",
                    "hvd_negotiation_duration_seconds"):
            assert fam in fams, fam
        health = json.loads(
            aggregate.scrape("127.0.0.1", srv.port, route="healthz"))
        assert health["status"] == "ok"
        assert health["pid"] == os.getpid()
        with pytest.raises(urllib.error.HTTPError):
            aggregate.scrape("127.0.0.1", srv.port, route="nope")
    finally:
        srv.close()


def test_rpc_server_custom_get_route_overrides():
    from horovod_tpu.runner.rpc import JsonRpcServer
    srv = JsonRpcServer({}, secret=None, get_routes={
        "metrics": lambda: (200, "text/plain", "custom_metric 1\n")})
    try:
        assert aggregate.scrape(
            "127.0.0.1", srv.port) == "custom_metric 1\n"
    finally:
        srv.close()


# --- RPC client/server metrics -----------------------------------------------

def test_rpc_client_retry_metrics_and_flight_events():
    import horovod_tpu.chaos as chaos
    from horovod_tpu.chaos import FaultSchedule
    from horovod_tpu.runner.rpc import (JsonRpcServer, json_request,
                                        _m_client_retries,
                                        _m_client_backoff)
    srv = JsonRpcServer({"hello": lambda p: {"ok": True}}, secret=None)
    before_r = _m_client_retries.value(method="hello")
    before_b = _m_client_backoff.value(method="hello")
    n0 = len([e for e in metrics.flight_events()
              if e["kind"] == "rpc.retry"])
    chaos.install(FaultSchedule(["rpc.request:hello nth=1 action=drop"],
                                seed=0))
    try:
        reply = json_request("127.0.0.1", srv.port, "hello", {},
                             secret=None, retries=2, backoff=0.01,
                             max_backoff=0.02)
        assert reply == {"ok": True}
    finally:
        chaos.uninstall()
        srv.close()
    assert _m_client_retries.value(method="hello") == before_r + 1
    assert _m_client_backoff.value(method="hello") > before_b
    retries = [e for e in metrics.flight_events()
               if e["kind"] == "rpc.retry"]
    assert len(retries) == n0 + 1
    assert retries[-1]["method"] == "hello"


def test_rpc_server_idem_replay_metric():
    from horovod_tpu.runner.rpc import (JsonRpcServer, _post_once,
                                        _m_server_replays)
    calls = []
    srv = JsonRpcServer({"once": lambda p: calls.append(1) or {"n": 1}},
                        secret=None)
    before = _m_server_replays.value()
    try:
        body = json.dumps({"_idem": "tok-xyz"}).encode()
        r1 = _post_once("127.0.0.1", srv.port, "once", body, None, 5.0)
        r2 = _post_once("127.0.0.1", srv.port, "once", body, None, 5.0)
        assert r1 == r2 and calls == [1]
    finally:
        srv.close()
    assert _m_server_replays.value() == before + 1


# --- chaos → metrics bridge --------------------------------------------------

def test_chaos_injections_counted_per_rule():
    import horovod_tpu.chaos as chaos
    from horovod_tpu.chaos import FaultSchedule
    live = "site.a every=1 action=delay:0.001"
    # deliberately-inert seed: the test asserts it records ZERO injections
    # hvdlint: disable=HVD305
    inert = "site.never nth=1 action=drop"
    counter = metrics.registry().counter(
        "hvd_chaos_injections_total",
        labels=("rule", "site", "action"))
    before = counter.value(rule=live, site="site.a", action="delay")
    chaos.install(FaultSchedule([live, inert], seed=0))
    try:
        for _ in range(3):
            chaos.fire("site.a")
        sched = chaos.current()
    finally:
        chaos.uninstall()
    # the CI-stage-9 assertion pattern: the schedule ACTUALLY fired —
    # a silently inert rule shows zero injections for its rule label
    assert counter.value(rule=live, site="site.a",
                         action="delay") == before + 3
    assert counter.value(rule=inert, site="site.never",
                         action="drop") == 0
    assert len(sched.fired_at("site.a")) == 3
    assert sched.rules[1].count_fired == 0


# --- stall inspector bookkeeping (satellite) ---------------------------------

def test_stall_missing_and_warned_bookkeeping():
    from horovod_tpu.stall import StallInspector, _m_warnings
    si = StallInspector(check_time=1.0, shutdown_time=0.0,
                        use_native=False)
    si.record_missing("t", [2, 1, 2])
    assert si.missing_processes("t") == [1, 2]
    assert si.missing_processes("other") == []
    before = _m_warnings.value()
    si.record_enqueue("t", 0.0)
    si.check(now=5.0)           # past check_time: one warning batch
    assert si.warnings_issued == 1
    assert _m_warnings.value() == before + 1
    assert "t" in si._warned
    si.check(now=6.0)           # already warned: no double warning
    assert si.warnings_issued == 1
    si.record_complete("t")
    assert si.missing_processes("t") == []
    assert "t" not in si._warned
    # a later re-stall of the SAME name warns again (reset worked)
    si.record_enqueue("t", 10.0)
    si.check(now=20.0)
    assert si.warnings_issued == 2


def test_stall_native_path_clears_warned_on_complete():
    """The unified reset: even when native bookkeeping is active, a
    tensor that completes after warning leaves no stale _warned entry."""
    from horovod_tpu.stall import StallInspector

    class _FakeNative:
        def __init__(self):
            self.done = []

        def record_enqueue(self, name, t):
            pass

        def record_complete(self, name):
            self.done.append(name)

        def check(self, now):
            return [("t", 99.0)], None

    si = StallInspector(check_time=1.0, use_native=False)
    si._native = _FakeNative()
    si.record_enqueue("t", 0.0)
    si.check(now=100.0)
    assert "t" in si._warned          # mirrored from the native warn
    si.record_complete("t")
    assert "t" not in si._warned      # cleared on the native path too
    assert si._native.done == ["t"]


# --- flight recorder ---------------------------------------------------------

def test_flight_recorder_ring_order_and_capacity():
    fr = FlightRecorder(capacity=5)
    for i in range(9):
        fr.record("k", i=i)
    evs = fr.events()
    assert [e["i"] for e in evs] == [4, 5, 6, 7, 8]
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs)
    assert [e["i"] for e in fr.events(limit=2)] == [7, 8]
    # non-JSON-serializable fields degrade to repr, never raise
    fr.record("k", obj=object())
    assert isinstance(fr.events()[-1]["obj"], str)


def test_flight_dump_file_format(tmp_path):
    fr = FlightRecorder()
    fr.record("elastic.assignment", epoch=3)
    fr.record("rpc.retry", method="running")
    path = tmp_path / "flight.jsonl"
    n = fr.dump("test-reason", path=str(path))
    assert n == 2 and fr.dumps == 1
    lines = [json.loads(line) for line in
             path.read_text().strip().splitlines()]
    assert lines[0]["reason"] == "test-reason"
    assert lines[0]["events"] == 2
    assert [ln["kind"] for ln in lines[1:]] == [
        "elastic.assignment", "rpc.retry"]
    assert lines[1]["seq"] < lines[2]["seq"]


def test_stall_error_dumps_flight_recorder(tmp_path, monkeypatch):
    from horovod_tpu.exceptions import StallError
    from horovod_tpu.stall import StallInspector
    path = tmp_path / "stall_flight.jsonl"
    monkeypatch.setenv(metrics.ENV_FLIGHT_PATH, str(path))
    metrics.flight_recorder().clear()
    metrics.event("elastic.assignment", epoch=7)
    metrics.event("rpc.retry", method="result")
    si = StallInspector(check_time=0.5, shutdown_time=1.0,
                        use_native=False)
    si.record_enqueue("ghost", 0.0)
    si.record_missing("ghost", [1])
    with pytest.raises(StallError, match="ghost"):
        si.check(now=10.0)
    lines = [json.loads(line) for line in
             path.read_text().strip().splitlines()]
    assert lines[0]["reason"].startswith("StallError")
    kinds = [ln.get("kind") for ln in lines[1:]]
    # the preceding elastic/RPC events appear, in order, before the abort
    ia = kinds.index("elastic.assignment")
    ir = kinds.index("rpc.retry")
    assert ia < ir < kinds.index("stall.abort")
    abort = [ln for ln in lines[1:] if ln.get("kind") == "stall.abort"][0]
    assert abort["tensor"] == "ghost" and abort["missing"] == [1]


def test_sigusr1_dumps_flight_recorder(tmp_path, monkeypatch):
    path = tmp_path / "usr1_flight.jsonl"
    monkeypatch.setenv(metrics.ENV_FLIGHT_PATH, str(path))
    metrics.flight_recorder().clear()
    metrics.event("elastic.running_reported", worker_id=0)
    metrics.event("rpc.retry", method="hosts_updated")
    assert metrics.install_signal_handler()
    os.kill(os.getpid(), signal.SIGUSR1)
    deadline = time.monotonic() + 5.0
    while not path.exists() and time.monotonic() < deadline:
        time.sleep(0.01)
    lines = [json.loads(line) for line in
             path.read_text().strip().splitlines()]
    assert lines[0]["reason"] == "SIGUSR1"
    kinds = [ln.get("kind") for ln in lines[1:]]
    assert (kinds.index("elastic.running_reported")
            < kinds.index("rpc.retry"))


def test_auto_stderr_dumps_capped(monkeypatch):
    """Failure-path dumps without a file path are capped per process;
    file dumps and force (SIGUSR1) dumps are not."""
    monkeypatch.delenv(metrics.ENV_FLIGHT_PATH, raising=False)
    monkeypatch.setattr(metrics, "_auto_stderr_dumps",
                        metrics._AUTO_STDERR_DUMP_LIMIT)
    metrics.event("noise")
    assert metrics.flight_dump("engine-fatal: Boom") == 0   # capped
    assert metrics.flight_dump("SIGUSR1", force=True) > 0   # never capped


def test_failure_report_carries_flight_events(monkeypatch):
    """A FAILURE report attaches the ring tail; the driver logs it."""
    from horovod_tpu.elastic import worker as eworker
    from horovod_tpu.runner.rpc import JsonRpcServer
    got = {}
    srv = JsonRpcServer({"result": lambda p: got.update(p) or {"ok": 1}},
                        secret=None)
    monkeypatch.setenv("HOROVOD_ELASTIC_DRIVER_ADDR", "127.0.0.1")
    monkeypatch.setenv("HOROVOD_ELASTIC_DRIVER_PORT", str(srv.port))
    monkeypatch.setenv("HOROVOD_ELASTIC_WORKER_ID", "3")
    monkeypatch.setenv("HOROVOD_SECRET_KEY", "")
    metrics.flight_recorder().clear()
    metrics.event("elastic.assignment", epoch=1)
    metrics.event("chaos.injection", site="engine.cycle", action="error")
    try:
        eworker.record_result("FAILURE")
    finally:
        srv.close()
    assert got["status"] == "FAILURE"
    kinds = [e["kind"] for e in got["flight"]]
    assert "elastic.assignment" in kinds and "chaos.injection" in kinds
    assert (kinds.index("elastic.assignment")
            < kinds.index("chaos.injection"))
    assert len(got["flight"]) <= metrics.FAILURE_REPORT_EVENTS


# --- driver-side aggregation -------------------------------------------------

def test_driver_metrics_job_route_merges_workers():
    from horovod_tpu.elastic import discovery
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.rpc import JsonRpcServer

    r0, r1 = _two_worker_registries()

    def route(reg):
        return lambda: (200, "text/plain; version=0.0.4",
                        reg.render_prometheus())

    w0 = JsonRpcServer({}, secret=None, get_routes={"metrics": route(r0)})
    w1 = JsonRpcServer({}, secret=None, get_routes={"metrics": route(r1)})
    driver = ElasticDriver(
        discovery.FixedHostDiscovery({"localhost": 1}), ["true"],
        min_np=1, port=free_port())
    try:
        driver._handle_register_notification(
            {"worker_id": 0, "addr": "127.0.0.1", "port": w0.port})
        driver._handle_register_notification(
            {"worker_id": 1, "addr": "127.0.0.1", "port": w1.port})
        text = aggregate.scrape("127.0.0.1", driver._server.port,
                                route="metrics/job")
    finally:
        driver._server.close()
        w0.close()
        w1.close()
    assert "aggregated over 2 worker(s)" in text
    fams = aggregate.parse_prometheus(text)
    count = [v for n, _, v in fams["w_lat_seconds"]["samples"]
             if n == "w_lat_seconds_count"]
    assert count == [5.0]    # 3 + 2, summed bucket-wise
    gs = {(lbl.get("agg"), lbl.get("worker")): v for _, lbl, v
          in fams["w_queue_depth"]["samples"]}
    assert gs[("min", "0")] == 10.0 and gs[("max", "1")] == 20.0
    # a dead worker degrades to a comment, not a failed scrape
    driver2 = ElasticDriver(
        discovery.FixedHostDiscovery({"localhost": 1}), ["true"],
        min_np=1, port=free_port())
    try:
        driver2._handle_register_notification(
            {"worker_id": 9, "addr": "127.0.0.1", "port": 1})
        text2 = aggregate.scrape("127.0.0.1", driver2._server.port,
                                 route="metrics/job")
    finally:
        driver2._server.close()
    assert "worker 9 unreachable" in text2


# --- engine integration (in-process, 8 virtual workers) ----------------------

def test_engine_stats_metrics_families(hvd):
    import numpy as np
    for _ in range(3):
        hvd.allreduce(np.ones((16,), np.float32), name="m_t", op=hvd.Sum)
    stats = hvd.runtime._state().engine.stats()
    m = stats["metrics"]
    assert m["enabled"] is True
    fams = m["families"]
    assert fams["hvd_engine_cycles_total"]["series"][0]["value"] >= 1
    hist = fams["hvd_cycle_duration_seconds"]
    assert hist["type"] == "histogram"
    assert hist["series"][0]["count"] >= 1
    assert hist["le"] == list(log2_edges(-17, 6))
    dispatch = fams["hvd_dispatch_bytes"]
    assert any(s["labels"].get("op") == "allreduce"
               for s in dispatch["series"])


def test_metrics_disable_enable():
    from horovod_tpu.metrics.registry import MetricRegistry  # noqa: F401
    assert metrics.ACTIVE
    try:
        metrics.disable()
        assert metrics.snapshot() == {"enabled": False}
    finally:
        metrics.enable()
    assert metrics.snapshot()["enabled"] is True


def test_metrics_dump_periodic_snapshot(tmp_path):
    env = {metrics.ENV_DUMP: str(tmp_path / "snap.json"),
           metrics.ENV_DUMP_INTERVAL: "0.05"}
    metrics.init_from_env(environ={**os.environ, **env})
    try:
        deadline = time.monotonic() + 5.0
        path = tmp_path / "snap.json"
        while not path.exists() and time.monotonic() < deadline:
            time.sleep(0.02)
        snap = json.loads(path.read_text())
    finally:
        metrics.stop_exposition()
    assert snap["pid"] == os.getpid()
    assert "hvd_rpc_client_requests_total" in snap["metrics"]


# --- 2-process integration ---------------------------------------------------

def test_two_process_scrape_and_merge():
    """ISSUE 3 acceptance: a 2-process run scrapes /metrics on both
    workers; cycle/negotiation/RPC histogram families are present,
    label-consistent, and merge bucket-wise."""
    import helpers_runner
    from horovod_tpu.runner import run
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)) + ":"
        + os.path.dirname(__file__),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_CYCLE_TIME": "0.2",
    }
    results = run(helpers_runner.metrics_scrape_fn, np=2, env=env,
                  port=free_port())
    assert len(results) == 2
    per_worker = {}
    for r in results:
        assert r["stats_enabled"] is True
        assert json.loads(r["healthz"])["status"] == "ok"
        per_worker[str(r["rank"])] = aggregate.parse_prometheus(
            r["metrics"])
    for rank, fams in per_worker.items():
        for fam in ("hvd_cycle_duration_seconds",
                    "hvd_negotiation_duration_seconds",
                    "hvd_rpc_request_duration_seconds"):
            assert fams[fam]["type"] == "histogram", (rank, fam)
            assert any(n.endswith("_count") and v > 0
                       for n, _, v in fams[fam]["samples"]), (rank, fam)
    # label-consistent across workers: same bucket edges per family →
    # the driver-side merge sums bucket-wise without error
    merged = aggregate.merge(per_worker)
    for fam in ("hvd_cycle_duration_seconds",
                "hvd_negotiation_duration_seconds"):
        total = sum(
            sum(1 for n, _, v in per_worker[rank][fam]["samples"]
                if n.endswith("_count") and v > 0)
            for rank in per_worker)
        assert total >= 2   # both workers contributed
        counts = [v for n, lbl, v in merged[fam]["samples"]
                  if n.endswith("_count")]
        assert sum(counts) == sum(
            v for rank in per_worker
            for n, _, v in per_worker[rank][fam]["samples"]
            if n.endswith("_count"))


# --- ISSUE 20: paged-KV families in the job merge ----------------------------

def test_job_merge_serve_kv_families_pick_labeled_series():
    """The job view's per-worker summaries read the paged-KV ledger
    gauges BY LABEL: ``kv_bytes`` is the kind=allocated series (never
    the kind=capacity max), ``kv_blocks`` the state=allocated series
    (never cached/free) — and a worker without a paged forward simply
    has no kv fields, not zeros."""
    from horovod_tpu.metrics import timeseries
    from horovod_tpu.metrics.registry import MetricRegistry

    reg = MetricRegistry()
    ring = timeseries.TimeSeriesRing(window=4, every_s=1.0, registry=reg)
    gb = reg.gauge("hvd_serve_kv_bytes", labels=("kind",))
    gn = reg.gauge("hvd_serve_kv_blocks", labels=("state",))
    gb.set(4096.0, kind="allocated")
    gb.set(65536.0, kind="capacity")       # bigger — must NOT win
    gn.set(2.0, state="allocated")
    gn.set(7.0, state="cached")            # bigger — must NOT win
    gn.set(9.0, state="free")
    reg.counter("hvd_serve_kv_reuse_total").inc(3)
    ring.sample()

    quiet = MetricRegistry()
    qring = timeseries.TimeSeriesRing(window=4, every_s=1.0,
                                      registry=quiet)
    quiet.counter("hvd_engine_cycles_total").inc(1)
    qring.sample()

    job = timeseries.merge_job_timeseries(
        {"0": {"enabled": True, "windows": ring.windows()},
         "1": {"enabled": True, "windows": qring.windows()}}, {})
    assert job["workers"]["0"]["kv_bytes"] == 4096.0
    assert job["workers"]["0"]["kv_blocks"] == 2.0
    assert "kv_bytes" not in job["workers"]["1"]
    assert "kv_blocks" not in job["workers"]["1"]

    # hvdtop renders the kv column: 4096 B formats as 4.0K, and the
    # kv-less worker shows the dash
    from horovod_tpu.metrics.top import render_job_timeseries
    table = render_job_timeseries(job)
    header, w0, w1 = table.splitlines()[:3]
    cols = header.split()
    assert "kv" in cols
    assert w0.split()[cols.index("kv")] == "4.0K"
    assert w1.split()[cols.index("kv")] == "-"


def test_gauge_last_label_filter():
    """`gauge_last(labels=...)` matches a SUBSET of each series' labels
    and still takes the freshest window; no match → None (not 0)."""
    from horovod_tpu.metrics import timeseries
    from horovod_tpu.metrics.registry import MetricRegistry

    reg = MetricRegistry()
    ring = timeseries.TimeSeriesRing(window=4, every_s=1.0, registry=reg)
    g = reg.gauge("hvd_serve_kv_bytes", labels=("kind",))
    g.set(10.0, kind="allocated")
    ring.sample()
    g.set(30.0, kind="allocated")
    g.set(99.0, kind="capacity")
    ring.sample()
    wins = ring.windows()
    assert timeseries.gauge_last(
        wins, "hvd_serve_kv_bytes", labels={"kind": "allocated"}) == 30.0
    assert timeseries.gauge_last(
        wins, "hvd_serve_kv_bytes", labels={"kind": "nope"}) is None
    # unlabeled call keeps the old worst-across-series contract
    assert timeseries.gauge_last(wins, "hvd_serve_kv_bytes") == 99.0
