"""hvdchaos: deterministic fault injection + retry/backoff hardening.

Three layers of coverage:

1. Unit: the fault-schedule grammar/determinism, `json_request` retry/
   backoff + idempotency dedup, controller KV-set retry, discovery
   last-known-good and preemption-notice filtering.
2. The simulated elastic join path: an `ElasticDriver` driven directly
   (no monitor thread) whose "workers" are in-process threads speaking
   the real RPC protocol — the whole join choreography (assignment poll,
   release gate, notification push, running/result reports) in
   milliseconds instead of per-process jax imports.
3. The leader-join flake (seen in the July 2026 builder runs on an
   earlier installation; not measured on the current code): a lost
   ``hosts_updated`` push strands an incumbent on the stale epoch, so
   the new epoch never forms until that worker's own failure detection
   fires — observed once mid-session as a join timeout.  Reproduced
   DETERMINISTICALLY here by dropping the first notification under a
   pinned `FaultSchedule` with the retry disabled (the pre-hardening
   transport), then locked: with the driver's retried notification path,
   the same fault schedule converges — 25 consecutive runs.
"""

import threading
import time
import urllib.error

import pytest

from _helpers import free_port

import horovod_tpu.chaos as chaos
from horovod_tpu.chaos import FaultRule, FaultSchedule
from horovod_tpu.elastic import discovery
from horovod_tpu.elastic.driver import ElasticDriver
from horovod_tpu.elastic.worker import HostUpdateResult
from horovod_tpu.runner.rpc import JsonRpcServer, json_request


@pytest.fixture(autouse=True)
def _no_leftover_chaos():
    """Every test starts and ends with injection disabled."""
    chaos.uninstall()
    yield
    chaos.uninstall()


# --- schedule grammar & determinism ------------------------------------------

def test_rule_parse_site_qualifier_and_matchers():
    r = FaultRule.parse("rpc.request:register_worker rank=2 nth=1 "
                        "action=drop")
    assert r.site == "rpc.request"
    assert r.matchers == {"method": "register_worker", "rank": "2"}
    assert r.nth == 1 and r.action == "drop" and r.action_arg is None
    assert r.matches("rpc.request", {"method": "register_worker",
                                     "rank": 2, "extra": "x"})
    assert not r.matches("rpc.request", {"method": "register_worker",
                                         "rank": 3})
    assert not r.matches("rpc.server", {"method": "register_worker",
                                        "rank": 2})


def test_rule_parse_action_arg_and_errors():
    r = FaultRule.parse("engine.cycle every=3 action=delay:0.25")
    assert r.every == 3 and r.action == "delay" and r.action_arg == "0.25"
    # an action ARGUMENT may contain spaces (action= is the last token)
    r2 = FaultRule.parse(
        "discovery.find nth=2 action=error:transient poll failure")
    assert r2.action == "error"
    assert r2.action_arg == "transient poll failure"
    with pytest.raises(ValueError):
        FaultRule.parse("rpc.request nth=1")          # no action
    with pytest.raises(ValueError):
        FaultRule.parse("rpc.request nth=x action=drop")   # bad number
    with pytest.raises(ValueError):
        FaultRule.parse("rpc.request junk action=drop")    # not key=value
    with pytest.raises(ValueError):                   # action not last
        FaultRule.parse("rpc.request action=drop nth=1")


def test_rule_parse_validates_firing_predicates():
    """A bad spec must fail loudly at install, not with an arbitrary
    exception at some mid-run injection point (every=0 used to raise
    ZeroDivisionError at the first match)."""
    for bad in ("a every=0 action=drop", "a nth=0 action=drop",
                "a times=0 action=drop", "a after=-1 action=drop",
                "a prob=1.5 action=drop", "a prob=-0.1 action=drop",
                "a nth=1 action=dorp"):      # typo'd action kind
        with pytest.raises(ValueError):
            FaultRule.parse(bad)


def test_injected_generic_error_is_absorbed_by_rpc_retry():
    """action=error at rpc.request is a generic TRANSIENT fault: the
    retry loop must absorb it exactly like drop/reset/http500."""
    srv = JsonRpcServer({"f": lambda p: {"ok": True}}, secret=None)
    try:
        chaos.install(FaultSchedule(
            ["rpc.request:f nth=1 action=error:injected glitch"], seed=0))
        reply = json_request("localhost", srv.port, "f", {}, secret=None,
                             retries=2, backoff=0.01)
        assert reply == {"ok": True}
        assert chaos.current().fired_at("rpc.request")
    finally:
        srv.close()


def test_schedule_parse_text_json_and_env(tmp_path):
    s = FaultSchedule.parse(
        "# comment\nrpc.request nth=1 action=drop\n\n"
        "kv.set nth=2 action=error", seed=5)
    assert [r.site for r in s.rules] == ["rpc.request", "kv.set"]
    assert s.seed == 5

    s2 = FaultSchedule.parse(
        '{"seed": 9, "rules": ["rpc.request nth=1 action=drop"]}')
    assert s2.seed == 9 and len(s2.rules) == 1

    f = tmp_path / "sched.txt"
    f.write_text("discovery.find nth=1 action=flap\n")
    env = {chaos.ENV_SPEC: f"@{f}", chaos.ENV_SEED: "3"}
    s3 = chaos.from_env(env)
    assert s3.seed == 3 and s3.rules[0].site == "discovery.find"
    assert chaos.from_env({}) is None


def test_schedule_nth_every_times_counters():
    s = FaultSchedule(["a nth=2 action=error", "a every=2 action=delay:0"],
                      seed=0)
    # match 1: rule0 seen=1 (no fire), rule1 seen=1 (no fire)
    assert s.decide("a", {}) is None
    # match 2: rule0 fires (nth=2) and wins before rule1 is consulted
    assert s.decide("a", {}).kind == "error"
    # match 3: rule0 done; rule1 seen=2 → fires
    assert s.decide("a", {}).kind == "delay"
    assert [k for _, k, _ in s.fired] == ["error", "delay"]


def test_schedule_prob_deterministic_per_seed():
    def draws(seed):
        s = FaultSchedule(["x prob=0.5 action=error"], seed=seed)
        return [s.decide("x", {}) is not None for _ in range(32)]

    assert draws(1) == draws(1)          # same seed → same firings
    assert draws(1) != draws(2)          # different seed → different


def test_fire_disabled_is_noop_and_delay_executes():
    assert not chaos.ACTIVE
    assert chaos.fire("anything", x=1) is None
    chaos.install(FaultSchedule(["t nth=1 action=delay:0.05"], seed=0))
    t0 = time.monotonic()
    assert chaos.fire("t") is None        # delay executed in-place
    assert time.monotonic() - t0 >= 0.04
    assert chaos.current().fired_at("t")


def test_fire_raising_actions():
    chaos.install(FaultSchedule([
        "a nth=1 action=drop", "b nth=1 action=reset",
        "c nth=1 action=http500", "d nth=1 action=error:boom"], seed=0))
    with pytest.raises(ConnectionError):
        chaos.fire("a")
    with pytest.raises(ConnectionResetError):
        chaos.fire("b")
    with pytest.raises(urllib.error.HTTPError):
        chaos.fire("c")
    with pytest.raises(chaos.ChaosError, match="boom"):
        chaos.fire("d")


# --- rpc retry/backoff + idempotency -----------------------------------------

def test_json_request_retries_transient_500():
    calls = []

    def flaky(payload):
        calls.append(payload)
        if len(calls) < 3:
            raise RuntimeError("transient")   # server replies 500
        return {"ok": len(calls)}

    srv = JsonRpcServer({"f": flaky}, secret=None)
    try:
        reply = json_request("localhost", srv.port, "f", {}, secret=None,
                             retries=3, backoff=0.01)
        assert reply == {"ok": 3} and len(calls) == 3
    finally:
        srv.close()


def test_json_request_no_retry_on_permanent_4xx():
    srv = JsonRpcServer({}, secret=None)
    try:
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError):
            json_request("localhost", srv.port, "nope", {}, secret=None,
                         retries=3, backoff=0.2)
        assert time.monotonic() - t0 < 0.5   # no backoff chain for 404
    finally:
        srv.close()


def test_json_request_retry_exhaustion_raises():
    port = free_port()   # nothing listening: connection refused
    with pytest.raises(OSError):
        json_request("localhost", port, "f", {}, secret=None,
                     retries=1, backoff=0.01)


def test_json_request_opt_out_single_attempt():
    port = free_port()
    t0 = time.monotonic()
    with pytest.raises(OSError):
        json_request("localhost", port, "f", {}, secret=None,
                     retries=0, backoff=5.0)
    assert time.monotonic() - t0 < 1.0


def test_idempotency_token_dedupes_duplicate_delivery():
    """chaos dup sends every request twice; with idempotent=False the
    handler must still run once (server-side token dedup) while a plain
    idempotent call really does run twice."""
    counter = {"n": 0}

    def incr(payload):
        counter["n"] += 1
        return {"n": counter["n"]}

    srv = JsonRpcServer({"incr": incr}, secret=None)
    try:
        chaos.install(FaultSchedule(
            ["rpc.request:incr every=1 action=dup"], seed=0))
        reply = json_request("localhost", srv.port, "incr", {},
                             secret=None, idempotent=False, retries=0)
        assert counter["n"] == 1          # duplicate deduped
        assert reply == {"n": 1}          # replayed reply, not a re-run
        json_request("localhost", srv.port, "incr", {}, secret=None,
                     retries=0)           # idempotent: no token
        assert counter["n"] == 3          # both deliveries ran
    finally:
        srv.close()


def test_retried_failure_report_counts_once():
    """The blacklist-feeding path: a FAILURE report whose REPLY is lost
    (handler ran, client retries) must not double-count the host — the
    retry replays the cached reply instead of re-running the handler."""
    from horovod_tpu.elastic import registration
    reg = registration.WorkerStateRegistry(blacklist_threshold=2)
    runs = []

    def result(payload):
        runs.append(payload)
        reg.record_result(0, payload["status"], payload["hostname"])
        return {"ok": True}

    srv = JsonRpcServer({"result": result}, secret=None)
    try:
        # drop-reply: the handler RUNS, then the reply is swallowed
        chaos.install(FaultSchedule(
            ["rpc.server:result nth=1 action=drop-reply"], seed=0))
        reply = json_request("localhost", srv.port, "result",
                             {"status": "FAILURE", "hostname": "h1"},
                             secret=None, idempotent=False, retries=2,
                             backoff=0.01)
        assert reply == {"ok": True}        # replayed from the cache
        assert len(runs) == 1               # handler applied exactly once
        assert reg.failure_count("h1") == 1
        assert not reg.is_blacklisted("h1")
    finally:
        srv.close()


def test_concurrent_duplicate_waits_for_in_flight_handler():
    """Check-then-act hole: a duplicate arriving while the first
    delivery's handler is still running must wait and replay its reply,
    not dispatch the handler a second time."""
    import json as _json
    import urllib.request
    gate = threading.Event()
    runs = []

    def slow(payload):
        runs.append(payload)
        gate.wait(10.0)
        return {"n": len(runs)}

    srv = JsonRpcServer({"slow": slow}, secret=None)
    try:
        body = _json.dumps({"_idem": "tok-race"}).encode()

        def post(out):
            req = urllib.request.Request(
                f"http://localhost:{srv.port}/slow", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=15) as resp:
                out.append(_json.loads(resp.read()))

        r1, r2 = [], []
        t1 = threading.Thread(target=post, args=(r1,), daemon=True)
        t2 = threading.Thread(target=post, args=(r2,), daemon=True)
        t1.start()
        time.sleep(0.2)                 # first delivery is in the handler
        t2.start()
        time.sleep(0.2)
        gate.set()                      # release the handler
        t1.join(15)
        t2.join(15)
        assert runs == [{}]             # handler ran exactly once
        assert r1 == [{"n": 1}] and r2 == [{"n": 1}]
    finally:
        gate.set()
        srv.close()


def test_kv_set_retries_transient_failures():
    from horovod_tpu.ops.controller import _kv_set

    class FlakyClient:
        def __init__(self, fails):
            self.fails = fails
            self.calls = 0

        def key_value_set(self, key, value, allow_overwrite=True):
            self.calls += 1
            if self.calls <= self.fails:
                raise RuntimeError("UNAVAILABLE: service hiccup")

    c = FlakyClient(fails=2)
    _kv_set(c, "k", "v")          # absorbed: 2 failures < 3 attempts
    assert c.calls == 3
    with pytest.raises(RuntimeError):
        _kv_set(FlakyClient(fails=3), "k", "v")


# --- discovery hardening ------------------------------------------------------

def test_discovery_last_known_good_on_transient_failure(tmp_path):
    hf = tmp_path / "hosts.txt"
    hf.write_text("a:2\n")
    d = discovery.HostDiscoveryScript(f"cat {hf}", failure_threshold=3)
    assert d.find_available_hosts_and_slots() == {"a": 2}
    hf.unlink()                              # script now exits non-zero
    assert d.find_available_hosts_and_slots() == {"a": 2}   # 1st flake
    assert d.find_available_hosts_and_slots() == {"a": 2}   # 2nd flake
    with pytest.raises(Exception):
        d.find_available_hosts_and_slots()   # 3rd consecutive: propagate
    hf.write_text("a:4\n")                   # recovery resets the count
    assert d.find_available_hosts_and_slots() == {"a": 4}
    hf.unlink()
    assert d.find_available_hosts_and_slots() == {"a": 4}


def test_discovery_failure_with_no_known_good_propagates():
    d = discovery.HostDiscoveryScript("false", failure_threshold=3)
    with pytest.raises(Exception):
        d.find_available_hosts_and_slots()


def test_discovery_chaos_error_and_flap(tmp_path):
    hf = tmp_path / "hosts.txt"
    hf.write_text("a:2\n")
    d = discovery.HostDiscoveryScript(f"cat {hf}", failure_threshold=3)
    assert d.find_available_hosts_and_slots() == {"a": 2}
    # note the counter semantics: a rule's counters only advance on
    # events it is CONSULTED for — rule 1 never sees the event rule 0
    # fired on, so its first consultation is the second poll
    chaos.install(FaultSchedule([
        "discovery.find nth=1 action=error:injected-poll-failure",
        "discovery.find nth=1 action=flap"], seed=0))
    # injected script failure → last-known-good with a warning
    assert d.find_available_hosts_and_slots() == {"a": 2}
    # injected flap → a *valid* empty answer (all hosts gone this poll)
    assert d.find_available_hosts_and_slots() == {}


def test_notified_preemption_discovery(tmp_path):
    inner = discovery.FixedHostDiscovery({"a": 2, "b": 2, "c": 1})
    notice = tmp_path / "preempt.txt"
    d = discovery.NotifiedPreemptionDiscovery(
        inner, notice_file=str(notice),
        notice_fn=lambda: ["c"])
    # callback only (file absent): c drained
    assert d.find_available_hosts_and_slots() == {"a": 2, "b": 2}
    notice.write_text("# maintenance\nb:eviction-in-120s\n")
    assert d.find_available_hosts_and_slots() == {"a": 2}
    assert d.preempted_hosts() == {"b", "c"}
    # a broken callback must not break discovery
    d2 = discovery.NotifiedPreemptionDiscovery(
        inner, notice_fn=lambda: 1 / 0)
    assert d2.find_available_hosts_and_slots() == {"a": 2, "b": 2, "c": 1}


# --- the simulated elastic join path -----------------------------------------

class SimWorker:
    """An in-process stand-in for an elastic worker: speaks the real RPC
    protocol (assignment poll under the release gate, notification
    endpoint, running/result reports) without the jax import/rendezvous
    cost, so join choreography runs in milliseconds and a whole fault-
    seed sweep fits in one test."""

    def __init__(self, wid, driver_port, total_steps=4, tick=0.01):
        self.wid = wid
        self.driver_port = driver_port
        self.total_steps = total_steps
        self.tick = tick
        self.exit_code = None
        self.epochs = []                    # epochs this worker ran in
        self._stop = threading.Event()
        self._update = threading.Event()
        self._srv = JsonRpcServer({"hosts_updated": self._on_update})
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _on_update(self, payload):
        self._update.set()
        return {"ok": True}

    def _rpc(self, name, payload, **kw):
        return json_request("127.0.0.1", self.driver_port, name,
                            payload, **kw)

    def _fetch(self, min_epoch, timeout=30.0):
        deadline = time.monotonic() + timeout
        while not self._stop.is_set():
            try:
                reply = self._rpc("assignment",
                                  {"worker_id": self.wid,
                                   "min_epoch": min_epoch}, retries=0)
            except Exception:  # noqa: BLE001 - transient; poll absorbs
                reply = {}
            if reply.get("removed"):
                return None
            if reply.get("ready"):
                return reply
            if time.monotonic() > deadline:
                raise TimeoutError(f"worker {self.wid}: no assignment")
            time.sleep(min(0.05, reply.get("retry_after", 0.05)))
        return None

    def _run(self):
        try:
            self._rpc("register_notification",
                      {"worker_id": self.wid, "addr": "127.0.0.1",
                       "port": self._srv.port}, backoff=0.01)
            epoch, steps = -1, 0
            while steps < self.total_steps and not self._stop.is_set():
                asg = self._fetch(min_epoch=epoch + 1)
                if asg is None:             # removed from the job
                    self.exit_code = 0
                    return
                epoch = asg["epoch"]
                self.epochs.append(epoch)
                # generous retry budget: the convergence sweep's bounded
                # fault budget must never exhaust a report permanently
                self._rpc("running", {"worker_id": self.wid,
                                      "epoch": epoch},
                          retries=8, backoff=0.01)
                # "train" until done or the driver announces new hosts
                while steps < self.total_steps and not self._stop.is_set():
                    if self._update.is_set():
                        self._update.clear()
                        break               # re-rendezvous into new epoch
                    time.sleep(self.tick)
                    steps += 1
            if self._stop.is_set():
                self.exit_code = 0
                return
            self._rpc("result", {"worker_id": self.wid,
                                 "status": "SUCCESS",
                                 "hostname": "localhost"},
                      idempotent=False, retries=8, backoff=0.01)
            self.exit_code = 0
        except Exception:  # noqa: BLE001 - any protocol failure = crash
            self.exit_code = 1

    def stop(self):
        self._stop.set()

    def close(self):
        self.stop()
        self.thread.join(timeout=10)
        self._srv.close()


class _SimProc:
    class _Popen:
        def __init__(self, worker):
            self._worker = worker

        def poll(self):
            return self._worker.exit_code

        def terminate(self):
            self._worker.stop()

        def kill(self):
            self._worker.stop()

    def __init__(self, worker):
        self.popen = self._Popen(worker)


class SimDriver(ElasticDriver):
    """ElasticDriver whose spawns are SimWorker threads.  Driven directly
    via ``_apply_hosts`` (no monitor loop), so every transition in a test
    is explicit and the run is deterministic."""

    def __init__(self, *args, **kw):
        self.workers = {}
        self.worker_steps = kw.pop("worker_steps", 4)
        super().__init__(*args, **kw)

    def _launch(self, slot, coord_addr, coord_port, env):
        w = SimWorker(int(env["HOROVOD_ELASTIC_WORKER_ID"]),
                      self.port, total_steps=self.worker_steps)
        self.workers[w.wid] = w
        return _SimProc(w)

    def close(self):
        for w in self.workers.values():
            w.stop()
        for w in self.workers.values():
            w.close()
        self._server.close()


@pytest.fixture
def sim_driver():
    d = SimDriver(discovery.FixedHostDiscovery({"localhost": 2}),
                  ["true"], min_np=2, port=free_port(),
                  start_timeout=60.0, worker_steps=10_000)
    yield d
    d.close()


def _drain(driver, timeout=20.0):
    """Wait for every sim worker to exit cleanly."""
    deadline = time.monotonic() + timeout
    for w in driver.workers.values():
        w.thread.join(timeout=max(0.0, deadline - time.monotonic()))
    return {w.wid: w.exit_code for w in driver.workers.values()}


def test_sim_join_path_no_faults(sim_driver):
    """Baseline: the simulated join choreography forms, scales up, and
    completes with no chaos installed."""
    d = sim_driver
    d.worker_steps = 30
    d._apply_hosts({"localhost": 2}, HostUpdateResult.ADDED)
    i, info = d.wait_event("epoch_formed", timeout=10,
                           match=lambda e: e["size"] == 2)
    d._apply_hosts({"localhost": 3}, HostUpdateResult.ADDED)
    d.wait_event("epoch_formed", timeout=10,
                 match=lambda e: e["size"] == 3, since=i + 1)
    codes = _drain(d)
    assert codes == {0: 0, 1: 0, 2: 0}
    assert 1 in d.workers[0].epochs     # incumbents re-joined epoch 1


# --- the leader-join flake: repro, fix, pin ----------------------------------

# The pinned schedule: lose the first hosts_updated push of the run.
LEADER_JOIN_FLAKE = "rpc.request:hosts_updated nth=1 action=drop"


def test_leader_join_flake_reproduction(sim_driver):
    """ROOT CAUSE (VERDICT weak #3): the driver pushed ``hosts_updated``
    with a single unretried POST.  One lost push → the incumbent keeps
    training on the stale epoch, never re-polls, and the new epoch's
    release gate holds every member hostage until the formation deadline
    — observed as a rare join timeout under load.  With the pre-
    hardening transport (retries disabled), the fault is a deterministic
    reproduction: the scaled-up epoch must NOT form."""
    d = sim_driver
    d.notify_retries = 0                 # the pre-fix notification path
    chaos.install(FaultSchedule([LEADER_JOIN_FLAKE], seed=1))
    d._apply_hosts({"localhost": 2}, HostUpdateResult.ADDED)
    i, _ = d.wait_event("epoch_formed", timeout=10,
                        match=lambda e: e["size"] == 2)
    d._apply_hosts({"localhost": 3}, HostUpdateResult.ADDED)
    with pytest.raises(TimeoutError):
        d.wait_event("epoch_formed", timeout=2.0,
                     match=lambda e: e["size"] == 3, since=i + 1)
    # exactly the scheduled fault fired, nothing else
    assert [k for _, k, _ in chaos.current().fired] == ["drop"]
    # and the stranded incumbent is still on epoch 0
    stranded = [w for w in d.workers.values() if 1 not in w.epochs]
    assert stranded, "some incumbent should have missed the update"


def test_leader_join_flake_regression_25_runs():
    """THE PIN: under the same fault schedule, the retried notification
    path (ElasticDriver.notify_retries, default 2) absorbs the lost push
    and the join converges — 25 consecutive seeded runs."""
    for run in range(25):
        d = SimDriver(discovery.FixedHostDiscovery({"localhost": 2}),
                      ["true"], min_np=2, port=free_port(),
                      start_timeout=60.0, worker_steps=10_000)
        try:
            chaos.install(FaultSchedule([LEADER_JOIN_FLAKE], seed=run))
            d._apply_hosts({"localhost": 2}, HostUpdateResult.ADDED)
            i, _ = d.wait_event("epoch_formed", timeout=10,
                                match=lambda e: e["size"] == 2)
            d._apply_hosts({"localhost": 3}, HostUpdateResult.ADDED)
            d.wait_event("epoch_formed", timeout=10,
                         match=lambda e: e["size"] == 3, since=i + 1)
            # the scheduled fault really was injected (the retry path
            # absorbed it; it did not just fail to fire)
            assert chaos.current().fired_at("rpc.request")
        finally:
            chaos.uninstall()
            d.close()


# --- convergence sweep under mixed fault seeds (CI stage 9) ------------------

def _sweep_schedule(seed):
    """Mixed adversity with a BOUNDED destructive budget per method:
    delays are free-running, but each method's drop cap (times=) stays
    below its caller's retry budget (reports retry 8×, hosts_updated
    pushes 3 attempts, assignment polls retry unboundedly), so
    convergence is guaranteed by construction and any hang is a real
    coordination bug, not an exhausted retry.  The sim workers have no
    collective-failure fallback (the real workers' safety net for a
    permanently lost push), so the schedule must not exceed what the
    retry layer alone absorbs."""
    return FaultSchedule([
        "rpc.request prob=0.15 action=delay:0.02",
        "rpc.request:hosts_updated nth=1 action=drop",  # the flake fault
        "rpc.request:running prob=0.2 times=6 action=drop",
        "rpc.request:result prob=0.2 times=6 action=drop",
        "rpc.request:register_notification prob=0.2 times=4 action=drop",
        "rpc.server:assignment prob=0.1 times=6 action=drop",
        "elastic.assignment prob=0.15 action=delay:0.02",
        "rpc.request:result nth=1 action=dup",
    ], seed=seed)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_join_converges_under_fault_seed(seed):
    """The elastic join path (form → scale-up → complete) must converge
    under each pinned fault seed; exercised by CI stage 9."""
    import horovod_tpu.metrics as metrics
    d = SimDriver(discovery.FixedHostDiscovery({"localhost": 2}),
                  ["true"], min_np=2, port=free_port(),
                  start_timeout=60.0, worker_steps=40)
    flake_rule = "rpc.request:hosts_updated nth=1 action=drop"
    inj = metrics.registry().counter("hvd_chaos_injections_total",
                                     labels=("rule", "site", "action"))
    inj_before = inj.value(rule=flake_rule, site="rpc.request",
                           action="drop")
    try:
        chaos.install(_sweep_schedule(seed))
        d._apply_hosts({"localhost": 2}, HostUpdateResult.ADDED)
        i, _ = d.wait_event("epoch_formed", timeout=30,
                            match=lambda e: e["size"] == 2)
        d._apply_hosts({"localhost": 3}, HostUpdateResult.ADDED)
        d.wait_event("epoch_formed", timeout=30,
                     match=lambda e: e["size"] == 3, since=i + 1)
        codes = _drain(d, timeout=30)
        assert codes == {0: 0, 1: 0, 2: 0}, (
            codes, chaos.current().stats())
        # the schedule actually FIRED — a silently inert HVD_CHAOS spec
        # must not pass as a chaos run (ISSUE 3 chaos→metrics bridge);
        # the deterministic nth=1 flake rule is the guaranteed witness
        assert chaos.current().fired, chaos.current().stats()
        if metrics.ACTIVE:   # counter only updates with metrics on
            assert inj.value(rule=flake_rule, site="rpc.request",
                             action="drop") == inj_before + 1
        # every worker's SUCCESS landed despite the fault schedule
        from horovod_tpu.elastic import registration
        for wid in codes:
            assert d.registry.state(wid) == registration.SUCCESS
    finally:
        d.close()


# --- engine-cycle injection point (end-to-end through a real cycle) ----------

def test_engine_cycle_injection(hvd):
    """The engine's cycle-loop injection point fires through a real
    allreduce; a delay action slows the cycle without corrupting it."""
    import numpy as np
    sched = FaultSchedule(["engine.cycle nth=1 action=delay:0.01"], seed=0)
    chaos.install(sched)
    x = hvd.allreduce(np.ones((4,), np.float32), op=hvd.Sum,
                      name="chaos.cycle.probe")
    np.testing.assert_allclose(np.asarray(x), np.full((4,), 8.0))
    assert sched.fired_at("engine.cycle")


# --- event-driven KV watch: drop → polled fallback (ISSUE 5) -----------------

def test_watch_drop_falls_back_to_poll_and_converges(monkeypatch):
    """Fixed-seed regression: a schedule dropping every
    ``rpc.request:key_value_dir_watch`` forces the controller off the
    long-poll transport; the round must DEMOTE to polled dir-gets (one
    fallback, sticky for the incarnation) and still converge on the
    same dispatch decision, with the schedule proven non-inert."""
    import hashlib
    import json

    from horovod_tpu.ops import controller as ctl_mod
    from horovod_tpu.runner.kv import KvServer, RpcKvClient

    monkeypatch.setenv("HOROVOD_RPC_RETRIES", "1")
    monkeypatch.setenv("HOROVOD_RPC_BACKOFF_S", "0.01")
    srv = KvServer(secret=None)
    cli = RpcKvClient("127.0.0.1", srv.port, secret=None)
    orig_client, orig_pi = ctl_mod._client, ctl_mod.jax.process_index
    ctl_mod._client = lambda: cli
    ctl_mod.jax.process_index = lambda: 0
    sched = FaultSchedule.parse(
        "rpc.request:key_value_dir_watch action=drop", seed=11)
    chaos.install(sched)
    try:
        ctl = ctl_mod.Controller()
        tok = json.dumps(
            {"s": [["t", "allreduce", "sum", "float32", [2], 0, False,
                    -1, 1.0, 1.0]], "r": -1, "sp": None},
            separators=(",", ":"), sort_keys=True)
        gk = "g" + hashlib.sha1(b"0,1").hexdigest()[:12]
        h = hashlib.sha1(tok.encode()).hexdigest()

        def peer(seq):
            time.sleep(0.03)
            srv.store.set(
                f"hvdctl/0/{gk}/{seq}/a/1",
                json.dumps({"h": h, "e": [tok]},
                           separators=(",", ":")))

        for seq in range(3):
            threading.Thread(target=peer, args=(seq,),
                             daemon=True).start()
            res = ctl.negotiate([tok], (0, 1))
            assert res.counts[tok] == 1        # converged every round
        st = ctl.stats()
        assert st["watch_fallbacks"] == 1, st  # demoted exactly once
        assert st["kv_dir_watches"] == 0, st   # no watch ever landed
        assert st["kv_dir_gets"] >= 3, st      # polling carried the job
        assert sched.fired_at("rpc.request"), sched.stats()
    finally:
        chaos.uninstall()
        ctl_mod._client = orig_client
        ctl_mod.jax.process_index = orig_pi
        srv.close()
