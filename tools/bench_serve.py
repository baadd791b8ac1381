#!/usr/bin/env python
"""Serving-plane loopback bench: the four tail-latency gates, CPU-only.

Real OS worker processes run real ``ServingWorker`` pull loops against a
real ``ServingPlane`` over the HMAC-free loopback RPC transport; the
driver sweeps an OPEN-LOOP (seeded Poisson) arrival process over the
``serve_submit`` data path and measures per-request end-to-end latency
from the result stream.  Every gate must hold every run:

1. **throughput**: at ~0.9x the sequential path's capacity the cap-1
   plane queues hard (that IS the sequential serving system); the
   batched plane at >= 3x that offered load must complete everything
   with p50 no worse — micro-batching buys >= 3x throughput at equal
   p50.
2. **tail under chaos**: under the pinned ``serve.batch worker=1``
   delay seed one worker straggles every batch; the plane's EWMA
   rotation must evict it and the post-rotation p99 must sit under the
   bound (while the pre-rotation max proves the seed was not inert).
3. **elasticity**: SIGKILL a worker mid-traffic; the lease reaper
   requeues its in-flight batch and every request still completes with
   the right answer — zero lost requests.
4. **no recompiles**: across the whole sweep every worker's forward
   compiles at most once per shape bucket and never after warmup
   (``recompiles == 0``) — the compile-cache hit-rate invariant.

    python tools/bench_serve.py            # full sweep
    python tools/bench_serve.py --smoke    # CI: small matrix, all gates

Results print as JSON; see docs/serving.md and docs/performance.md.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The pinned chaos seed of gate 2: worker 1 sleeps on EVERY batch.
CHAOS_DELAY_S = 0.25
CHAOS_RULE = f"serve.batch worker=1 every=1 action=delay:{CHAOS_DELAY_S}"
CHAOS_SEED = 7

SEQ_BUCKETS = "8,16,32"
MAX_BATCH = 8

# -- llama phases (--paged / --mp): tiny llama, reduced bucket table --
# (6 compiled shapes per worker, not 12 — decode compiles dominate the
# phase wall on CPU and the gates need shapes, not scale)
LLAMA_SEQ = "8,16"
LLAMA_CAP = 4
LLAMA_NEW = 4
#: KV block size: 4 divides every seq bucket AND max_new_tokens, so
#: the paged logical width (blocks x 4) equals the dense max_len
#: (bucket + new) exactly — the bit-parity precondition.
LLAMA_BLOCK = 4
#: Shared prompt head of the reuse mix: exactly 2 full blocks.
LLAMA_HEAD = [7] * (2 * LLAMA_BLOCK)

#: Deterministic parity probes: the driver decodes these sequentially
#: (greedy_generate) and every serving path — paged, mesh-sliced —
#: must return bit-identical rows THROUGH the plane.  Lengths sweep
#: both seq buckets; first tokens are unique across the bench so no
#: probe shares a prefix block with the reuse mix.
VERIFY_PROMPTS = [
    [31, 5, 9, 2, 7],
    [37, 1, 8, 3, 6, 4, 2, 9],
    [41, 2, 2, 7, 5, 9, 1, 3, 8, 6, 4, 2],
    [43, 9, 4, 4, 1, 6, 2, 8, 5, 3, 7, 1, 9, 2, 6, 4],
]


def _percentile(sorted_vals, q):
    # lazy: sys.path gains the repo inside worker/_Phase setup
    from horovod_tpu.metrics.aggregate import percentile
    return percentile(sorted_vals, q)


# -- worker -------------------------------------------------------------------

def run_worker(args) -> int:
    sys.path.insert(0, REPO)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from horovod_tpu.runner.rpc import JsonRpcServer
    from horovod_tpu.serving.models import toy_echo_forward
    from horovod_tpu.serving.shapes import ShapeBuckets
    from horovod_tpu.serving.worker import ServingWorker

    kv_post_warmup = None
    if args.model == "toy":
        buckets = ShapeBuckets(
            batch_buckets=tuple(
                1 << i for i in range(MAX_BATCH.bit_length())
                if (1 << i) <= MAX_BATCH),
            seq_buckets=tuple(int(s) for s in SEQ_BUCKETS.split(",")))
        fwd = toy_echo_forward(buckets)
    else:
        from horovod_tpu.models import llama
        cfg = llama.tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        buckets = ShapeBuckets(
            batch_buckets=tuple(
                1 << i for i in range(LLAMA_CAP.bit_length())
                if (1 << i) <= LLAMA_CAP),
            seq_buckets=tuple(int(s) for s in LLAMA_SEQ.split(",")))
        if args.model == "paged":
            from horovod_tpu.serving.models import \
                paged_llama_decode_forward
            fwd = paged_llama_decode_forward(
                params, cfg, LLAMA_NEW, buckets,
                block_size=LLAMA_BLOCK)
        elif args.model == "mp":
            from horovod_tpu.serving.models import mp_llama_decode_forward
            fwd = mp_llama_decode_forward(params, cfg, LLAMA_NEW,
                                          buckets, mp=2)
        else:
            raise SystemExit(f"unknown bench model {args.model!r}")
    # per-worker metrics exposition: the plane learns the port from the
    # pull payload, so the driver can scrape-and-merge /metrics across
    # workers exactly like the elastic driver's /metrics/job
    msrv = JsonRpcServer({}, secret=None)
    if args.model == "paged":
        # warm here (not in the worker loop) so the driver's exact
        # fresh/reuse block expectations can start from a post-warmup
        # allocator snapshot
        fwd.warmup()
        kv_post_warmup = fwd.allocator.stats()
    worker = ServingWorker(args.addr, args.port, fwd,
                           worker_id=str(args.id), wait_s=2.0,
                           secret=None, metrics_port=msrv.port,
                           warmup=args.model != "paged")
    worker.run()   # returns on the plane's {"stop"} after close()
    stats = worker.stats()
    if kv_post_warmup is not None:
        stats["kv_post_warmup"] = kv_post_warmup
        stats["pool_nbytes"] = fwd.pool_nbytes
        stats["n_blocks"] = fwd.allocator.n_blocks
    with open(args.out, "w") as f:
        json.dump(stats, f)
    msrv.close()
    return 0


# -- driver -------------------------------------------------------------------

class _Phase:
    """One plane + worker-pool lifecycle."""

    def __init__(self, n_workers: int, max_batch: int,
                 chaos: str = "", lease_s: float = 10.0,
                 straggler_factor: float = 0.0, tmp: str = ".",
                 model: str = "toy", seq_buckets: str = SEQ_BUCKETS,
                 cap: int = MAX_BATCH):
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from horovod_tpu.runner.rpc import JsonRpcServer
        from horovod_tpu.serving.plane import ServingPlane
        # buckets always cover the full batch table; ``max_batch`` only
        # moves the ADMISSION cap (cap 1 = the sequential baseline —
        # same plane, same workers, one request per forward)
        self.plane = ServingPlane(
            tick_ms=2.0, max_batch=cap, seq_buckets=seq_buckets,
            deadline_ms=0, lease_s=lease_s,
            straggler_factor=straggler_factor)
        if max_batch != cap:
            self.plane.set_max_batch(max_batch)
        self.srv = JsonRpcServer(self.plane.rpc_handlers(), secret=None)
        self.tmp = tmp
        self.procs = []
        for wid in range(n_workers):
            env = dict(os.environ)
            env.update({"JAX_PLATFORMS": "cpu",
                        "PYTHONPATH": REPO + os.pathsep
                        + env.get("PYTHONPATH", "")})
            env.pop("HOROVOD_SECRET_KEY", None)
            if model == "mp":
                # the mesh slice: 2 virtual CPU devices per worker
                # process (x 2 worker processes = the 2x2 bench mesh)
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=2").strip()
            if chaos:
                env["HVD_CHAOS"] = chaos
                env["HVD_CHAOS_SEED"] = str(CHAOS_SEED)
            else:
                env.pop("HVD_CHAOS", None)
            out = os.path.join(tmp, f"w{len(self.procs)}_{wid}.json")
            cmd = [sys.executable, os.path.abspath(__file__), "--worker",
                   "--id", str(wid), "--addr", "127.0.0.1",
                   "--port", str(self.srv.port), "--out", out,
                   "--model", model]
            self.procs.append((subprocess.Popen(cmd, env=env), out, wid))

    def wait_ready(self, timeout: float = 180.0):
        """Block until every worker has pulled once (jax import +
        shape warmup are seconds; traffic must not race them)."""
        deadline = time.monotonic() + timeout
        want = len(self.procs)
        while time.monotonic() < deadline:
            if len(self.plane.stats()["workers"]) >= want:
                return
            time.sleep(0.05)
        raise TimeoutError(f"only {len(self.plane.stats()['workers'])}"
                           f"/{want} bench workers came up")

    def submit(self, rid: str, tokens):
        from horovod_tpu.runner.rpc import json_request
        json_request("127.0.0.1", self.srv.port, "serve_submit",
                     {"id": rid, "tokens": tokens}, secret=None)

    def result(self, rid: str, wait_s: float = 30.0):
        # one serve_result hold is server-capped at 30 s; re-poll up to
        # the caller's deadline so a slow machine waits, never asserts
        from horovod_tpu.runner.rpc import json_request
        deadline = time.monotonic() + wait_s
        while True:
            hold = min(max(deadline - time.monotonic(), 0.0), 20.0)
            res = json_request("127.0.0.1", self.srv.port,
                               "serve_result",
                               {"id": rid, "wait_s": hold},
                               timeout=hold + 10.0, secret=None)
            if res.get("done") or time.monotonic() >= deadline:
                return res

    def drain(self, wait_s: float = 1.0):
        from horovod_tpu.runner.rpc import json_request
        return json_request("127.0.0.1", self.srv.port, "serve_drain",
                            {"wait_s": wait_s}, timeout=wait_s + 10.0,
                            secret=None)

    def close(self, expect_stats: bool = True) -> list:
        self.plane.close()
        stats = []
        for proc, out, wid in self.procs:
            try:
                rc = proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                rc = proc.wait()
            if rc == 0 and os.path.exists(out):
                with open(out) as f:
                    stats.append(json.load(f))
            elif expect_stats and rc not in (0, -9):
                raise RuntimeError(f"bench worker {wid} exited {rc}")
        self.srv.close()
        return stats


def _open_loop(phase: _Phase, n: int, rate: float, seed: int,
               rng_tokens, tag: str, submitters: int = 4):
    """Submit ``n`` requests at seeded-Poisson ``rate``; wait for every
    result; returns (latencies sorted, per-request records, wall).

    The arrival SCHEDULE (tokens + absolute due times) is pre-generated
    single-threaded from the seed, then driven by several submitter
    threads — one thread's POST round-trip must not throttle the
    offered rate below the schedule.
    """
    rng = random.Random(seed)
    toks_list = [rng_tokens(rng) for _ in range(n)]
    due = []
    t_acc = 0.0
    for _ in range(n):
        t_acc += rng.expovariate(rate)
        due.append(t_acc)
    expected = {f"{tag}{i}": toks_list[i] for i in range(n)}
    submits: dict = {}
    records: dict = {}
    lock = threading.Lock()
    fail = []
    t0 = time.monotonic()

    def collector():
        # one fan-in serve_drain long-poll instead of a result poll per
        # request: the client must not throttle the offered rate
        hard = time.monotonic() + 120
        try:
            while len(records) < n and time.monotonic() < hard:
                reply = phase.drain(wait_s=1.0)
                t_done = time.monotonic()
                for rid, res in reply.get("results", {}).items():
                    toks = expected.get(rid)
                    if toks is None:
                        continue
                    assert res.get("done") and not res.get("expired"), \
                        (rid, res)
                    got = (res.get("output") or [])[:len(toks)]
                    assert got == [t * 2 + 1 for t in toks], \
                        f"{tag}: wrong answer for {rid}"
                    records[rid] = {"lat": float(res["latency_s"]),
                                    "t_done": t_done}
        except Exception as e:  # noqa: BLE001 - surfaced by the join
            fail.append(e)

    col = threading.Thread(target=collector, daemon=True)
    col.start()

    def submit_loop(indices):
        for i in indices:
            target = t0 + due[i]
            while True:
                dt = target - time.monotonic()
                if dt <= 0:
                    break
                time.sleep(min(dt, 0.0005))
            rid = f"{tag}{i}"
            t_submit = time.monotonic()
            phase.submit(rid, toks_list[i])
            with lock:
                submits[rid] = t_submit

    subs = [threading.Thread(
        target=submit_loop, args=(range(k, n, submitters),), daemon=True)
        for k in range(submitters)]
    for th in subs:
        th.start()
    for th in subs:
        th.join(timeout=120)
        assert not th.is_alive(), f"{tag}: submitter wedged"
    col.join(timeout=120)
    if fail:
        raise fail[0]
    assert len(records) == n, (f"{tag}: {len(records)}/{n} requests "
                               f"completed")
    wall = max(r["t_done"] for r in records.values()) - t0
    recs = [{"rid": rid, "t_submit": submits[rid],
             "t_done": r["t_done"], "lat": r["lat"]}
            for rid, r in records.items()]
    lats = sorted(r["lat"] for r in recs)
    return lats, recs, wall


def _tokens_sampler(rng):
    # lengths sweep all three seq buckets (workers pre-warm every
    # bucket, so this only varies which compiled shapes serve)
    length = rng.choice((5, 8, 13, 16, 21, 32))
    return [rng.randrange(0, 100) for _ in range(length)]


def _short_sampler(rng):
    # one seq class: the latency-gated phases keep the arrival stream
    # in a single shape bucket so micro-batches fill instead of
    # fragmenting across classes (real fleets route per shape class)
    length = rng.choice((3, 5, 8))
    return [rng.randrange(0, 100) for _ in range(length)]


def _gate(report, name, ok, detail):
    report["gates"][name] = {"ok": bool(ok), **detail}
    status = "PASS" if ok else "FAIL"
    print(f"gate {name}: {status} {json.dumps(detail)}", file=sys.stderr)
    if not ok:
        report["failed"] = True


def _submit_collect(phase: _Phase, reqs, tag: str,
                    stagger: float = 0.0) -> list:
    """Submit ``reqs`` (token lists), wait for every result, return the
    outputs in request order.  Deterministic closed-loop driver for the
    llama phases — the exact block-count gates need a known request
    set, not a Poisson sample."""
    for i, toks in enumerate(reqs):
        phase.submit(f"{tag}{i}", toks)
        if stagger:
            time.sleep(stagger)
    outs: dict = {}
    deadline = time.monotonic() + 120
    while len(outs) < len(reqs) and time.monotonic() < deadline:
        reply = phase.drain(wait_s=1.0)
        for rid, res in reply.get("results", {}).items():
            if not rid.startswith(tag):
                continue
            assert res.get("done") and not res.get("expired"), (rid, res)
            outs[rid] = res.get("output")
    assert len(outs) == len(reqs), \
        f"{tag}: {len(outs)}/{len(reqs)} requests completed"
    return [outs[f"{tag}{i}"] for i in range(len(reqs))]


def _verify_reference():
    """Driver-side sequential decode of VERIFY_PROMPTS — the
    bit-parity reference every serving path must match exactly
    (greedy_generate at the same max_len the bucketed forward uses)."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from horovod_tpu.models import llama
    from horovod_tpu.models.generate import greedy_generate
    from horovod_tpu.serving.shapes import ShapeBuckets
    cfg = llama.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    buckets = ShapeBuckets(
        (1,), tuple(int(s) for s in LLAMA_SEQ.split(",")))
    ref = []
    for toks in VERIFY_PROMPTS:
        s = buckets.seq_bucket(len(toks))
        out = greedy_generate(params, cfg,
                              np.asarray([toks], np.int32), LLAMA_NEW,
                              max_len=s + LLAMA_NEW)
        ref.append([int(t) for t in np.asarray(out)[0]])
    return ref


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="CI run: small request counts, all four gates")
    p.add_argument("--paged", action="store_true",
                   help="paged-KV phase: tiny-llama worker through the "
                        "block allocator; exact byte/block gates + "
                        "prefix-reuse gate + bit-parity probes")
    p.add_argument("--mp", action="store_true",
                   help="model-parallel phase: 2 workers x mp=2 (the "
                        "2x2 CPU mesh); exact per-chip param-byte gate "
                        "+ bit-parity probes")
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--n-seq", type=int, default=150)
    p.add_argument("--n-batched", type=int, default=400)
    p.add_argument("--n-chaos", type=int, default=300)
    p.add_argument("--n-kill", type=int, default=200)
    # internal: worker mode
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--model", default="toy", help=argparse.SUPPRESS)
    p.add_argument("--id", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--addr", default="127.0.0.1", help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--out", default="", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.worker:
        return run_worker(args)

    if args.smoke:
        args.n_seq, args.n_batched = 80, 240
        args.n_chaos, args.n_kill = 300, 120

    import tempfile
    report = {"gates": {}, "failed": False}
    all_worker_stats = []
    with tempfile.TemporaryDirectory(prefix="bench_serve_") as tmp:
        # ---- gates 1 + 4: sequential baseline vs batched, one worker ----
        phase = _Phase(n_workers=1, max_batch=1, tmp=tmp)
        try:
            phase.wait_ready()
            # closed-loop service probe: per-request latency with no
            # queueing — the sequential system's service time (sweeps
            # every seq class; the worker pre-warmed all shapes)
            svc = []
            rng = random.Random(args.seed)
            for i in range(24):
                rid = f"probe{i}"
                t0 = time.monotonic()
                phase.submit(rid, _tokens_sampler(rng))
                res = phase.result(rid, wait_s=60.0)
                assert res.get("done"), res
                svc.append(time.monotonic() - t0)
            svc_p50 = _percentile(sorted(svc), 0.5)
            # the sequential serving system AT LOAD: ~0.85x its
            # capacity, Poisson arrivals — the queueing its p50 pays
            # there is the cost micro-batching exists to remove
            seq_rate = 0.85 / svc_p50
            lats_seq, _, wall_seq = _open_loop(
                phase, args.n_seq, seq_rate, args.seed + 1,
                _short_sampler, "seq")
            thr_seq = args.n_seq / wall_seq

            phase.plane.set_max_batch(MAX_BATCH)
            batched_rate = 3.5 * thr_seq
            lats_b, _, wall_b = _open_loop(
                phase, args.n_batched, batched_rate, args.seed + 2,
                _short_sampler, "bat")
            thr_b = args.n_batched / wall_b
        finally:
            all_worker_stats += phase.close()
        p50_seq = _percentile(lats_seq, 0.5)
        p50_b = _percentile(lats_b, 0.5)
        report["sequential"] = {
            "service_p50_ms": round(svc_p50 * 1e3, 2),
            "offered_rps": round(seq_rate, 1),
            "throughput_rps": round(thr_seq, 1),
            "p50_ms": round(p50_seq * 1e3, 2),
            "p99_ms": round(_percentile(lats_seq, 0.99) * 1e3, 2)}
        report["batched"] = {
            "offered_rps": round(batched_rate, 1),
            "throughput_rps": round(thr_b, 1),
            "p50_ms": round(p50_b * 1e3, 2),
            "p99_ms": round(_percentile(lats_b, 0.99) * 1e3, 2)}
        _gate(report, "throughput_3x_at_equal_p50",
              # "equal p50" with a 10% measurement tolerance: both
              # medians ride loopback RPC + scheduler noise
              thr_b >= 3.0 * thr_seq and p50_b <= 1.10 * p50_seq,
              {"speedup": round(thr_b / max(thr_seq, 1e-9), 2),
               "p50_seq_ms": round(p50_seq * 1e3, 2),
               "p50_batched_ms": round(p50_b * 1e3, 2)})

        # ---- gate 2: chaos straggler + rotation ----
        phase = _Phase(n_workers=3, max_batch=MAX_BATCH,
                       chaos=CHAOS_RULE, straggler_factor=3.0, tmp=tmp)
        try:
            phase.wait_ready()
            lats_c, recs_c, _ = _open_loop(
                phase, args.n_chaos, 1.5 * thr_seq, args.seed + 3,
                _short_sampler, "chaos")
            stats = phase.plane.stats()
        finally:
            all_worker_stats += phase.close()
        rotated = [wid for wid, w in stats["workers"].items()
                   if w["rotated"]]
        # tail window: requests submitted after the rotation landed
        # (plus one injected-delay drain margin) must see healthy-path
        # latency — the straggler's last held batch finishes slow, but
        # nothing NEW rides it
        rot_at = max((w["rotated_at"] or 0.0
                      for w in stats["workers"].values()), default=0.0)
        tail = sorted(r["lat"] for r in recs_c
                      if r["t_submit"] >= rot_at + CHAOS_DELAY_S)
        p99_tail = _percentile(tail, 0.99)
        worst = max(lats_c)
        bound = 0.6 * CHAOS_DELAY_S
        report["chaos"] = {
            "rule": CHAOS_RULE, "seed": CHAOS_SEED,
            "rotated_workers": rotated,
            "p99_all_ms": round(_percentile(lats_c, 0.99) * 1e3, 2),
            "post_rotation_n": len(tail),
            "p99_post_rotation_ms": round(p99_tail * 1e3, 2),
            "max_ms": round(worst * 1e3, 2),
            "bound_ms": round(bound * 1e3, 2)}
        _gate(report, "chaos_p99_bounded_with_rotation",
              rotated == ["1"] and len(tail) >= args.n_chaos // 6
              and p99_tail <= bound and worst >= CHAOS_DELAY_S,
              {"rotated": rotated, "post_rotation_n": len(tail),
               "p99_post_rotation_ms": round(p99_tail * 1e3, 2),
               "bound_ms": round(bound * 1e3, 2),
               "seed_not_inert_max_ms": round(worst * 1e3, 2)})

        # ---- gate 3: kill a worker mid-traffic ----
        # the victim (worker 0) gets one injected 1.2 s batch hold; the
        # assassin SIGKILLs it MID-LEASE, so the requeue path is
        # exercised every run, not only on lucky timing
        phase = _Phase(n_workers=2, max_batch=MAX_BATCH, lease_s=2.0,
                       chaos="serve.batch worker=0 nth=10 "
                             "action=delay:1.2", tmp=tmp)
        killed = {"done": False}
        try:
            phase.wait_ready()
            victim = phase.procs[0][0]

            def assassin():
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if "0" in phase.plane.stats()["leased_workers"]:
                        # re-check after a beat: a normal ~ms lease has
                        # been pushed by now; the injected hold has not
                        time.sleep(0.15)
                        if "0" in phase.plane.stats()["leased_workers"]:
                            victim.kill()
                            killed["done"] = True
                            return
                    time.sleep(0.002)

            th = threading.Thread(target=assassin, daemon=True)
            th.start()
            lats_k, _, _ = _open_loop(
                phase, args.n_kill, 2.0 * thr_seq, args.seed + 4,
                _tokens_sampler, "kill")
            th.join(timeout=60)
            kstats = phase.plane.stats()
        finally:
            all_worker_stats += phase.close(expect_stats=False)
        requeued = kstats["queue"]["requeued"]
        _gate(report, "kill_worker_zero_lost",
              killed["done"] and len(lats_k) == args.n_kill
              and kstats["completed"] == args.n_kill and requeued >= 1,
              {"killed": killed["done"],
               "completed": kstats["completed"], "expected": args.n_kill,
               "requeued": requeued,
               "p99_ms": round(_percentile(lats_k, 0.99) * 1e3, 2)})

        # ---- paged-KV phase (--paged): exact bytes, reuse, parity ----
        verify_ref = None
        if args.paged or args.mp:
            verify_ref = _verify_reference()
        if args.paged:
            from horovod_tpu.models import llama as _llama
            from horovod_tpu.serving.paging import (dense_kv_nbytes,
                                                    kv_block_nbytes,
                                                    row_blocks)
            _cfg = _llama.tiny()
            bs, new = LLAMA_BLOCK, LLAMA_NEW
            phase = _Phase(n_workers=1, max_batch=LLAMA_CAP,
                           model="paged", seq_buckets=LLAMA_SEQ,
                           cap=LLAMA_CAP, tmp=tmp)
            try:
                phase.wait_ready()
                rngp = random.Random(args.seed + 7)
                # unique mix: first token unique per request, so no two
                # prompts share a prefix block — every block is fresh
                lens_a = [5, 8, 11, 16, 3, 13] * 4
                reqs_a = [[100 + i] + [rngp.randrange(0, 256)
                                       for _ in range(ln - 1)]
                          for i, ln in enumerate(lens_a)]
                _submit_collect(phase, reqs_a, "pgA", stagger=0.002)
                # shared-head mix: every prompt opens with the same 2
                # full blocks — request 0 allocates them, every later
                # request must reuse both
                n_b = 12
                reqs_b = [LLAMA_HEAD + [rngp.randrange(0, 256)
                                        for _ in range(3 + (i % 5))]
                          for i in range(n_b)]
                _submit_collect(phase, reqs_b, "pgB", stagger=0.002)
                # parity probes THROUGH the plane (padded, batched,
                # paged) vs the driver's sequential greedy_generate
                outs_v = _submit_collect(phase, VERIFY_PROMPTS, "pgV")
                # final probe burst: equal-length rows (one seq class,
                # distinct heads — no sharing), so whatever batch split
                # admission picks, the last batch's ledger must price
                # every real row at exactly row_blocks(9) blocks while
                # the dense cache would pay bucket-max for the whole
                # batch bucket, pad rows included
                probe_len = 9
                probes = [[60 + i] + [9] * (probe_len - 1)
                          for i in range(4)]
                _submit_collect(phase, probes, "pgP")
                plane_kv = phase.plane.stats()["kv"]
            finally:
                pstats = phase.close()
            all_worker_stats += pstats
            kv0 = pstats[0]["kv_post_warmup"]
            kv1 = pstats[0]["forward"]["kv"]
            pool_nbytes = pstats[0]["pool_nbytes"]
            n_blocks = pstats[0]["n_blocks"]
            blk = kv_block_nbytes(_cfg, bs)
            # exact accounting: the allocator's per-block price times
            # the pool size must equal tree_nbytes of the LIVE pool
            # arrays — priced, not estimated (the sharded_tile_layout
            # precedent)
            _gate(report, "paged_bytes_exact_vs_tree_nbytes",
                  kv1["block_nbytes"] == blk
                  and pool_nbytes == n_blocks * blk
                  and kv1["bytes_capacity"] == (n_blocks - 1) * blk,
                  {"block_nbytes": kv1["block_nbytes"],
                   "expected_block_nbytes": blk,
                   "pool_tree_nbytes": pool_nbytes,
                   "n_blocks": n_blocks})
            # per-row pricing: every real row of the last probe batch
            # held exactly ceil((len+new)/block) blocks, priced at the
            # exact per-block bytes, vs the dense cache's bucket-max
            # for the batch bucket (pad rows included — dense pays them)
            per_row = row_blocks(probe_len, new, bs)
            last = kv1["last"]
            from horovod_tpu.serving.shapes import ShapeBuckets
            bkts = ShapeBuckets(
                tuple(1 << i for i in range(LLAMA_CAP.bit_length())
                      if (1 << i) <= LLAMA_CAP),
                tuple(int(s) for s in LLAMA_SEQ.split(",")))
            s_bkt = bkts.seq_bucket(probe_len)
            dense_b = dense_kv_nbytes(
                _cfg, bkts.batch_bucket(last["rows"]), s_bkt + new)
            paged_b = last["bytes_in_use"]
            _gate(report, "paged_per_row_bytes_exact",
                  last["rows"] >= 1
                  and last["blocks"] == per_row * last["rows"]
                  and paged_b == per_row * last["rows"] * blk
                  and paged_b < dense_b,
                  {"last": last, "row_blocks": per_row,
                   "expected_bytes": per_row * last["rows"] * blk,
                   "dense_bucket_bytes": dense_b,
                   "paged_fraction": round(paged_b / dense_b, 4)})
            # exact block ledger across the whole request set: every
            # grant is either predicted-fresh or predicted-reused
            exp_reuse = len(LLAMA_HEAD) // bs * (n_b - 1)
            exp_total = (sum(row_blocks(ln, new, bs) for ln in lens_a)
                         + sum(row_blocks(len(r), new, bs)
                               for r in reqs_b)
                         + sum(row_blocks(len(p), new, bs)
                               for p in VERIFY_PROMPTS)
                         + per_row * len(probes))
            fresh_d = kv1["fresh"] - kv0["fresh"]
            reuse_d = kv1["reuse_hits"] - kv0["reuse_hits"]
            _gate(report, "paged_alloc_ledger_exact",
                  reuse_d == exp_reuse
                  and fresh_d == exp_total - exp_reuse
                  and kv1["in_use"] == 0,
                  {"fresh_delta": fresh_d, "reuse_delta": reuse_d,
                   "expected_total_blocks": exp_total,
                   "expected_reuse": exp_reuse,
                   "in_use_after_drain": kv1["in_use"]})
            # prefix reuse measurably cuts allocation under the
            # shared-head mix: the head blocks were allocated once and
            # served n_b requests
            _gate(report, "paged_prefix_reuse_cuts_blocks",
                  reuse_d > 0 and reuse_d == exp_reuse,
                  {"blocks_saved": reuse_d,
                   "shared_head_requests": n_b,
                   "saved_fraction_of_mix": round(
                       reuse_d / sum(row_blocks(len(r), new, bs)
                                     for r in reqs_b), 4)})
            _gate(report, "paged_parity_with_sequential",
                  outs_v == verify_ref,
                  {"probes": len(VERIFY_PROMPTS),
                   "match": outs_v == verify_ref})
            # satellite: the KV ledger rides serve_push onto the
            # plane's GET /serve/stats
            _gate(report, "paged_kv_on_serve_stats",
                  plane_kv is not None
                  and plane_kv["bytes_capacity"]
                  == kv1["bytes_capacity"],
                  {"plane_kv": plane_kv})
            report["paged"] = {
                "block_size": bs, "block_nbytes": blk,
                "pool_blocks": n_blocks,
                "fresh_blocks": fresh_d, "reused_blocks": reuse_d,
                "evictions": kv1["evictions"] - kv0["evictions"]}

        # ---- model-parallel phase (--mp): the 2x2 CPU mesh ----
        if args.mp:
            import jax as _jax
            from jax.sharding import PartitionSpec as _P
            from horovod_tpu.models import llama as _llama
            from horovod_tpu.training import fsdp_param_specs
            _cfg = _llama.tiny()
            phase = _Phase(n_workers=2, max_batch=LLAMA_CAP,
                           model="mp", seq_buckets=LLAMA_SEQ,
                           cap=LLAMA_CAP, tmp=tmp)
            try:
                phase.wait_ready()
                outs_m = _submit_collect(phase, VERIFY_PROMPTS, "mpV")
                rngm = random.Random(args.seed + 8)
                extra = [[51 + i] + [rngm.randrange(0, 256)
                                     for _ in range(7)]
                         for i in range(8)]
                _submit_collect(phase, extra, "mpX", stagger=0.002)
            finally:
                mstats = phase.close()
            all_worker_stats += mstats
            # expected per-chip residency: replicated leaves whole,
            # sharded leaves exactly 1/mp — computed from the same
            # specs the worker shards with
            shapes = _jax.eval_shape(
                lambda: _llama.init_params(_cfg,
                                           _jax.random.PRNGKey(0)))
            specs = fsdp_param_specs(shapes, 2, axis="hvd_serve_mp")
            is_p = lambda x: isinstance(x, _P)  # noqa: E731
            exp_chip = exp_full = 0
            for spec, leaf in zip(
                    _jax.tree_util.tree_leaves(specs, is_leaf=is_p),
                    _jax.tree_util.tree_leaves(shapes)):
                n = 1
                for d in leaf.shape:
                    n *= d
                n *= leaf.dtype.itemsize
                exp_full += n
                sharded = any(
                    "hvd_serve_mp" in (e if isinstance(e, tuple)
                                       else (e,))
                    for e in spec)
                exp_chip += n // 2 if sharded else n
            fwd_m = [s.get("forward", {}) for s in mstats]
            _gate(report, "mp_per_chip_bytes_exact",
                  len(fwd_m) == 2
                  and all(f.get("mp") == 2 for f in fwd_m)
                  and all(f.get("per_chip_param_nbytes") == exp_chip
                          for f in fwd_m)
                  and all(f.get("replica_param_nbytes") == exp_full
                          for f in fwd_m)
                  and exp_chip < exp_full,
                  {"per_chip_nbytes": exp_chip,
                   "replica_nbytes": exp_full,
                   "resident_fraction": round(exp_chip / exp_full, 4),
                   "mesh": "2 workers x mp=2"})
            _gate(report, "mp_parity_with_sequential",
                  outs_m == verify_ref,
                  {"probes": len(VERIFY_PROMPTS),
                   "match": outs_m == verify_ref})
            report["mp"] = {"workers": 2, "mp": 2,
                            "per_chip_param_nbytes": exp_chip,
                            "replica_param_nbytes": exp_full}

        # ---- gate 4: zero recompiles after warmup ----
        n_buckets_max = 4 * len(SEQ_BUCKETS.split(","))  # batch x seq
        fwd = [s.get("forward", {}) for s in all_worker_stats]
        recompiles = sum(f.get("recompiles", 0) for f in fwd)
        over = [f for f in fwd
                if f.get("compiles", 0) > n_buckets_max
                or f.get("compiles", 0) != f.get("shapes_seen", 0)]
        seen = max((f.get("shapes_seen", 0) for f in fwd), default=0)
        _gate(report, "zero_recompiles_after_warmup",
              recompiles == 0 and not over and seen >= 3
              and len(fwd) >= 4,
              {"recompiles": recompiles, "workers_reporting": len(fwd),
               "max_shapes_seen": seen,
               "bucket_ceiling": n_buckets_max})

    print(json.dumps(report, indent=2))
    if report["failed"]:
        print("bench_serve: GATE FAILURE", file=sys.stderr)
        return 1
    if args.smoke:
        print("bench_serve smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
