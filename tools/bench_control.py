#!/usr/bin/env python
"""Control-plane microbench: steady-state negotiation latency, CPU-only.

Measures what ISSUE 5 changes — the coordination tail between "every
process has announced its tensors" and "every process knows" — with no
TPU, no XLA dispatch, and no jax.distributed: N real OS processes run
real ``Controller.negotiate`` rounds against the launcher-hosted RPC KV
(``runner/kv.py``) on loopback, with a seeded per-(rank, round) arrival
jitter standing in for compute skew.

Per round, every member publishes its wall-clock call time as the
round's ``aux`` payload; the **wake lag** is ``t_return − max(aux ts)``
— how long after the last member arrived this member learned the
round's outcome.  Long-poll watch bounds that by ~one RTT; the polled
transport bounds it by the exponential-backoff poll tick (capped at
250 ms), which is the gap this bench exists to show:

    python tools/bench_control.py              # watch vs poll, 4 procs
    python tools/bench_control.py --smoke      # CI: fast correctness run

Results (rounds/s, wake-lag p50/p99, controller KV-op stats proving
zero polled dir-gets under watch) print as JSON; see
docs/performance.md "Control plane".
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TOKEN = json.dumps(
    {"s": [["bench.grad", "allreduce", "sum", "float32", [1024], 0,
            False, -1, 1.0, 1.0]], "r": -1, "sp": None},
    separators=(",", ":"), sort_keys=True)


def _percentile(sorted_vals, q):
    # lazy: sys.path gains the repo inside run_worker/_spawn_and_collect
    from horovod_tpu.metrics.aggregate import percentile
    return percentile(sorted_vals, q)


# -- worker -------------------------------------------------------------------

def run_worker(args) -> int:
    sys.path.insert(0, REPO)
    from horovod_tpu.ops import controller as ctl_mod

    rank, nprocs = args.rank, args.np
    ctl_mod.jax.process_index = lambda: rank
    ctl_mod.jax.process_count = lambda: nprocs
    client = ctl_mod._client()           # the RPC KV via HOROVOD_KV_ADDR
    ctl = ctl_mod.Controller(namespace=args.namespace)
    procs = tuple(range(nprocs))

    # rendezvous through the store itself: everyone is up before round 0,
    # so spawn skew doesn't pollute the first samples
    client.key_value_set(f"bench/{args.namespace}/ready/{rank}", "1")
    deadline = time.monotonic() + 60
    while len(client.key_value_dir_get(
            f"bench/{args.namespace}/ready/")) < nprocs:
        if time.monotonic() > deadline:
            raise TimeoutError("bench rendezvous timed out")
        time.sleep(0.005)

    rng = random.Random(args.seed * 10007 + rank)
    samples = []
    t_start = time.monotonic()
    for r in range(args.rounds):
        if args.jitter_ms > 0:
            time.sleep(rng.uniform(0.0, args.jitter_ms / 1000.0))
        t_call = time.time()
        res = ctl.negotiate([_TOKEN], procs, aux={"ts": t_call})
        t_ret = time.time()
        assert res.counts[_TOKEN] == 1, (rank, r, dict(res.counts))
        last_arrival = max(res.aux[p]["ts"] for p in procs)
        samples.append({"lag": max(0.0, t_ret - last_arrival),
                        "waiter": t_call < last_arrival})
    wall = time.monotonic() - t_start
    with open(args.out, "w") as f:
        json.dump({"rank": rank, "wall_s": wall, "samples": samples,
                   "stats": ctl.stats()}, f)
    return 0


# -- driver -------------------------------------------------------------------

def _spawn_and_collect(transport: str, args) -> dict:
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from horovod_tpu.runner.kv import KV_ADDR_ENV, KV_WATCH_ENV, KvServer
    from horovod_tpu.runner.spawn import ensure_job_secret

    ensure_job_secret()
    server = KvServer()
    ns = f"{transport}{args.seed}"
    try:
        with tempfile.TemporaryDirectory(prefix="bench_ctl_") as tmp:
            workers = []
            for rank in range(args.np):
                env = dict(os.environ)
                env.update({
                    KV_ADDR_ENV: f"127.0.0.1:{server.port}",
                    KV_WATCH_ENV: "1" if transport == "watch" else "0",
                    "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": REPO + os.pathsep
                    + env.get("PYTHONPATH", ""),
                })
                out = os.path.join(tmp, f"r{rank}.json")
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--worker", "--rank", str(rank), "--np",
                       str(args.np), "--rounds", str(args.rounds),
                       "--jitter-ms", str(args.jitter_ms), "--seed",
                       str(args.seed), "--namespace", ns, "--out", out]
                workers.append((subprocess.Popen(cmd, env=env), out))
            results = []
            for proc, out in workers:
                rc = proc.wait(timeout=300)
                if rc != 0:
                    raise RuntimeError(
                        f"bench worker exited {rc} (transport="
                        f"{transport})")
                with open(out) as f:
                    results.append(json.load(f))
    finally:
        server.close()

    # wake lag per round = the slowest member's lag that round (when the
    # whole CYCLE can proceed); notify lag = the first WAITER's lag (the
    # transport's pure wake-up latency — a waiter parked on the watch
    # wakes ~one RTT after the last arrival, a polling waiter wakes at
    # its next backoff tick).  The last arriver itself is excluded from
    # notify lag: it never waits, on either transport.
    per_round = [max(w["samples"][r]["lag"] for w in results)
                 for r in range(args.rounds)]
    notify = [min((w["samples"][r]["lag"] for w in results
                   if w["samples"][r]["waiter"]), default=0.0)
              for r in range(args.rounds)]
    lags = sorted(per_round)
    notify = sorted(notify)
    wall = max(w["wall_s"] for w in results)
    stats = {k: sum(w["stats"][k] for w in results)
             for k in ("rounds", "kv_sets", "kv_dir_gets",
                       "kv_dir_watches", "kv_left_gets",
                       "kv_blocking_gets", "watch_fallbacks")}
    return {
        "transport": transport,
        "np": args.np,
        "rounds": args.rounds,
        "jitter_ms": args.jitter_ms,
        "rounds_per_s": round(args.rounds / wall, 1),
        "wake_lag_p50_ms": round(_percentile(lags, 0.50) * 1e3, 3),
        "wake_lag_p99_ms": round(_percentile(lags, 0.99) * 1e3, 3),
        "notify_lag_p50_ms": round(_percentile(notify, 0.50) * 1e3, 3),
        "notify_lag_p99_ms": round(_percentile(notify, 0.99) * 1e3, 3),
        "kv_ops": stats,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--np", type=int, default=4)
    p.add_argument("--rounds", type=int, default=150)
    p.add_argument("--jitter-ms", type=float, default=150.0,
                   help="per-(rank, round) seeded uniform arrival skew "
                        "(stands in for per-step compute/straggler skew; "
                        "the polled transport's backoff overshoot grows "
                        "with it, the watch transport's RTT does not)")
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--repeat", type=int, default=1,
                   help="interleaved repetitions per transport; the "
                        "MEDIAN-p50 run is reported (damps scheduler "
                        "noise on small shared machines)")
    p.add_argument("--transport", choices=("watch", "poll", "both"),
                   default="both")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI run: small matrix + invariant asserts")
    # internal: worker mode
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--rank", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--namespace", default="b", help=argparse.SUPPRESS)
    p.add_argument("--out", default="", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.worker:
        return run_worker(args)

    if args.smoke:
        args.np, args.rounds, args.jitter_ms = 2, 25, 2.0

    transports = (["watch", "poll"] if args.transport == "both"
                  else [args.transport])
    runs = {t: [] for t in transports}
    base_seed = args.seed
    for rep in range(max(1, args.repeat)):   # interleaved: noise bursts
        for t in transports:                 # hit both transports alike
            args.seed = base_seed + rep
            runs[t].append(_spawn_and_collect(t, args))
    args.seed = base_seed
    report = {}
    for t in transports:
        ordered = sorted(runs[t], key=lambda r: r["wake_lag_p50_ms"])
        report[t] = ordered[len(ordered) // 2]
        report[t]["runs_p50_ms"] = [r["wake_lag_p50_ms"] for r in runs[t]]
    if "watch" in report:
        w = report["watch"]["kv_ops"]
        # the event-driven invariants the docs and CI lean on
        assert w["kv_dir_gets"] == 0, w       # ZERO polled dir-gets
        assert w["kv_blocking_gets"] == 0, w
        assert w["watch_fallbacks"] == 0, w
        assert w["kv_dir_watches"] >= args.rounds, w
        assert w["kv_sets"] == args.np * args.rounds, w
    if len(report) == 2:
        report["speedup"] = {
            k: round(report["poll"][f"{k}_ms"]
                     / max(report["watch"][f"{k}_ms"], 1e-6), 1)
            for k in ("wake_lag_p50", "wake_lag_p99",
                      "notify_lag_p50", "notify_lag_p99")}
    print(json.dumps(report, indent=2))
    if args.smoke:
        print("bench_control smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
