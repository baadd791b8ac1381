"""KV-cache text generation with the Llama family.

Runs greedy and sampled decoding on a randomly-initialized tiny model
(the framework ships architecture + decoding machinery, not weights —
load real checkpoints with horovod_tpu.checkpoint.restore).

    python examples/generate_llama.py [--temperature 0.8 --top-k 40]

``--serve`` drives the elastic serving plane end to end instead: a
ServingPlane + ServingWorker pair micro-batches a burst of ragged
prompts through the SAME model (batched ragged KV-cache decode,
per-row bit-identical to this script's sequential path — the
correctness floor tests/test_generate.py pins) and prints p50/p99
request latency next to the sequential one-at-a-time baseline.
``tools/bench_serve.py`` is the gated CPU-loopback version.

    python examples/generate_llama.py --serve [--requests 32]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import generate, llama


def serve_mode(args, cfg, params):
    """The serving-plane A/B: sequential one-at-a-time decode (the
    pre-existing path below, the baseline) vs the micro-batched plane
    over the identical model."""
    import time as _time

    from horovod_tpu.models import generate as gen
    from horovod_tpu.runner.rpc import JsonRpcServer, json_request
    from horovod_tpu.serving.models import llama_decode_forward
    from horovod_tpu.serving.plane import ServingPlane
    from horovod_tpu.serving.worker import ServingWorker

    rng = np.random.RandomState(0)
    lengths = [int(rng.randint(4, 24)) for _ in range(args.requests)]
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lengths]

    # sequential baseline: the single-request path, one jit per shape
    seq_fn = jax.jit(lambda p, t: gen.greedy_generate(
        p, cfg, t, args.max_new, max_len=32 + args.max_new))
    pad = [np.pad(pr, (0, 32 - len(pr))) for pr in prompts]
    seq_fn(params, jnp.asarray(pad[0][None, :]))  # compile
    t0 = _time.perf_counter()
    seq_lat = []
    for row in pad:
        t1 = _time.perf_counter()
        seq_fn(params, jnp.asarray(row[None, :])).block_until_ready()
        seq_lat.append(_time.perf_counter() - t1)
    seq_wall = _time.perf_counter() - t0

    plane = ServingPlane(tick_ms=2.0, max_batch=8, seq_buckets="32",
                         deadline_ms=0)
    srv = JsonRpcServer(plane.rpc_handlers(), secret=None)
    fwd = llama_decode_forward(params, cfg, args.max_new, plane.buckets)
    worker = ServingWorker("127.0.0.1", srv.port, fwd, worker_id="0",
                           wait_s=2.0, secret=None, warmup=True)
    worker.start()
    # wait out the warmup compiles so latency measures serving
    deadline = _time.monotonic() + 600
    while not plane.stats()["workers"] and _time.monotonic() < deadline:
        _time.sleep(0.05)

    t0 = _time.perf_counter()
    for i, pr in enumerate(prompts):
        json_request("127.0.0.1", srv.port, "serve_submit",
                     {"id": f"r{i}", "tokens": pr.tolist()},
                     secret=None)
    lats = []
    for i in range(args.requests):
        # one serve_result hold is server-capped (30 s); re-poll so a
        # slow CPU burst waits instead of failing
        deadline = _time.monotonic() + 600
        while True:
            res = json_request("127.0.0.1", srv.port, "serve_result",
                               {"id": f"r{i}", "wait_s": 20.0},
                               timeout=30.0, secret=None)
            if res.get("done") or _time.monotonic() > deadline:
                break
        assert res.get("done"), res
        lats.append(res["latency_s"])
    serve_wall = _time.perf_counter() - t0
    plane.close()
    worker.stop()
    worker.join(10)
    srv.close()

    seq_lat.sort()
    lats.sort()
    n = args.requests
    tok = n * args.max_new
    from horovod_tpu.metrics.aggregate import percentile

    def pct(v, q):
        return percentile(v, q) * 1e3

    print(f"sequential: {tok / seq_wall:8.1f} tok/s   "
          f"p50 {pct(seq_lat, .5):7.1f} ms   p99 {pct(seq_lat, .99):7.1f} ms")
    print(f"serving:    {tok / serve_wall:8.1f} tok/s   "
          f"p50 {pct(lats, .5):7.1f} ms   p99 {pct(lats, .99):7.1f} ms   "
          f"({fwd.stats()['compiles']} compiled shapes, "
          f"{fwd.stats()['recompiles']} recompiles)")
    print(f"speedup: {seq_wall / serve_wall:.2f}x over {n} ragged "
          f"requests x {args.max_new} new tokens")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--serve", action="store_true",
                   help="drive the serving plane A/B instead of the "
                        "one-shot decode (docs/serving.md)")
    p.add_argument("--requests", type=int, default=24,
                   help="--serve: ragged requests in the burst")
    args = p.parse_args()

    on_cpu = jax.devices()[0].platform == "cpu"
    cfg = (llama.tiny(vocab=512, seq=256) if on_cpu else
           llama.LlamaConfig(vocab_size=4096, d_model=512, n_layers=8,
                             n_heads=8, n_kv_heads=4, d_ff=1536,
                             max_seq_len=1024))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if args.serve:
        serve_mode(args, cfg, params)
        return
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch, 16)), jnp.int32)

    fn = jax.jit(lambda p, t, r: generate.generate(
        p, cfg, t, args.max_new, temperature=args.temperature,
        top_k=args.top_k, rng=r))
    key = jax.random.PRNGKey(42)
    toks = fn(params, prompt, key)       # compile
    toks.block_until_ready()
    t0 = time.perf_counter()
    toks = fn(params, prompt, key)
    toks.block_until_ready()
    dt = time.perf_counter() - t0
    mode = ("greedy" if args.temperature == 0 else
            f"T={args.temperature} top_k={args.top_k}")
    print(f"{mode}: {args.batch}x{args.max_new} tokens in {dt*1e3:.0f} ms "
          f"({args.batch * args.max_new / dt:.0f} tok/s)")
    print("ids:", np.asarray(toks[0, :16]))


if __name__ == "__main__":
    main()
