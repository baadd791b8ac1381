"""Perf probe: step timing + device trace for the flagship bench config.

Usage (on the chip):

    python tools/perf_probe.py [--trace /tmp/hvd_trace] [--steps 10]

Runs the same ~1B llama training step as bench.py, prints per-step wall
time and MFU, and (with --trace) captures a Perfetto trace through
``hvd.start_profiler`` for kernel-level attribution (view in
ui.perfetto.dev or tensorboard).
"""

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--trace", default=None)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--remat", default="full", choices=["full", "dots"])
    p.add_argument("--loss-chunk", type=int, default=0)
    p.add_argument("--remat-skip", type=int, default=0)
    p.add_argument("--pipelined", action="store_true",
                   help="time like bench.py: sync once at the end")
    p.add_argument("--opt", default="adamw", choices=["adamw", "adamw_lp"])
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import training
    from horovod_tpu.models import llama
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
    from horovod_tpu.runtime import use_compile_cache
    from bench import detect_peak

    use_compile_cache()

    cfg = llama.LlamaConfig(
        vocab_size=32768, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=8, d_ff=8192, max_seq_len=args.seq, remat=True,
        remat_policy=args.remat, loss_chunk=args.loss_chunk,
        remat_skip_layers=args.remat_skip)
    if jax.devices()[0].platform == "cpu":  # smoke-test shrink
        cfg = dataclasses.replace(
            cfg, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
            d_ff=1024, vocab_size=4096)
    n_chips = jax.local_device_count()
    pmesh = ParallelMesh(MeshConfig(dp=n_chips, pp=1, sp=1, tp=1))
    if args.opt == "adamw_lp":
        from horovod_tpu.optim.precision import adamw_lp
        opt = adamw_lp(3e-4)
    else:
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    ts = training.make_llama_train_step(cfg, pmesh, optimizer=opt)
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    sh = training.make_data_sharding(ts)
    toks = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch * n_chips, args.seq)),
        jnp.int32), sh)
    tgts = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch * n_chips, args.seq)),
        jnp.int32), sh)

    t0 = time.perf_counter()
    params, opt_state, loss = ts.step_fn(params, opt_state, toks, tgts)
    float(loss)
    print(f"compile+first step: {time.perf_counter() - t0:.1f}s")

    if args.trace:
        import horovod_tpu as hvd
        hvd.init()
        hvd.start_profiler(args.trace)

    if args.pipelined:
        # bench.py-style timing: one device sync at the end, so host
        # dispatch overlaps device steps (the deployment-realistic number)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, opt_state, loss = ts.step_fn(params, opt_state, toks,
                                                 tgts)
        float(loss)
        times = np.full(args.steps,
                        (time.perf_counter() - t0) / args.steps)
    else:
        times = []
        for _ in range(args.steps):
            t0 = time.perf_counter()
            params, opt_state, loss = ts.step_fn(params, opt_state, toks,
                                                 tgts)
            float(loss)
            times.append(time.perf_counter() - t0)

    if args.trace:
        import horovod_tpu as hvd
        hvd.stop_profiler()
        print(f"trace written to {args.trace}")

    times = np.asarray(times)
    tok = args.batch * n_chips * args.seq
    tps = tok / times.mean() / n_chips
    mfu = tps * 6 * llama.count_params(cfg) / (detect_peak() * 1e12)
    if args.pipelined:
        # amortized timing has no per-step distribution to report
        print(f"step: mean {times.mean()*1e3:.1f} ms (pipelined)")
    else:
        print(f"step: mean {times.mean()*1e3:.1f} ms  "
              f"min {times.min()*1e3:.1f} ms  "
              f"p90 {np.percentile(times, 90)*1e3:.1f} ms")
    print(f"{tps:.0f} tokens/s/chip  MFU {mfu:.3f}  "
          f"vs_baseline {mfu/0.40:.3f}")


if __name__ == "__main__":
    main()
