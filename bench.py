"""Benchmark: flagship Llama train-step throughput on the available chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The measured quantity is training tokens/sec/chip for a ~1B-param
Llama-family model (bf16 compute, fp32 master params, adamw with bf16
momentum, fused DP train step — BASELINE config 4 scaled to a single
chip).  ``vs_baseline`` reports measured MFU divided by 0.40 — i.e.
≥1.0 means the compiled step meets or beats the ~40% model-FLOPs
utilization a well-tuned reference (NCCL/GPU) training stack achieves
on its own headline benchmarks.

The hot attention op runs the framework's own Pallas flash-attention
kernel (horovod_tpu/ops/flash_attention.py); the trunk weights are
bulk-cast to bf16 once per step (models/llama.py _layer_stack).
"""

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# Peak bf16 TFLOP/s per chip, keyed by ``jax.devices()[0].device_kind``.
# "TPU v5 lite": Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
# A kind that is not listed is an error, never priced as another chip.
PEAK_TFLOPS = {"TPU v5 lite": 197.0}
# the CPU shrinks below (on_cpu) exist for tests/test_llama.py only and
# go with them when ROADMAP S1 rebuilds this file
_CPU_TEST_PEAK_TFLOPS = 0.5


def detect_peak() -> float:
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return _CPU_TEST_PEAK_TFLOPS
    if dev.device_kind not in PEAK_TFLOPS:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind "
            f"{dev.device_kind!r} (platform {dev.platform!r}); add it to "
            f"bench.PEAK_TFLOPS with its source")
    return PEAK_TFLOPS[dev.device_kind]


def _env_batch(default: int) -> int:
    """HOROVOD_BENCH_BATCH: per-chip batch override for the secondary
    bench modes (the reference's synthetic benchmarks expose
    --batch-size the same way; TPU conv/attention utilization is
    batch-hungry, so the A/B sweep tunes this per mode)."""
    import os
    return int(os.environ.get("HOROVOD_BENCH_BATCH", default))


def _env_scan(default: int = 1) -> int:
    """HOROVOD_BENCH_SCAN: drive K train steps per device dispatch via
    ``lax.scan`` (1 = eager loop), amortizing host dispatch over K
    steps.  Per-mode defaults come from July 2026 builder runs on an
    earlier installation; not measured on the current code."""
    import os
    return max(1, int(os.environ.get("HOROVOD_BENCH_SCAN", str(default))))


def _scan_wrap(step_fn, n_carry: int, loss_idx: int, k: int):
    """jit(scan) of ``k`` chained ``step_fn`` calls.

    ``step_fn``'s first ``n_carry`` outputs feed its first ``n_carry``
    inputs on the next step; remaining inputs repeat (synthetic data).
    Returns a callable with step_fn's signature yielding
    (carry..., last_loss)."""
    from jax import lax

    def multi(carry, *inputs):
        def body(c, _):
            out = step_fn(*c, *inputs)
            return tuple(out[:n_carry]), out[loss_idx]
        c2, losses = lax.scan(body, carry, None, length=k)
        return c2, losses[-1]

    jitted = jax.jit(multi, donate_argnums=(0,))

    def run(*args):
        carry, rest = tuple(args[:n_carry]), args[n_carry:]
        c2, loss = jitted(carry, *rest)
        return (*c2, loss)

    return run


def bench_bert():
    """Secondary bench entry (HOROVOD_BENCH_MODEL=bert): BERT fine-tune
    throughput, BASELINE config 3.  The default metric stays llama_1b so
    round-over-round numbers remain comparable."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import bert

    import os

    on_cpu = jax.devices()[0].platform == "cpu"
    cfg = bert.bert_base(num_labels=4) if not on_cpu else bert.tiny()
    # batch 512: the knee in July 2026 builder runs on an earlier
    # installation; not measured on the current code
    batch, seq, steps = (_env_batch(512), 128, 40) if not on_cpu \
        else (4, 32, 3)
    cfg = dataclasses.replace(
        cfg, max_seq_len=max(cfg.max_seq_len, seq),
        # remat is REQUIRED at the b512 default: the b512 (and b256)
        # remat-off variants ran out of HBM in the July 2026 builder
        # runs; only at b<=128 did activations fit without recompute
        remat=os.environ.get("HOROVOD_BENCH_REMAT", "1") != "0")
    n_chips = jax.local_device_count()
    mesh = jax.make_mesh((n_chips,), ("dp",))
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adamw(5e-5)
    opt_state = jax.jit(opt.init)(params)
    step = bert.make_dp_finetune_step(cfg, mesh, "dp", opt,
                                      reduce_grads=True)
    k = _env_scan(10) if not on_cpu else _env_scan()
    if k > 1:
        step = _scan_wrap(step, 2, 2, k)

    rng = np.random.RandomState(0)
    sh = NamedSharding(mesh, P("dp"))
    toks = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch * n_chips, seq)), jnp.int32),
        sh)
    labs = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.num_labels, (batch * n_chips,)), jnp.int32), sh)
    params, opt_state, loss = step(params, opt_state, toks, labs)
    float(loss)
    outer = max(1, steps // k)
    t0 = time.perf_counter()
    for _ in range(outer):
        params, opt_state, loss = step(params, opt_state, toks, labs)
    float(loss)
    dt = time.perf_counter() - t0
    seq_per_sec_chip = batch * outer * k / dt
    mfu = (seq_per_sec_chip * seq * 6 * bert.count_params(cfg)
           ) / (detect_peak() * 1e12)
    print(json.dumps({
        "metric": "bert_base_finetune_sequences_per_sec_per_chip",
        "value": round(seq_per_sec_chip, 1),
        "unit": "sequences/s/chip",
        "vs_baseline": round(mfu / 0.40, 3),
    }))


def bench_resnet():
    """ResNet-50 synthetic entry (HOROVOD_BENCH_MODEL=resnet): img/sec
    through the data-parallel classifier step — BASELINE config 2, the
    reference's pytorch_synthetic_benchmark.py.  The default metric
    stays llama_1b so round-over-round numbers remain comparable."""
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu import training
    from horovod_tpu.models import resnet
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

    on_cpu = jax.devices()[0].platform == "cpu"
    # batch 256: the knee in July 2026 builder runs on an earlier
    # installation; not measured on the current code
    variant, img, batch, steps = (50, 224, _env_batch(256), 40) \
        if not on_cpu else (18, 32, 2, 3)
    cfg = resnet.ResNetConfig(variant=variant, dtype=jnp.bfloat16)
    n_chips = jax.local_device_count()
    pmesh = ParallelMesh(MeshConfig(dp=n_chips))
    ts = training.make_classifier_train_step(
        lambda p, s, x, train, axis_name: resnet.forward(
            p, s, x, cfg, train=train, axis_name=axis_name),
        lambda rng: resnet.init(cfg, rng), pmesh,
        optimizer=optax.sgd(0.01, momentum=0.9), sync_bn=True)
    params, state, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    B = batch * n_chips
    sh = NamedSharding(ts.mesh, ts.data_spec)
    x = jax.device_put(jnp.asarray(rng.rand(B, img, img, 3), jnp.float32),
                       sh)
    y = jax.device_put(jnp.asarray(rng.randint(0, 1000, B), jnp.int32), sh)

    k = _env_scan(10) if not on_cpu else _env_scan()
    sf = ts.step_fn if k == 1 else _scan_wrap(ts.step_fn, 3, 3, k)
    out = sf(params, state, opt_state, x, y)
    params, state, opt_state, loss = out[0], out[1], out[2], out[3]
    float(loss)
    outer = max(1, steps // k)
    t0 = time.perf_counter()
    for _ in range(outer):
        out = sf(params, state, opt_state, x, y)
        params, state, opt_state, loss = out[0], out[1], out[2], out[3]
    float(loss)
    dt = time.perf_counter() - t0
    img_per_sec_chip = batch * outer * k / dt
    # ResNet-50 fwd ~4.09 GFLOPs/image at 224^2; train ~3x fwd
    flops_per_img = 3 * 4.089e9 if variant == 50 else 0.0
    mfu = (img_per_sec_chip * flops_per_img) / (detect_peak() * 1e12)
    print(json.dumps({
        "metric": "resnet50_train_img_per_sec_per_chip",
        "value": round(img_per_sec_chip, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(mfu / 0.40, 3),
    }))


def bench_longctx():
    """Long-context entry (HOROVOD_BENCH_MODEL=longctx): training
    throughput at 8k sequence length, where the flash-attention kernel's
    O(T·blk) memory is what makes the step fit at all.  The default
    metric stays llama_1b for round-over-round comparability."""
    import os

    import optax

    from horovod_tpu import training
    from horovod_tpu.models import llama
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

    on_cpu = jax.devices()[0].platform == "cpu"
    cfg = llama.LlamaConfig(
        vocab_size=32768, d_model=1024, n_layers=8, n_heads=16,
        n_kv_heads=8, d_ff=4096, max_seq_len=8192,
        # ~100M params: 8k-seq activations fit HBM without remat.
        # remat off and batch 2 come from July 2026 builder runs on an
        # earlier installation (b4 did not fit); not measured on the
        # current code
        remat=os.environ.get("HOROVOD_BENCH_REMAT", "0") != "0",
        remat_policy="full", loss_chunk=1024)
    batch, seq, steps = _env_batch(2), 8192, 10
    if on_cpu:
        cfg = dataclasses.replace(cfg, d_model=256, n_layers=2, n_heads=8,
                                  head_dim=0, n_kv_heads=4, d_ff=1024,
                                  vocab_size=4096, max_seq_len=1024)
        batch, seq, steps = 1, 1024, 2

    n_chips = jax.local_device_count()
    pmesh = ParallelMesh(MeshConfig(dp=n_chips, pp=1, sp=1, tp=1))
    opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    ts = training.make_llama_train_step(cfg, pmesh, optimizer=opt)
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    sh = training.make_data_sharding(ts)
    toks = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch * n_chips, seq)), jnp.int32),
        sh)
    k = _env_scan()
    sf = ts.step_fn if k == 1 else _scan_wrap(ts.step_fn, 2, 2, k)
    params, opt_state, loss = sf(params, opt_state, toks, toks)
    float(loss)
    outer = max(1, steps // k)
    t0 = time.perf_counter()
    for _ in range(outer):
        params, opt_state, loss = sf(params, opt_state, toks, toks)
    float(loss)
    dt = time.perf_counter() - t0
    tok_per_sec_chip = batch * seq * outer * k / dt
    # attention FLOPs matter at 8k: 6·N·params + 12·L·H·Dh·T per token
    n_params = llama.count_params(cfg)
    attn_flops_tok = 12 * cfg.n_layers * cfg.d_model * seq / 2
    mfu = (tok_per_sec_chip * (6 * n_params + attn_flops_tok)
           ) / (detect_peak() * 1e12)
    print(json.dumps({
        "metric": "llama_longctx8k_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 3),
    }))


def bench_llama8b_dp():
    """BASELINE config 4 — the north star (HOROVOD_BENCH_MODEL=
    llama8b_dp): Llama-3-8B data-parallel on a v5p-128 slice.

    On >= 64 chips: measure tokens/s/chip on the full dp x tp4 mesh AND
    on a tp4-only reference slice (the smallest mesh that fits 8B);
    scaling efficiency = full-mesh per-chip throughput / reference
    per-chip throughput, and ``vs_baseline`` = efficiency / 0.90
    (BASELINE: >= 90% linear scaling).

    Below 64 chips: AOT-rehearse the
    REAL 8B step over 64 virtual devices in a subprocess
    (tools/rehearse_8b.py — trace + StableHLO + per-chip HBM from the
    actual shardings) and emit the same metric shape with value 0.0 and
    the rehearsal payload attached.

    HOROVOD_BENCH_8B_FORCE=1 runs the measurement path on a scaled-down
    config over the devices present, validating the efficiency math
    end-to-end (tests use this on the 8-device CPU mesh).
    """
    import os
    import subprocess

    from horovod_tpu import training
    from horovod_tpu.models import llama
    from horovod_tpu.optim.precision import adamw_lp
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

    metric = "llama3_8b_dp_scaling_efficiency"
    force = os.environ.get("HOROVOD_BENCH_8B_FORCE") == "1"
    n = jax.device_count()
    on_cpu = jax.devices()[0].platform == "cpu"
    if not force and (on_cpu or n < 64):
        here = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # rehearse sets its own 64-dev flag
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "tools",
                                              "rehearse_8b.py")],
                capture_output=True, text=True, timeout=1800, env=env)
            line = next((ln for ln in proc.stdout.splitlines()
                         if ln.startswith("{")), None)
            if line is None:
                # crashed before emitting: carry the diagnosis in the
                # metric line — it may be all that gets collected
                reh = {"ok": False,
                       "error": f"no JSON line, rc={proc.returncode}",
                       "stderr_tail": proc.stderr[-400:]}
            else:
                reh = json.loads(line)
        except (subprocess.TimeoutExpired, ValueError) as exc:
            # the metric line must come out even when the rehearsal
            # hangs or emits garbage
            reh = {"ok": False, "error": str(exc)[:200]}
        print(json.dumps({
            "metric": metric, "value": 0.0, "unit": "fraction",
            "vs_baseline": 0.0,
            "rehearsal": reh,
            "note": (f"{n} device(s) available; the measurement needs a "
                     f">=64-chip v5p slice — AOT rehearsal "
                     + ("ok" if reh.get("ok") else "FAILED")),
        }))
        return

    if force and n < 64:
        tp = 2 if n >= 4 else 1
        cfg = dataclasses.replace(
            llama.LlamaConfig(
                vocab_size=4096, d_model=256, n_layers=2, n_heads=8,
                n_kv_heads=4, d_ff=1024, max_seq_len=256, remat=True),
            vocab_parallel=tp > 1)
        seq, steps = 256, 3
    else:
        # the SAME configuration the rehearsal lowers (shared helper —
        # rehearsal and measurement cannot drift apart)
        tp = llama.LLAMA8B_TP
        cfg = llama.llama3_8b_train_cfg(seq=4096)
        seq, steps = 4096, 10
    dp_full = n // tp

    def measure(dp: int) -> float:
        """tokens/s/chip of the real train step on a dp x tp submesh."""
        pmesh = ParallelMesh(MeshConfig(dp=dp, tp=tp),
                             devices=jax.devices()[:dp * tp])
        ts = training.make_llama_train_step(
            cfg, pmesh, optimizer=adamw_lp(3e-4), zero1=dp > 1)
        params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        sh = training.make_data_sharding(ts)
        toks = jax.device_put(jnp.asarray(
            rng.randint(0, cfg.vocab_size, (dp, seq)), jnp.int32), sh)
        params, opt_state, loss = ts.step_fn(params, opt_state, toks,
                                             toks)
        float(loss)  # sync
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = ts.step_fn(params, opt_state,
                                                 toks, toks)
        float(loss)
        return dp * seq * steps / (time.perf_counter() - t0) / (dp * tp)

    ref = measure(1)           # tp-only slice: the smallest 8B fit
    full = measure(dp_full)    # the whole slice
    eff = full / ref
    print(json.dumps({
        "metric": metric, "value": round(eff, 3), "unit": "fraction",
        "vs_baseline": round(eff / 0.90, 3),
        "tokens_per_sec_per_chip": round(full, 1),
        "reference_tokens_per_sec_per_chip": round(ref, 1),
        "mesh": {"dp": dp_full, "tp": tp, "chips": dp_full * tp},
        "seq": seq,
    }))


def main():
    import os

    import optax

    from horovod_tpu import training
    from horovod_tpu.models import llama
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

    if os.environ.get("HOROVOD_BENCH_MODEL") == "bert":
        return bench_bert()
    if os.environ.get("HOROVOD_BENCH_MODEL") == "longctx":
        return bench_longctx()
    if os.environ.get("HOROVOD_BENCH_MODEL") == "resnet":
        return bench_resnet()
    if os.environ.get("HOROVOD_BENCH_MODEL") == "llama8b_dp":
        return bench_llama8b_dp()

    on_cpu = jax.devices()[0].platform == "cpu"
    # ~1B-param geometry: head_dim 128 keeps the flash kernel's score
    # matmuls at the MXU's full 128-wide contraction; full remat trades
    # recompute FLOPs for the HBM that lets adamw master state fit.
    # Env knobs (defaults from July 2026 builder runs on an earlier
    # installation; not measured on the current code):
    #   HOROVOD_BENCH_LOSS_CHUNK  chunked vocab cross-entropy
    #   HOROVOD_BENCH_REMAT_SKIP  last-k layers un-remat'd
    #   HOROVOD_BENCH_OPT=lp      bf16-moment AdamW
    cfg = llama.LlamaConfig(
        vocab_size=32768, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=8, d_ff=8192, max_seq_len=1024, remat=True,
        # "dots" saves matmul outputs and recomputes only elementwise in
        # the backward pass (A/B knob; "full" = max memory savings)
        remat_policy=os.environ.get("HOROVOD_BENCH_REMAT_POLICY", "full"),
        loss_chunk=int(os.environ.get("HOROVOD_BENCH_LOSS_CHUNK", "2048")),
        remat_skip_layers=int(
            os.environ.get("HOROVOD_BENCH_REMAT_SKIP", "2")))
    batch, seq, steps = _env_batch(8), 1024, 30
    if on_cpu:  # keep the CPU fallback path quick
        cfg = dataclasses.replace(
            cfg, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
            d_ff=1024, vocab_size=4096,
            # keep the default chunking active at the smaller seq len
            loss_chunk=min(cfg.loss_chunk, 128) if cfg.loss_chunk else 0)
        batch, seq, steps = 2, 256, 3

    n_chips = jax.local_device_count()
    pmesh = ParallelMesh(MeshConfig(dp=n_chips, pp=1, sp=1, tp=1))
    if os.environ.get("HOROVOD_BENCH_OPT", "lp") == "lp":
        from horovod_tpu.optim.precision import adamw_lp
        opt = adamw_lp(3e-4)
    else:
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    ts = training.make_llama_train_step(cfg, pmesh, optimizer=opt)
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    sh = training.make_data_sharding(ts)
    toks = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch * n_chips, seq)), jnp.int32),
        sh)
    tgts = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch * n_chips, seq)), jnp.int32),
        sh)

    # warmup (compile).  scan10: from July 2026 builder runs on an
    # earlier installation; not measured on the current code.
    k = _env_scan(10) if not on_cpu else _env_scan()
    sf = ts.step_fn if k == 1 else _scan_wrap(ts.step_fn, 2, 2, k)
    params, opt_state, loss = sf(params, opt_state, toks, tgts)
    float(loss)  # device→host transfer is the reliable sync point

    outer = max(1, steps // k)
    t0 = time.perf_counter()
    for _ in range(outer):
        params, opt_state, loss = sf(params, opt_state, toks, tgts)
    float(loss)
    dt = time.perf_counter() - t0

    tokens_per_step = batch * n_chips * seq
    tok_per_sec = tokens_per_step * outer * k / dt
    tok_per_sec_chip = tok_per_sec / n_chips

    # model FLOPs: ~6 * params * tokens per train step (fwd+bwd)
    n_params = llama.count_params(cfg)
    flops_per_tok = 6 * n_params
    mfu = (tok_per_sec_chip * flops_per_tok) / (detect_peak() * 1e12)

    print(json.dumps({
        "metric": "llama_1b_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 3),
    }))


if __name__ == "__main__":
    from horovod_tpu.runtime import use_compile_cache
    use_compile_cache()
    sys.exit(main())
