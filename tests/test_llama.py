"""Flagship-model tests: every parallel config must match the single-device
baseline (the SPMD analog of the reference's rank-dependent-input tests —
if any collective were wrong, losses would diverge)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import training
from horovod_tpu.models import llama
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

CFG = llama.tiny(vocab=64, seq=32)
_RNG = np.random.RandomState(0)
TOKS = jnp.asarray(_RNG.randint(0, 64, (8, 32)), jnp.int32)
TGTS = jnp.asarray(_RNG.randint(0, 64, (8, 32)), jnp.int32)


import optax


def run_steps(cfg, mc, steps=3, sgd=False, **kw):
    pmesh = ParallelMesh(mc)
    if sgd:
        # scale-sensitive optimizer: catches axis-size gradient-scaling
        # bugs that adamw (invariant to uniform grad scaling) masks
        kw = dict(kw, optimizer=optax.sgd(0.05))
    ts = training.make_llama_train_step(cfg, pmesh, **kw)
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    sh = training.make_data_sharding(ts)
    toks = jax.device_put(TOKS, sh)
    tgts = jax.device_put(TGTS, sh)
    losses = []
    for _ in range(steps):
        params, opt_state, loss = ts.step_fn(params, opt_state, toks, tgts)
        losses.append(float(loss))
    return losses


@pytest.fixture(scope="module")
def baseline(hvd):
    return run_steps(CFG, MeshConfig(1, 1, 1, 1))


@pytest.fixture(scope="module")
def baseline_sgd(hvd):
    return run_steps(CFG, MeshConfig(1, 1, 1, 1), sgd=True)


def test_baseline_loss_decreases(baseline):
    assert baseline[-1] < baseline[0]


_CONFIGS = [
    ("dp8", MeshConfig(8, 1, 1, 1), {}),
    ("dp2_sp2_tp2", MeshConfig(2, 1, 2, 2), {}),
    ("pp2_sp2_tp2", MeshConfig(1, 2, 2, 2), {"n_microbatches": 4}),
    ("dp2_pp2_tp2", MeshConfig(2, 2, 1, 2), {"n_microbatches": 2}),
    ("ulysses_sp2", MeshConfig(2, 1, 2, 2), {"attn": "ulysses"}),
]


@pytest.mark.parametrize("name,mc,kw", _CONFIGS)
def test_parallel_config_matches_baseline(baseline, name, mc, kw):
    got = run_steps(CFG, mc, **kw)
    np.testing.assert_allclose(got, baseline, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("name,mc,kw", _CONFIGS)
def test_parallel_config_matches_baseline_sgd(baseline_sgd, name, mc, kw):
    """Regression: with check_vma=False, gradients came out ×tp·pp —
    invisible under adamw, caught immediately by SGD."""
    got = run_steps(CFG, mc, sgd=True, **kw)
    np.testing.assert_allclose(got, baseline_sgd, atol=1e-4, err_msg=name)


def test_moe_expert_parallel_tracks_baseline(hvd):
    cfg = dataclasses.replace(CFG, n_experts=4, expert_top_k=2,
                              capacity_factor=2.0)
    base = run_steps(cfg, MeshConfig(1, 1, 1, 1))
    assert base[-1] < base[0]
    ep = run_steps(cfg, MeshConfig(4, 1, 1, 2))
    # per-shard capacity dropping makes EP runs track (not bit-match) the
    # single-shard baseline — same property GShard documents
    np.testing.assert_allclose(ep, base, atol=5e-2)


def test_moe_dedicated_ep_axis_tracks_baseline(hvd):
    """MeshConfig.ep creates a real expert axis: batch shards over dp×ep,
    experts over ep; must track the single-shard baseline like aliased ep."""
    cfg = dataclasses.replace(CFG, n_experts=4, expert_top_k=2,
                              capacity_factor=2.0)
    base = run_steps(cfg, MeshConfig(1, 1, 1, 1))
    ded = run_steps(cfg, MeshConfig(dp=2, ep=2, tp=2))
    np.testing.assert_allclose(ded, base, atol=5e-2)


def test_moe_dedicated_ep_axis_sgd(hvd):
    """SGD variant catches gradient-scale bugs on the dedicated ep axis
    (dense grads must be scaled 1/(dp·sp·ep), not 1/(dp·sp))."""
    cfg = dataclasses.replace(CFG, n_experts=4, expert_top_k=2,
                              capacity_factor=2.0)
    base = run_steps(cfg, MeshConfig(1, 1, 1, 1), sgd=True)
    ded = run_steps(cfg, MeshConfig(dp=2, ep=2, tp=1), sgd=True)
    np.testing.assert_allclose(ded, base, atol=5e-2)


def test_moe_pipeline_tracks_baseline(hvd):
    """MoE composed with pipeline parallelism: the aux load-balance loss
    rides the per-stage accumulator (live ticks only), so pp training
    tracks the single-shard baseline like every other MoE layout."""
    cfg = dataclasses.replace(CFG, n_experts=4, expert_top_k=2,
                              capacity_factor=2.0)
    base = run_steps(cfg, MeshConfig(1, 1, 1, 1))
    got = run_steps(cfg, MeshConfig(2, 2, 1, 1), n_microbatches=2)
    np.testing.assert_allclose(got, base, atol=5e-2)


def test_moe_pipeline_aux_invariant_to_microbatch_count(hvd):
    """Regression: the aux term must be a MEAN over microbatches — with
    a deliberately large coefficient, the first-step loss may not scale
    with n_microbatches."""
    cfg = dataclasses.replace(CFG, n_experts=4, expert_top_k=2,
                              capacity_factor=2.0, aux_loss_coef=1.0)
    l2 = run_steps(cfg, MeshConfig(1, 2, 1, 1), steps=1,
                   n_microbatches=2)[0]
    l4 = run_steps(cfg, MeshConfig(1, 2, 1, 1), steps=1,
                   n_microbatches=4)[0]
    assert abs(l2 - l4) < 0.15, (l2, l4)


def test_param_count_llama3_8b():
    # Llama-3-8B geometry with tied embedding head: 7.50B params
    # (the official 8.03B unties the 0.53B lm_head)
    n = llama.count_params(llama.llama3_8b())
    assert abs(n - 7.50e9) / 7.5e9 < 0.01


def test_forward_shapes(hvd):
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    par = llama.ParallelSpec()
    logits, aux = llama.forward(
        params, TOKS[:2], CFG, par)
    assert logits.shape == (2, 32, 64)
    assert float(aux) == 0.0


def test_chunked_xent_matches_one_shot(hvd):
    """loss_chunk computes the identical loss AND gradients as the
    one-shot log-softmax path (it is the same math, tiled)."""
    cfg_c = dataclasses.replace(CFG, loss_chunk=8)
    par = llama.ParallelSpec()
    params = llama.init_params(CFG, jax.random.PRNGKey(1))

    def loss_with(cfg):
        return lambda p: llama.loss_fn(p, TOKS, TGTS, cfg, par)

    l0, g0 = jax.value_and_grad(loss_with(CFG))(params)
    l1, g1 = jax.value_and_grad(loss_with(cfg_c))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), g0, g1)


def test_chunked_xent_training_matches_baseline(baseline_sgd, hvd):
    """Full parallel train steps with the chunked loss track the one-shot
    baseline trajectory (chunking is invisible to the optimizer)."""
    cfg_c = dataclasses.replace(CFG, loss_chunk=16)
    got = run_steps(cfg_c, MeshConfig(2, 1, 2, 2), sgd=True)
    np.testing.assert_allclose(got, baseline_sgd, atol=1e-4)


@pytest.mark.parametrize("name,mc,kw", [
    ("zero_dp8", MeshConfig(8, 1, 1, 1), {}),
    ("zero_dp2_sp2_tp2", MeshConfig(2, 1, 2, 2), {}),
    ("zero_dp2_pp2_tp2", MeshConfig(2, 2, 1, 2), {"n_microbatches": 2}),
])
def test_zero1_matches_baseline(baseline_sgd, name, mc, kw):
    """ZeRO-1 sharded optimizer state must train identically: slicing the
    moments over dp is storage layout, not math."""
    got = run_steps(CFG, mc, sgd=True, zero1=True, **kw)
    np.testing.assert_allclose(got, baseline_sgd, atol=1e-4, err_msg=name)


def test_zero1_shards_opt_state_over_dp(hvd):
    """The moment buffers' global sharding actually includes dp."""
    pmesh = ParallelMesh(MeshConfig(8, 1, 1, 1))
    ts = training.make_llama_train_step(
        CFG, pmesh, optimizer=optax.adamw(1e-3), zero1=True)
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    mu_embed = opt_state[0].mu["embed"]
    spec = mu_embed.sharding.spec
    assert "dp" in tuple(spec), spec
    # 1/8th of the full buffer per device
    assert (mu_embed.addressable_shards[0].data.size
            == mu_embed.size // 8)


def test_remat_skip_layers_matches_baseline(baseline_sgd, hvd):
    """Partial remat changes memory layout only, never the math."""
    cfg_s = dataclasses.replace(CFG, remat=True, remat_skip_layers=1)
    got = run_steps(cfg_s, MeshConfig(2, 1, 2, 2), sgd=True)
    np.testing.assert_allclose(got, baseline_sgd, atol=1e-4)


def test_fsdp_matches_baseline(baseline_sgd, hvd):
    """FSDP (ZeRO-3 class) training is the same global math as replicated
    DP — sharding params/grads/opt-state over dp is layout, not numerics."""
    pmesh = ParallelMesh(MeshConfig(8, 1, 1, 1))
    ts = training.make_llama_fsdp_step(CFG, pmesh,
                                       optimizer=optax.sgd(0.05))
    params, opt_state = ts.init_fn(jax.random.PRNGKey(0))
    sh = training.make_data_sharding(ts)
    toks, tgts = jax.device_put(TOKS, sh), jax.device_put(TGTS, sh)
    losses = []
    for _ in range(3):
        params, opt_state, loss = ts.step_fn(params, opt_state, toks, tgts)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, baseline_sgd, atol=1e-4)
    # params are genuinely sharded: largest leaves hold 1/8 per device
    wq = params["layers"]["wq"]
    assert "dp" in tuple(wq.sharding.spec), wq.sharding.spec
    assert wq.addressable_shards[0].data.size == wq.size // 8


def test_fsdp_rejects_model_parallel_meshes(hvd):
    with pytest.raises(ValueError, match="does not compose with tp>1"):
        training.make_llama_fsdp_step(CFG, ParallelMesh(MeshConfig(2, 1, 1, 2)))


def test_zero1_with_aliased_ep_moe(hvd):
    """Regression: expert weights already sharded over dp (ep aliased)
    must not gain a second dp entry in their optimizer-state spec."""
    cfg = dataclasses.replace(CFG, n_experts=4, expert_top_k=2,
                              capacity_factor=2.0)
    base = run_steps(cfg, MeshConfig(1, 1, 1, 1), sgd=True)
    got = run_steps(cfg, MeshConfig(4, 1, 1, 2), sgd=True, zero1=True)
    np.testing.assert_allclose(got, base, atol=5e-2)


def test_fsdp_specs_shard_embed_axis0(hvd):
    """Non-stacked leaves may shard axis 0: with d_model indivisible by
    dp, embed [V, D] must still shard over V instead of replicating."""
    import jax as _jax
    shapes = {
        "embed": _jax.ShapeDtypeStruct((64, 6), jnp.float32),
        "layers": {"wq": _jax.ShapeDtypeStruct((2, 6, 8), jnp.float32)},
    }
    specs = training.fsdp_param_specs(shapes, dp=8)
    from jax.sharding import PartitionSpec as P
    assert specs["embed"] == P("dp", None), specs["embed"]
    # stacked leaf: axis 0 excluded (scan dim), shards the 8-wide axis
    assert specs["layers"]["wq"] == P(None, None, "dp")


@pytest.mark.parametrize("name,mc,kw", [
    ("vp_dp2_tp2", MeshConfig(2, 1, 1, 2), {}),
    ("vp_dp2_sp2_tp2", MeshConfig(2, 1, 2, 2), {}),
    ("vp_pp2_tp2", MeshConfig(1, 2, 1, 2), {"n_microbatches": 4}),
])
def test_vocab_parallel_matches_baseline(baseline_sgd, name, mc, kw):
    """Vocab-parallel embedding + cross-shard lse loss must train
    identically to the replicated-vocab baseline (megatron
    VocabParallelEmbedding semantics)."""
    cfg_vp = dataclasses.replace(CFG, vocab_parallel=True)
    got = run_steps(cfg_vp, mc, sgd=True, **kw)
    np.testing.assert_allclose(got, baseline_sgd, atol=1e-4, err_msg=name)


def test_vocab_parallel_shards_embedding(hvd):
    cfg_vp = dataclasses.replace(CFG, vocab_parallel=True)
    pmesh = ParallelMesh(MeshConfig(4, 1, 1, 2))
    ts = training.make_llama_train_step(cfg_vp, pmesh,
                                        optimizer=optax.sgd(0.05))
    params, _ = ts.init_fn(jax.random.PRNGKey(0))
    emb = params["embed"]
    assert "tp" in tuple(emb.sharding.spec), emb.sharding.spec
    assert emb.addressable_shards[0].data.shape[0] == emb.shape[0] // 2
    # forward still returns full logits (API contract)
    par = llama.ParallelSpec(tp_axis=None)
    logits, _ = llama.forward(jax.device_get(params), TOKS[:2], CFG, par)
    assert logits.shape == (2, 32, 64)


def test_vocab_parallel_with_loss_chunk_matches_baseline(baseline_sgd, hvd):
    """loss_chunk composes with vocab_parallel: sequence-chunked,
    vocab-sharded loss still trains identically."""
    cfg_vpc = dataclasses.replace(CFG, vocab_parallel=True, loss_chunk=16)
    got = run_steps(cfg_vpc, MeshConfig(2, 1, 1, 2), sgd=True)
    np.testing.assert_allclose(got, baseline_sgd, atol=1e-4)


@pytest.mark.parametrize("name,mc,kw", [
    ("accum2_dp2_tp2", MeshConfig(2, 1, 1, 2), {"grad_accum": 2}),
    ("accum4_dp2", MeshConfig(2, 1, 1, 1), {"grad_accum": 4}),
    ("accum2_zero1", MeshConfig(2, 1, 1, 2),
     {"grad_accum": 2, "zero1": True}),
])
def test_grad_accum_matches_baseline(baseline_sgd, name, mc, kw):
    """In-jit gradient accumulation (the jit-path backward_passes_per_step)
    sees the same global batch in k microbatches — averaged grads equal
    the full-batch gradient exactly."""
    got = run_steps(CFG, mc, sgd=True, **kw)
    np.testing.assert_allclose(got, baseline_sgd, atol=1e-4, err_msg=name)


def test_llama3_8b_aot_rehearsal_subprocess():
    """VERDICT r3 #7 (BASELINE config 4 readiness): the REAL llama3_8b
    training step — dp16 x tp4 (v5p-128's 64 chips), vocab-parallel
    embedding/head, ZeRO-1, bf16-moment AdamW, chunked loss, full remat
    — AOT-lowers end to end over 64 virtual CPU devices, and the
    per-chip HBM of the sharded train state fits v5p with headroom
    (docs/estimators.md records the table this asserts)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # the script sets its own count
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "rehearse_8b.py")],
        capture_output=True, text=True, timeout=900, env=env)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, (out.stdout[-2000:], out.stderr[-2000:])
    r = json.loads(lines[-1])
    assert r["ok"] and r["mesh"]["chips"] == 64
    assert r["n_params"] > 7e9          # the real 8B geometry traced
    assert r["stablehlo_bytes"] > 10_000
    # sharded state + transients leave ample activation headroom on v5p
    assert r["per_chip_gib"]["steady_plus_peak"] < 0.5 * r["v5p_hbm_gib"]
    # ISSUE 14: the composed spec-aware plane's train-state bytes DROP
    # by the data-axis degree (exact planner tile accounting — the
    # same layout tools/bench_fsdp.py gates against live state): bf16
    # moments tile 1/dp within each tp shard, padding included
    spec = r["specaware"]
    assert spec["moments_bf16_zero_tiles_bytes"] < \
        spec["moments_bf16_replicated_dp_bytes"]
    assert spec["state_drop_vs_replicated"] >= 0.9 * r["mesh"]["dp"]
    # and the composed number sits beside (not above) the GSPMD zero1
    # reading it must eventually replace
    assert spec["per_chip_gib"] <= \
        r["per_chip_gib"]["opt_moments_bf16_zero1"] * 1.25 + 0.01
    # ISSUE 20: serving-side KV residency beside the training state —
    # paged bytes are exact block arithmetic: strictly under dense at
    # short true lengths, and exactly dense at bucket-max (16 divides
    # both the bucket and max_new, so there is no rounding slack)
    skv = r["serving_kv"]
    assert skv["dense_gib"] > 1.0       # bucket-max is real HBM at 8B
    fr = skv["paged_fraction_at_len"]
    assert fr["1024"] < 0.5
    assert fr[str(r["seq"])] == 1.0
    assert all(fr[a] <= fr[b] for a, b in zip(sorted(fr, key=int),
                                              sorted(fr, key=int)[1:]))


def test_bench_llama8b_dp_mode_forced_measurement():
    """VERDICT r4 #8: HOROVOD_BENCH_MODEL=llama8b_dp as a bench mode.
    The forced path runs the REAL measurement code (full mesh vs
    tp-reference submesh, efficiency ratio) scaled down on the 8-device
    CPU mesh — validating the math that will run on a real v5p slice."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "HOROVOD_BENCH_MODEL": "llama8b_dp",
        "HOROVOD_BENCH_8B_FORCE": "1",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": repo,
    })
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=900, env=env)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, (out.stdout[-2000:], out.stderr[-2000:])
    r = json.loads(lines[-1])
    assert r["metric"] == "llama3_8b_dp_scaling_efficiency"
    assert r["unit"] == "fraction"
    assert r["mesh"] == {"dp": 4, "tp": 2, "chips": 8}
    # time-sliced virtual devices make the ratio meaningless as a
    # number; the contract is that both submeshes measured and the
    # ratio + vs_baseline shape came out
    assert r["value"] > 0 and r["tokens_per_sec_per_chip"] > 0
    assert r["reference_tokens_per_sec_per_chip"] > 0
    assert abs(r["vs_baseline"] - round(r["value"] / 0.90, 3)) < 0.01


def test_bench_llama8b_dp_mode_rehearsal_fallback():
    """Without 64 chips the mode AOT-rehearses the real 8B step in a
    subprocess and emits the metric shape with the rehearsal payload."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "HOROVOD_BENCH_MODEL": "llama8b_dp",
        "JAX_PLATFORMS": "cpu",
        # small seq: the asserted contract (chips==64, n_params>7e9) is
        # seq-independent, and the full-seq trace is already covered by
        # test_llama3_8b_aot_rehearsal_subprocess; this also keeps the
        # outer timeout comfortably above bench.py's inner 1800s budget
        "REHEARSE_SEQ": "512",
        "PYTHONPATH": repo,
    })
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=1800, env=env)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert lines, (out.stdout[-2000:], out.stderr[-2000:])
    r = json.loads(lines[-1])
    assert r["metric"] == "llama3_8b_dp_scaling_efficiency"
    assert r["value"] == 0.0 and "needs a >=64-chip" in r["note"]
    assert r["rehearsal"]["ok"] is True
    assert r["rehearsal"]["mesh"]["chips"] == 64
    assert r["rehearsal"]["n_params"] > 7e9
