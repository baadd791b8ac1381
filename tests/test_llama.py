"""Flagship-model tests: every parallel config must match the single-device
baseline (the SPMD analog of the reference's rank-dependent-input tests —
if any collective were wrong, losses would diverge)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import (eqns as _eqns, kernels_by_place as _kernels_by_place,
                      make_qkv)
from horovod_tpu import training
from horovod_tpu.models import llama
from horovod_tpu.ops import flash_attention as fa
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


# ------------------------------------------ the residuals under remat
# The forward rules name ``out`` and ``lse`` (fa.OUT_NAME, fa.LSE_NAME) and
# models/llama.py::remat_policy saves what is named: a remat'd layer
# stack (the decoder trunk's, BERT's encoder) runs the forward kernel once
# a layer, not twice.

# attention path -> (heads, kv heads, head_dim, tokens, the mask of a [T]
# sequence); ``rows``: the masked kernels on the caller's layout
REMAT_PATHS = {
    "masked-numpy-mask": (2, 1, 64, 256, lambda T: fa.window_ranges(T, 100)),
    "masked-traced-mask": (2, 1, 64, 256, lambda T: jnp.asarray(
        fa.window_ranges(T, 100))),
    "masked-rows": (2, 1, 128, 256, lambda T: fa.window_ranges(T, 100)),
    "packed": (2, 2, 64, 128, lambda T: None),
}


def _remat_stack(policy, path):
    """A two-layer remat'd stack at toy widths, bf16, through the
    interpreted kernels: ``(grads, (h, layers, mask), cfg, pos)`` with
    ``grads`` a FRESH function of the three (jax caches a traced function
    by identity, and two policies must not share a trace)."""
    H, Hkv, Dh, T, make_mask = REMAT_PATHS[path]
    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=128, n_layers=2, n_heads=H, n_kv_heads=Hkv,
        head_dim=Dh, d_ff=128, max_seq_len=T, dtype=jnp.bfloat16,
        remat=True, remat_policy=policy)
    par = llama.ParallelSpec()
    layers = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]
    B = 2
    h = jnp.asarray(np.random.RandomState(1).randn(B, T, cfg.d_model),
                    cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = make_mask(T)

    def loss(h, layers, mask):
        out, _ = llama._layer_stack(h, layers, cfg, par, pos, mask)
        return (out.astype(jnp.float32) ** 2).mean()

    grads = jax.value_and_grad(loss, argnums=(0, 1))
    if isinstance(mask, jax.Array):           # traced: a jit argument
        return jax.jit(grads), (h, layers, mask), cfg, pos
    return (jax.jit(lambda h, layers, _: grads(h, layers, mask)),
            (h, layers, 0), cfg, pos)


@pytest.mark.parametrize("path", sorted(REMAT_PATHS))
@pytest.mark.parametrize("policy", ["full", "dots"])
def test_remat_policies_save_the_named_residuals(policy, path, monkeypatch,
                                                 pallas_interpret):
    """Under either policy of ``_layer_stack`` the forward kernel stands in
    the forward scan alone and the backward scan's remat body holds only
    the backward kernels; the layer's saved residuals are the two named
    values, ``out`` in the compute dtype and ``lse`` in float32, as the
    kernel wrote them (``out`` as ``[B, T, H*D]`` on the packed path and
    on the masked one at ``head_dim`` 128, what the ``wo`` product reads;
    ``[B, H, T, D]`` at 64); loss and every gradient equal, to the bit, those
    of the same stack under the policy without the names (the program
    before the kernels' residuals were kept), which runs the forward
    kernel twice."""
    from jax._src.ad_checkpoint import saved_residuals
    grads, args, cfg, pos = _remat_stack(policy, path)
    backward = ({"hvd_flash_bwd"} if path == "packed"
                else {"hvd_flash_dq", "hvd_flash_dkv"})
    placed = _kernels_by_place(grads, *args)
    forward = [p for p, name in placed if name == "hvd_flash_fwd"]
    assert len(forward) == 1 and "scan" in forward[0]
    assert "remat2" not in forward[0]
    assert {name for p, name in placed if "remat2" in p} == backward
    assert len(placed) == 1 + len(backward)

    # one layer under the stack's own policy: what it keeps beside its
    # arguments are the two named values (jax puts a reduce_precision of
    # the value's own precision, a no-op, on a residual that the forward
    # also uses: ``out`` shows under that, ``lse`` under its name)
    h, layers, _ = args
    H, T = cfg.n_heads, h.shape[1]
    one = jax.tree_util.tree_map(lambda w: w[0].astype(cfg.dtype), layers)
    static = None if path == "packed" else fa.window_ranges(T, 100)
    layer = jax.checkpoint(
        lambda h, lp: llama.block(h, lp, cfg, llama.ParallelSpec(), pos,
                                  static)[0],
        policy=llama.remat_policy(policy))
    kept = [(aval, why) for aval, why in saved_residuals(layer, h, one)
            if "flash_attention.py" in why]
    D = cfg.head_dim
    out_shape = ((2, T, H * D) if path in ("packed", "masked-rows")
                 else (2, H, T, D))
    assert sorted((a.shape, str(a.dtype)) for a, _ in kept)[-1] == (
        out_shape, "bfloat16")
    assert [(a.shape, str(a.dtype)) for a, why in kept
            if f"named '{fa.LSE_NAME}'" in why] == [
        ((2, H, 1, T), "float32")]
    if policy == "full":
        assert len(kept) == 2
    traced = jax.make_jaxpr(jax.grad(lambda h: layer(h, one).astype(
        jnp.float32).sum()))(h)
    assert {eqn.params["name"] for _, eqn in _eqns(traced.jaxpr)
            if eqn.primitive.name == "name"} == {fa.OUT_NAME, fa.LSE_NAME}

    # the same stack, the names saved by no policy: the forward kernel a
    # second time in the remat body, and the same numbers to the bit
    got = grads(*args)
    cp = jax.checkpoint_policies
    monkeypatch.setattr(cp, "save_only_these_names",
                        lambda *names: cp.nothing_saveable)
    before, args, _, _ = _remat_stack(policy, path)
    placed = _kernels_by_place(before, *args)
    assert sorted(name for p, name in placed if "remat2" in p) == sorted(
        backward | {"hvd_flash_fwd"})
    want = before(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert np.isfinite(float(got[0])) and float(got[0]) > 0


def test_a_name_that_no_policy_saves_changes_nothing(monkeypatch,
                                                     pallas_interpret):
    """A caller that remats around the op and saves dots only (BERT's
    stack, before it took ``remat_policy("dots")``): with the names in
    the forward rule and no policy that keeps them, the remat body still
    holds the forward kernel and every number is what it was without the
    names."""
    q, k, v = make_qkv(4, 128, 12, 12, 64, jnp.bfloat16)    # BERT's block

    def grads():
        layer = jax.checkpoint(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=False),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.jit(jax.grad(lambda q, k, v: (layer(q, k, v).astype(
            jnp.float32) ** 2).sum(), (0, 1, 2)))

    named = grads()
    placed = _kernels_by_place(named, q, k, v)
    assert sorted(name for p, name in placed) == [
        "hvd_flash_bwd", "hvd_flash_fwd", "hvd_flash_fwd"]
    got = named(q, k, v)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    plain = grads()
    assert _kernels_by_place(plain, q, k, v) == placed
    for a, b in zip(got, plain(q, k, v)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_layer_stack_counts_its_remat_policy(monkeypatch):
    """``hvd_remat_policy_total{policy, saves}``: one count per traced
    remat'd stack, none where ``remat`` is off."""
    from horovod_tpu import metrics
    monkeypatch.setattr(metrics, "ACTIVE", True)

    def counts():
        family = metrics.registry().to_dict().get(
            "hvd_remat_policy_total", {})
        return {(s["labels"]["policy"], s["labels"]["saves"]): s["value"]
                for s in family.get("series", [])}

    def trace(**kw):
        cfg = llama.LlamaConfig(vocab_size=64, d_model=64, n_layers=2,
                                n_heads=2, n_kv_heads=2, d_ff=64,
                                max_seq_len=16, **kw)
        layers = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]
        h = jax.ShapeDtypeStruct((1, 16, 64), cfg.dtype)
        pos = jnp.arange(16)[None]
        jax.make_jaxpr(lambda h, ls: llama._layer_stack(
            h, ls, cfg, llama.ParallelSpec(), pos))(h, layers)

    before = counts()
    trace(remat_policy="full")
    trace(remat_policy="dots")
    trace(remat_policy="dots")
    trace(remat=False)
    after = counts()
    assert {key: after[key] - before.get(key, 0) for key in after} == {
        ("full", "flash"): 1, ("dots", "flash"): 2}
    with pytest.raises(ValueError, match="remat_policy"):
        trace(remat_policy="some")


# --------------------------------------------- a kind's rotary table
# (models/hybrid.py hands one to its ``attention`` and ``swa`` layers; the
# trunk of identical layers keeps ``_rope``, whose lowered text is pinned in
# tests/benchmark/test_bench_phi4flash.py)

MELLUM_YARN = llama.RopeTable(
    theta=500000, rope_type="yarn", factor=16,
    original_max_position_embeddings=8192, beta_fast=32, beta_slow=1,
    attention_factor=1.2772588722239782)


def _yarn_in_float64(t, dim):
    """``transformers``' ``_compute_yarn_parameters``, written out."""
    import math
    j = np.arange(dim // 2, dtype=np.float64)
    e = float(t.theta) ** (-2 * j / dim)
    c = lambda r: (dim * math.log(t.original_max_position_embeddings
                                  / (r * 2 * math.pi)) / (2 * math.log(t.theta)))
    low, high = max(math.floor(c(t.beta_fast)), 0), min(math.ceil(c(t.beta_slow)), dim - 1)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    return low, high, e / t.factor * ramp + e * (1 - ramp)


def test_yarn_table_at_the_published_numbers_by_hand():
    """Mellum 2's ``full_attention`` table: ``c(32) = 18.08``, ``c(1) =
    34.98``, so ``low`` 18 and ``high`` 35; five frequencies worked out by
    hand; ``attention_factor`` = 0.1 ln 16 + 1, given or not."""
    low, high, want = _yarn_in_float64(MELLUM_YARN, 128)
    assert (low, high) == (18, 35)
    inv_freq, factor = llama.rope_inv_freq(MELLUM_YARN, 128)
    assert inv_freq.dtype == np.float32 and inv_freq.shape == (64,)
    by_hand = {0: 1.0, 18: 0.024955408670558694,        # fast: as they were
               26: 0.004839421345719893 * (8 / 17 / 16 + 9 / 17),
               35: 0.0007644969883171747 / 16,          # slow: interpolated
               63: 2.455140791131609e-06 / 16}
    for j, value in by_hand.items():
        assert inv_freq[j] == np.float32(value), j
    np.testing.assert_array_equal(inv_freq, want.astype(np.float32))
    assert factor == 1.2772588722239782
    assert llama.rope_inv_freq(dataclasses.replace(
        MELLUM_YARN, attention_factor=0.0), 128)[1] == pytest.approx(
            1.2772588722239782, rel=1e-15)
    plain, one = llama.rope_inv_freq(llama.RopeTable(theta=500000), 128)
    assert one == 1.0
    np.testing.assert_array_equal(
        plain, (500000.0 ** (-np.arange(64) / 64)).astype(np.float32))
    # the ramp rises between whole dimensions (``truncate``'s default):
    # 18 keeps its frequency, 19 is a seventeenth of the way
    assert inv_freq[19] == np.float32(
        500000.0 ** (-19 / 64) * (1 / 17 / 16 + 16 / 17))


@pytest.mark.parametrize("table", [llama.RopeTable(theta=10000.0), llama.RopeTable(
    theta=10000.0, rope_type="yarn", factor=4, original_max_position_embeddings=16,
    beta_fast=2, beta_slow=0.125)], ids=["default", "yarn"])
def test_rotary_table_and_rotation_against_float64(table):
    """cos and sin of ``p * inv_freq[j]``, both times the table's factor,
    and the rotate-half products, against the equations in float64; the
    plain table's rotation is ``_rope``'s."""
    T, dim = 64, 32
    cos, sin = llama.rope_table(table, dim, T)
    if table.rope_type == "yarn":
        low, high, inv = _yarn_in_float64(table, dim)
        assert (low, high) == (0, 6) and 0 < ((np.arange(16) - low) / 6)[3] < 1
        factor = 0.1 * np.log(4.0) + 1.0
    else:
        inv, factor = 10000.0 ** (-np.arange(16) / 16), 1.0
    angles = np.arange(T)[:, None] * inv.astype(np.float32).astype(np.float64)
    np.testing.assert_allclose(cos, np.cos(angles) * factor, atol=2e-5)
    np.testing.assert_allclose(sin, np.sin(angles) * factor, atol=2e-5)
    x = np.asarray(jax.random.normal(jax.random.key(0), (2, T, 3, dim)), np.float64)
    x1, x2 = x[..., :16], x[..., 16:]
    c, s = (a[None, :, None] * factor for a in (np.cos(angles), np.sin(angles)))
    want = np.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    got = llama.rotate(jnp.asarray(x, jnp.float32), cos, sin)
    np.testing.assert_allclose(got, want, atol=1e-4)
    if table.rope_type == "default":
        pos = jnp.broadcast_to(jnp.arange(T)[None], (2, T))
        np.testing.assert_allclose(
            got, llama._rope(jnp.asarray(x, jnp.float32), pos, 10000.0), atol=1e-5)
    else:   # both halves of every pair carry the factor: a score its square
        plain = llama.rotate(jnp.asarray(x, jnp.float32), cos / factor, sin / factor)
        np.testing.assert_allclose(got, plain * factor, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kw,error", [
    (dict(rope_type="linear"), "rope_type"), (dict(rope_type="yarn"), "yarn table"),
    (dict(rope_type="yarn", factor=0.5, original_max_position_embeddings=8), "yarn table")])
def test_rotary_tables_that_cannot_be_are_refused(kw, error):
    with pytest.raises(ValueError, match=error):
        llama.RopeTable(**kw)
    with pytest.raises(ValueError, match="layer_kinds"):
        llama.LlamaConfig(rope_tables=(("attention", llama.RopeTable()),))
