"""The ``phi4-mini-flash`` configuration's files: the program against the
plain reference at the toy sizes, the kinds of the published layers and
of the cut, the chip's slice of the vocabulary against the uncut tied
head, the key ranges the program hands the kernels against the
reference's masks, and the counts that ``mfu_pct`` and the two rooflines
rest on."""

import hashlib
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import check, registry

CONFIG = bench_tree.BENCH / "configs" / "phi4-mini-flash"
CELL = "phi4-mini-flash.s8192-b1.dp1"
KEPT = [0, 1, 16, 17, 18, 19]


def _load(name):
    return registry.load_module(str(CONFIG / f"{name}.py"))


def _cfg(toy=True):
    cfg = bench_tree.load(CONFIG / "config.json")
    if toy:
        cfg.update(cfg["toy"])
        cfg["dtype"]["compute"] = "float32"
    return cfg


def test_toy_model_through_the_train_step_follows_the_reference(hvd):
    """Loss, first gradient leaf by leaf and update of three steps through
    ``make_llama_train_step``, on seeded weights, float32."""
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    program = adapter.build(cfg, ref, jax.devices()[:1], 4)
    key = jax.random.key(11)
    batches = [ref.make_samples(cfg, jax.random.fold_in(key, j), 4)
               for j in range(check.STEPS)]
    state, losses, grad = program.init(key), [], None
    for batch in batches:
        state, loss = program.step(state, program.place(batch))
        if grad is None:
            grad = check.leaf_norms(program.first_gradient(state))
        losses.append(loss)
    w0 = ref.make_weights(cfg, key)
    got = jax.device_get({
        "losses": losses, "grad_norms": grad,
        "update_norms": check.leaf_norms(
            {k: v - w0[k] for k, v in program.params(state).items()})})
    want = check.Reference(ref, cfg, jax.devices()[:1]).run(key, batches)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == set(
        ref.weight_shapes(cfg))
    for name, (value, where) in check.compare(got, want).items():
        assert value < 5e-5, (name, value, where)
    # the program counted the parameters the reference made
    from horovod_tpu.models import llama
    assert llama.count_params(adapter.program_config(cfg)) == sum(
        int(np.prod(s)) for s in ref.weight_shapes(cfg).values())


def test_kinds_of_the_published_layers_and_of_the_cut():
    from horovod_tpu.models import hybrid
    cfg, ref, adapter, flops = _cfg(False), _load("reference"), _load("adapter"), _load("flops")
    kinds = ref.layer_kinds(32)
    assert list(hybrid.published_kinds(32)) == kinds
    pairs = [tuple(kinds[i:i + 2]) for i in range(0, 32, 2)]
    assert pairs == ([("mamba", "window")] * 8 + [("mamba", "full")]
                     + [("gmu", "cross")] * 7)
    assert cfg["kept_layers"] == KEPT
    cut = ["mamba", "window", "mamba", "full", "gmu", "cross"]
    assert ref.kept_kinds(cfg) == list(adapter.kept_kinds(cfg)) == cut \
        == flops._kinds(cfg)
    lcfg = adapter.program_config(cfg)
    assert lcfg.layer_ids == tuple(KEPT) and lcfg.head_dim == 64
    # one run a layer; layer 16 emits the memory, layer 17 its k and v
    assert [(r[0], r[2], r[3]) for r in hybrid._runs(lcfg)] == [
        ("mamba", [0], False), ("window", [1], False), ("mamba", [16], True),
        ("full", [17], True), ("gmu", [18], False), ("cross", [19], False)]


@pytest.mark.parametrize("i", KEPT + [31])
def test_lambda_init_by_published_index(i):
    from horovod_tpu.models import hybrid
    ref = _load("reference")
    want = 0.8 - 0.6 * math.exp(-0.3 * i)
    assert ref.lambda_init(i) == hybrid.lambda_init(i) == pytest.approx(want)
    assert 0.2 <= want < 0.8


def test_config_carries_the_published_widths():
    cfg, ref = _cfg(False), _load("reference")
    z = ref.sizes(cfg)
    assert (z["d"], z["f"], z["h"], z["hkv"], z["dh"]) == (2560, 10240, 40, 20, 64)
    assert (z["di"], z["n"], z["kc"], z["r"]) == (5120, 16, 4, 160)
    assert cfg["sliding_window"] == 512 and cfg["vocab_size"] * 8 == 200064
    shapes = ref.weight_shapes(cfg)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == 697_073_792 and abs(n * 16 / 1e9 - 11.15) < 0.01
    per_layer = [sum(int(np.prod(s)) for k, s in shapes.items()
                     if k.startswith(f"l{i}.")) for i in range(6)]
    assert [round(p / 1e6, 2) for p in per_layer] == [
        119.9, 98.31, 119.9, 98.31, 104.87, 91.76]


def test_the_eight_vocabulary_slices_side_by_side_are_the_uncut_tied_head():
    """Logits of each chip's slice of the tied embedding, side by side,
    are the uncut head's; the program's loss over a slice is the
    reference's over that slice."""
    from horovod_tpu.models import llama
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    shares, V = 8, cfg["vocab_size"]
    uncut = {**cfg, "vocab_size": shares * V}
    w = ref.make_weights(uncut, jax.random.key(5))
    tokens, targets = ref.make_samples(cfg, jax.random.key(6), 2)
    lcfg, par = adapter.program_config(cfg), llama.ParallelSpec()
    logits = []
    for s in range(shares):
        held = {**w, "embed": w["embed"][s * V:(s + 1) * V]}
        # every chip reads the same rows here: the first slice's
        params = adapter._to_program({**held, "embed": w["embed"][:V]}, cfg)
        h, _ = llama.hidden(params, tokens, lcfg, par)
        logits.append(h @ held["embed"].T)
        if s == 0:
            with jax.default_matmul_precision("highest"):
                want = ref.loss(cfg, held, (tokens, targets))
            got = llama.loss_fn(params, tokens, targets, lcfg, par)
            np.testing.assert_allclose(got, want, rtol=2e-5)
    with jax.default_matmul_precision("highest"):
        full = ref.hidden(cfg, {**w, "embed": w["embed"][:V]}, tokens) \
            @ w["embed"].T
    np.testing.assert_allclose(jnp.concatenate(logits, -1), full,
                               atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("kind", ["window", "full", "cross"])
@pytest.mark.parametrize("T", [64, 1024])
def test_adapters_key_ranges_are_the_references_masks(kind, T):
    from horovod_tpu.ops import flash_attention as fa
    ref, adapter = _load("reference"), _load("adapter")
    cfg = _cfg(toy=T == 64)
    live = ref.attention_mask(kind, T, cfg["sliding_window"])
    assert (fa.dense_mask(adapter.key_ranges(cfg, kind, T), T) == live).all()
    flops = _load("flops")
    assert flops.live_pairs({**cfg, "seq_len": T}, kind) == live.sum()
    if kind == "window":
        assert live.sum(1).max() == min(cfg["sliding_window"], T)
    else:
        assert (live == np.tri(T, dtype=bool)).all()


def test_weights_are_made_as_the_configuration_says():
    cfg, ref = _cfg(), _load("reference")
    w = ref.make_weights(cfg, jax.random.key(2))
    assert set(w) == set(ref.weight_shapes(cfg))
    assert all(w[k].shape == s for k, s in ref.weight_shapes(cfg).items())
    n = cfg["mamba_d_state"]
    np.testing.assert_allclose(np.exp(w["l0.A_log"]),
                               np.tile(np.arange(1, n + 1), (128, 1)), rtol=1e-6)
    assert (np.asarray(w["l2.D"]) == 1).all()
    step = np.asarray(jax.nn.softplus(w["l0.dt_bias"]))
    assert cfg["dt_min"] * 0.999 <= step.min() and step.max() <= cfg["dt_max"] * 1.001
    assert np.abs(w["l0.conv_w"]).max() <= cfg["mamba_d_conv"] ** -0.5
    assert np.abs(w["l2.dt_proj"]).max() <= cfg["mamba_dt_rank"] ** -0.5
    assert abs(float(w["l1.wqkv"].std()) - cfg["initializer_range"]) < 2e-3
    assert abs(float(w["embed"].std()) - cfg["initializer_range"]) < 2e-3
    for leaf in ("l0.out_proj", "l3.wo", "l4.w2"):
        assert abs(float(w[leaf].std()) - cfg["residual_out_range"]) < 3e-4
    assert abs(float(w["l5.lambda_q1"].std()) - cfg["lambda_range"]) < 0.04
    assert (np.asarray(w["l3.subln"]) == 1).all() and not np.asarray(w["l3.norm1_b"]).any()
    assert "l5.wq" in w and "l5.wqkv" not in w and set(
        k.split(".")[1] for k in w if k.startswith("l4.")) == set(ref.LEAVES["gmu"])
    other = ref.make_weights(cfg, jax.random.key(3))
    assert not np.allclose(w["l0.in_proj"], other["l0.in_proj"])


def test_samples_are_full_rows_with_the_next_token_as_target():
    cfg, ref = _cfg(), _load("reference")
    tokens, targets = ref.make_samples(cfg, jax.random.key(3), 16)
    assert tokens.shape == targets.shape == (16, cfg["seq_len"])
    assert (tokens[:, 1:] == targets[:, :-1]).all()
    assert 0 <= tokens.min() and tokens.max() < cfg["vocab_size"]
    again = ref.make_samples(cfg, jax.random.key(3), 16)
    assert (again[0] == tokens).all() and (again[1] == targets).all()


def test_phi4flash_flops_from_shapes():
    cfg, flops = _cfg(toy=False), _load("flops")
    # the three sums of the issue's arithmetic
    assert abs(6 * flops.projection_macs(cfg) / 1e12 - 34.25) < 0.01
    assert flops.live_pairs(cfg, "full") == 33_558_528
    assert flops.live_pairs(cfg, "window") == 4_063_488
    assert flops.attention_macs(cfg) == 7680 * (2 * 33_558_528 + 4_063_488)
    assert abs(6 * flops.attention_macs(cfg) / 1e12 - 3.28) < 0.01
    assert flops.train_flops_per_sample(cfg) == 6 * flops.forward_macs(cfg)
    # the kernels' own: 13 Dh a softmax, two a head pair, 20 head pairs
    f, b = flops.mask_flash_kernel_cost(cfg, 1)
    assert f == 2 * (2 * 33_558_528 + 4_063_488) * 20 * 2 * 13 * 64 and b > 0
    ops, b = flops.ssm_scan_kernel_cost(cfg, 1)
    # 22 bytes a (position, channel), both directions, two scans a step
    assert 2 * 8192 * 5120 * 22 < b < 2 * 8192 * 5120 * 22 * 1.01 and ops > 0


@pytest.mark.parametrize("metric,kinds", [
    ("ssm_scan_roofline", [["hvd_ssm_scan_fwd (custom-call)", 0.3],
                           ["hvd_ssm_scan_bwd (custom-call)", 0.5]]),
    ("hybrid_flash_roofline", [["hvd_flash_fwd (custom-call)", 0.2],
                               ["hvd_flash_dq (custom-call)", 0.2],
                               ["hvd_flash_dkv (custom-call)", 0.4]])])
def test_new_readers_read_what_is_there_and_nothing_otherwise(metric, kinds):
    """On a program without the kernels' names (the parent commit under
    these files) a reader returns None and does not raise; with them it
    reads the share."""
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    cfg, said = _cfg(toy=False), []
    stretch = types.SimpleNamespace(stamps=[0.0] * 4, global_batch=1, chips=1)
    ctx = types.SimpleNamespace(
        config=cfg, flops=_load("flops"), traced=stretch, say=said.append,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(device_ops=[["fusion", 1.0]]))
    bare = types.SimpleNamespace(**{**vars(ctx), "trace": None,
                                    "flops": types.SimpleNamespace()})
    assert read(bare) is None and read(ctx) is None
    assert read(types.SimpleNamespace(**{**vars(ctx), "flops": bare.flops})) is None
    ctx.trace.device_ops += kinds
    value = read(ctx)
    flops = _load("flops")
    if metric == "ssm_scan_roofline":
        least = flops.ssm_scan_kernel_cost(cfg, 1)[1] / 819e9
        assert "bytes bound only" in said[-1]
    else:
        least = flops.mask_flash_kernel_cost(cfg, 1)[0] / 197e12
        assert any("compute-bound" in line for line in said)
    assert value == pytest.approx(100 * least * 4 / 0.8) and 0 < value < 100


# The lowered text (``lower().as_text()``) of two programs whose trunk is of
# identical layers, as it was at the commit before the kinds came (PR 32's
# tree, jax 0.9.0, 8 host devices): a later change to these programs
# changes the hash and states it here.
LOWERED = {"sdar_toy": "3e1d2e5bf85f60e6", "llama_tiny": "2e4ff1e9c7890188"}


def _lowered_text(which):
    import optax
    from horovod_tpu import training
    from horovod_tpu.models import llama
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
    if which == "llama_tiny":
        pm = ParallelMesh(MeshConfig(dp=2), devices=jax.devices()[:2])
        ts = training.make_llama_train_step(llama.tiny(), pm, optax.adamw(1e-3))
        tok = jnp.zeros((4, 128), jnp.int32)
        return ts.step_fn.lower(*ts.init_fn(jax.random.key(0)), tok, tok).as_text()
    cdir = bench_tree.BENCH / "configs" / "sdar-30b-a3b"
    cfg = bench_tree.load(cdir / "config.json")
    cfg.update(cfg["toy"])
    cfg["dtype"]["compute"] = "float32"
    ref = registry.load_module(str(cdir / "reference.py"))
    prog = registry.load_module(str(cdir / "adapter.py")).build(
        cfg, ref, jax.devices()[:1], 2)
    batch = prog.place(ref.make_samples(cfg, jax.random.key(1), 2))
    return prog._step.lower(*prog.init(jax.random.key(0)), batch).as_text()


@pytest.mark.parametrize("which", sorted(LOWERED))
def test_lowered_step_text_is_unchanged_by_the_trunks_new_kinds(which, hvd):
    text = _lowered_text(which)
    assert "hvd_ssm" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED[which]
