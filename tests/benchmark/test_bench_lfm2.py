"""The ``lfm2-8b-a1b`` configuration's files: the manifest's entries found
by ``harness/registry``, the cut and its count of parameters, every number
of the catalog's config, the adapter's round trip, the program through the
train step against the plain reference at the toy sizes (float32 and
bfloat16, a tolerance each), the reference's own convolution against a
loop, the four shares that add up to the uncut layer, what a program
without the kind says, the counts the rooflines rest on by hand, and the
four new readers on made-up traces."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import check, registry, scopes

CONFIG = bench_tree.BENCH / "configs" / "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b.s8192-b2.dp1"
MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
NEW_METRICS = ("conv_mixer_ms", "gated_conv_ms", "conv_hybrid_flash_roofline",
               "conv_moe_experts_roofline")
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
# float32 program against float32 reference (Solar's test's); bfloat16
# products and activations against it: the largest gap read here is the
# worst leaf's 1.5e-2 (a router's, whose gradient moves with every choice
# that bf16 flips; the median leaf's 1.2e-4, the loss's 1.3e-5)
TOLERANCE = {"float32": 5e-5, "bfloat16": 5e-2}


def _load(name):
    return registry.load_module(str(CONFIG / f"{name}.py"))


def _cfg(toy=True, compute="float32", **over):
    cfg = bench_tree.load(CONFIG / "config.json")
    if toy:
        cfg.update(cfg["toy"])
        cfg["dtype"]["compute"] = compute
    cfg.update(over)
    return cfg


def test_manifest_names_the_configuration_its_cell_and_its_metrics():
    """Appended after the accepted entries, in one piece, and found by the
    registry as added files; a configuration that comes later lies after
    these and changes nothing asserted here."""
    names = lambda section: [x["name"] for x in MANIFEST[section]]
    at = names("configs").index("lfm2-8b-a1b")
    entry = MANIFEST["configs"][at]
    assert names("configs")[at - 1] == "kanana-2-30b-a3b", "added at the end of its list"
    assert entry["reduced"] == REDUCED
    assert entry["source"] == _cfg(False)["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/lfm2-8b-a1b/config.json"
    where = names("workloads").index(CELL)
    cell = MANIFEST["workloads"][where]
    assert names("workloads")[where - 1] == "kanana-2-30b-a3b.s16384-b1.dp1"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b", "host-fed.s8192-b2", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    first = names("per_layer").index(NEW_METRICS[0])
    assert names("per_layer")[first - 1] == "setup_steps_s"
    assert names("per_layer")[first:first + 4] == list(NEW_METRICS)
    for m in MANIFEST["per_layer"][first:first + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
        assert m["source"] == "device_trace"
        assert (m["unit"], m["layer"]) == (("%", "Kernels") if "roofline" in m["name"]
                                           else ("ms", "Model"))
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        module = registry.load_module(str(
            bench_tree.BENCH / "layer_metrics" / f"{m['name']}.py"))
        assert (module.UNIT, module.LAYER, module.MOVES, module.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
    # of the accepted metrics' lists two took the new cell, after the cells
    # they had
    took = {m["name"]: m["workloads"] for m in MANIFEST["per_layer"][:first]
            if CELL in m.get("workloads", [])}
    assert list(took) == ["mlp_ms", "model_unscoped_pct"]
    for cells in took.values():
        assert cells.index(CELL) == cells.index("kanana-2-30b-a3b.s16384-b1.dp1") + 1
    four = [w["name"] for w in MANIFEST["workloads"][:where + 1] if w["chips"] == 4]
    assert four == ["resnet50-synth.b128.dp4"] and where + 1 == 11 and at + 1 == 9
    loaded = registry.load_cell(str(bench_tree.BENCH), MANIFEST, CELL)
    assert loaded.traffic == {**bench_tree.load(
        bench_tree.BENCH / "traffic" / "host-fed.s8192-b1.json"),
        "why": loaded.traffic["why"], "per_chip_batch": 2}
    assert "8,192" in loaded.traffic["why"] and loaded.config == _cfg(False)
    assert set(loaded.reference.LIMITS) <= {"loss_gap", "grad_norm_gap",
                                            "grad_norm_mid_gap", "update_norm_gap"}
    reported = {m["name"] for m in registry.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(NEW_METRICS) | {"mfu_pct", "busy_mfu_pct", "device_idle_pct",
                               "mlp_ms", "model_unscoped_pct"} <= reported
    assert not reported & {"mask_flash_roofline", "moe_experts_roofline",
                           "attention_ms", "rope_ms", "mla_attention_ms"}
    assert {m["name"] for m in registry.metrics_for(MANIFEST, "end_to_end", CELL)} == {
        "throughput", "step_ms.p95", "setup_s"}


def test_config_carries_the_published_widths_and_states_its_cut():
    cfg, ref = _cfg(False), _load("reference")
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["conv_L_cache"],
            cfg["num_experts_per_tok"], cfg["router_outputs"], cfg["norm_eps"],
            cfg["rope_theta"], cfg["router_eps"]) == (
        2048, 7168, 1792, 32, 8, 64, 3, 4, 32, 1e-5, 1000000, 1e-6)
    assert cfg["seq_len"] == 8192 and cfg["loss_chunk"] == 512
    assert cfg["reduced"] == REDUCED
    assert cfg["published"] == {"num_hidden_layers": 24, "num_dense_layers": 2,
                                "num_experts": 32, "vocab_size": 65536}
    assert cfg["vocab_size"] * 4 == 65536 and cfg["num_experts"] * 4 == 32
    assert cfg["deployment"] == (
        "4 chips share each layer: 8 of 32 experts and 1/4 of the tied table a "
        "chip, mixers and router whole on each; the 19 layers left out lie on "
        "further pipeline stages; 2 leading dense layers count once")
    assert len(cfg["deployment"]) <= 200    # the issue's words, 15 letters shorter
    assert cfg["kept_layers"] == [0, 2, 3, 4, 5] and len(cfg["layer_types"]) == 24
    assert ref.kept_types(cfg) == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [i for i, t in enumerate(cfg["layer_types"]) if t == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    said = " ".join(cfg["assumed"])
    for words in ("8.339 B", "8.473 B", "q_layernorm, k_layernorm",
                  "head_dim 64 = hidden_size / num_attention_heads",
                  "sum + 1e-6", "zeros from the seed", "no auxiliary loss",
                  "0.00289 = 0.02 / sqrt(2 x 24 layers)", "learning rate of 1e-6",
                  "what the 24 absent experts would add is left out",
                  "start the loss at about 2,040 nats"):
        assert words in said, words
    assert abs(cfg["residual_out_range"] - 0.02 / (2 * 24) ** 0.5) < 4e-6
    # the issue's arithmetic, a layer at a time
    shapes = ref.weight_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items() if keep(k))
    n = count(lambda k: True)
    assert n == 507_820_288 and abs(n * 16 / 1e9 - 8.13) < 0.01
    assert abs(n * 12 / 1e9 - 6.09) < 0.01
    assert [count(lambda k: k.startswith(f"l{i}.")) for i in range(5)] == [
        60_827_648, 98_635_936, 104_933_408, 104_933_408, 104_933_408]
    assert count(lambda k: k in ("l0.in_proj", "l0.conv_w", "l0.out_proj")) == 16_783_360
    assert count(lambda k: k in ("l1.wq", "l1.wk", "l1.wv", "l1.wo", "l1.q_norm",
                                 "l1.k_norm")) == 10_485_888
    assert count(lambda k: k in ("l0.w1", "l0.w2", "l0.w3")) == 44_040_192
    assert count(lambda k: k.startswith("l2.we_")) == 88_080_384 == 8 * 3 * 2048 * 1792
    assert count(lambda k: k in ("l2.router", "l2.router_bias")) == 65_536 + 32
    assert count(lambda k: k == "embed") == 33_554_432 and "head" not in shapes
    # the published 8.3 B by the same leaves: a tied head
    whole = (22 * (32 * 3 * 2048 * 1792 + 65_568) + 2 * 44_040_192 + 18 * 16_783_360
             + 6 * 10_485_888 + 24 * 4096 + 65536 * 2048 + 2048)
    assert abs(whole / 1e9 - 8.339) < 0.001
    from horovod_tpu.models import llama
    lcfg = _load("adapter").program_config(cfg)
    assert llama.count_params(lcfg) == n
    assert lcfg.layer_kinds == ("conv", "attention", "conv", "conv", "conv")
    assert lcfg.layer_ids == (0, 2, 3, 4, 5)
    assert (lcfg.trunk_norm, lcfg.head_dim, lcfg.ssm_conv, lcfg.qk_norm,
            lcfg.tie_embeddings, lcfg.router_score, lcfg.n_shared_experts,
            lcfg.n_experts, lcfg.experts_held, lcfg.experts_first,
            lcfg.expert_top_k, lcfg.first_dense_layers, lcfg.dense_d_ff,
            lcfg.routed_scaling_factor, lcfg.router_eps, lcfg.remat) == (
        "rmsnorm", 64, 3, True, True, "sigmoid", 0, 32, 8, 0, 4, 1, 7168, 1, 1e-6,
        True)
    assert lcfg.rope_tables == (("attention", llama.RopeTable(theta=1000000)),)


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` as published but the keys that are cut."""
    import json
    cfg = _cfg(False)
    period = ["full_attention", "conv", "conv", "conv"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168,
        "layer_types": ["conv", "conv"] + period * 4 + ["full_attention", "conv",
                                                       "conv", "full_attention",
                                                       "conv", "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 24,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
    assert len(published["layer_types"]) == 24
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(guide)]
        published = next(r for r in rows if r["name"] == "LFM2-8B-A1B")["config"]
    except OSError:
        pass                # no guide beside this checkout: the copy above
    for key, value in published.items():
        if key in REDUCED:
            assert cfg[key] != value and cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the toy changes sizes alone: the kinds, the taps and the cut stay
    assert not set(cfg["toy"]) & {"layer_types", "kept_layers", "conv_L_cache",
                                  "num_dense_layers", "router_eps"}


@pytest.fixture(scope="module")
def toy(hvd):
    """The toy program (float32) built once for the module, with the
    reference's three steps beside it."""
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    program = adapter.build(cfg, ref, jax.devices()[:1], 2)
    key = jax.random.key(11)
    batches = [ref.make_samples(cfg, jax.random.fold_in(key, j), 2)
               for j in range(check.STEPS)]
    want = check.Reference(ref, cfg, jax.devices()[:1]).run(key, batches)
    return types.SimpleNamespace(cfg=cfg, ref=ref, adapter=adapter, key=key,
                                 program=program, batches=batches, want=want)


def _three_steps(program, ref, cfg, key, batches):
    state, losses, grad = program.init(key), [], None
    for b in batches:
        state, loss = program.step(state, program.place(b))
        if grad is None:
            grad = check.leaf_norms(program.first_gradient(state))
        losses.append(loss)
    w0 = jax.jit(lambda k: ref.make_weights(cfg, k))(key)
    got = jax.device_get({
        "losses": losses, "grad_norms": grad,
        "update_norms": check.leaf_norms(
            {k: v - w0[k] for k, v in program.params(state).items()})})
    return got, state


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_toy_model_through_the_train_step_follows_the_reference(toy, compute):
    """Loss, first gradient leaf by leaf and update of three steps through
    ``make_llama_train_step`` on seeded weights, two rows a step: float32
    to rounding, bfloat16 products and activations within what that
    precision moves a toy's leaves by."""
    cfg, ref = toy.cfg, toy.ref
    program = toy.program
    if compute != "float32":
        cfg = _cfg(compute=compute)
        program = toy.adapter.build(cfg, ref, jax.devices()[:1], 2)
    got, state = _three_steps(program, ref, cfg, toy.key, toy.batches)
    assert set(got["grad_norms"]) == set(toy.want["grad_norms"]) == set(
        ref.weight_shapes(cfg))
    numbers = check.compare(got, toy.want)
    for name, (value, where) in numbers.items():
        assert value < TOLERANCE[compute], (name, value, where)
    if compute == "bfloat16":       # and bf16 is seen: not the same program
        assert numbers["grad_norm_gap"][0] > TOLERANCE["float32"]
        return
    # the selection bias has no gradient and stays where the seed put it
    assert got["grad_norms"]["l2.router_bias"] == 0 == got["update_norms"]["l2.router_bias"]
    text = program.compiled(state, program.place(toy.batches[0])).as_text()
    for scope in ("hvd_conv_mixer", "hvd_gated_conv", "hvd_attention", "hvd_rope",
                  "hvd_mlp", "hvd_moe_route", "hvd_moe_experts", "hvd_head",
                  "hvd_embed"):
        assert scope in text, scope
    assert "hvd_moe_shared" not in text and "hvd_window_attention" not in text
    # the routing statistics a step hands on: 4 routed layers of the 5
    _, _, _, stats = program._step(*state, program.place(toy.batches[0]))
    pairs, rows, fullest, layers = np.asarray(stats)
    assert layers == 4 and rows == pairs and 0 < fullest < pairs
    even = 4 * 2 * cfg["seq_len"] * cfg["num_experts_per_tok"] * 4 / 8
    assert 0.5 * even < pairs < 1.5 * even


def test_adapter_round_trip_is_exact_both_ways(toy):
    """The reference's leaves into the program's tree and back: ``wq``,
    ``wk``, ``wv`` the columns of ``wqkv``, ``w1`` and ``w3`` of the fused
    gate/up, every other leaf under its own name, bit for bit."""
    cfg, ref, adapter = toy.cfg, toy.ref, toy.adapter
    flat = jax.jit(lambda k: ref.make_weights(cfg, k))(toy.key)
    params = adapter._to_program(flat, cfg)
    assert set(params) == {"embed", "final_norm", "layers"}
    assert list(params["layers"]) == ["dense_conv", "attention", "conv"]
    assert params["layers"]["conv"]["in_proj"].shape == (3, 64, 192)
    wqkv = params["layers"]["attention"]["wqkv"][0]
    np.testing.assert_array_equal(wqkv[:, :64], flat["l1.wq"])
    np.testing.assert_array_equal(wqkv[:, 64:96], flat["l1.wk"])
    np.testing.assert_array_equal(wqkv[:, 96:], flat["l1.wv"])
    w1 = params["layers"]["dense_conv"]["w1"][0]
    np.testing.assert_array_equal(w1[:, :96], flat["l0.w1"])
    np.testing.assert_array_equal(w1[:, 96:], flat["l0.w3"])
    np.testing.assert_array_equal(params["layers"]["conv"]["conv_w"][2],
                                  flat["l4.conv_w"])
    back = adapter._to_flat(params, cfg)
    assert set(back) == set(flat)
    for name in flat:
        np.testing.assert_array_equal(back[name], flat[name], err_msg=name)
    state = toy.program.init(toy.key)
    for name, leaf in toy.program.params(state).items():
        np.testing.assert_array_equal(leaf, flat[name], err_msg=name)


def test_a_program_without_the_kind_says_so_at_once(monkeypatch):
    """The parent commit under these files: a ValueError from the
    configuration's kinds, before anything is built."""
    from horovod_tpu.models import hybrid
    adapter = _load("adapter")
    monkeypatch.setattr(hybrid, "KINDS", tuple(k for k in hybrid.KINDS
                                               if k != "conv"))
    with pytest.raises(ValueError, match=r"has no \['conv'\]"):
        adapter.program_config(_cfg())
    with pytest.raises(ValueError, match=r"has no \['conv'\]"):
        adapter.build(_cfg(), _load("reference"), jax.devices()[:1], 1)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="maps the published lfm2_moe keys"):
        adapter.program_config(_cfg(conv_bias=True))


def test_weights_and_samples_are_made_as_the_configuration_says():
    cfg, ref = _cfg(), _load("reference")
    w = ref.make_weights(cfg, jax.random.key(2))
    assert set(w) == set(ref.weight_shapes(cfg))
    assert all(w[k].shape == s for k, s in ref.weight_shapes(cfg).items())
    for leaf in ("l0.norm1_w", "l2.norm2_w", "l1.q_norm", "l1.k_norm", "final_norm_w"):
        assert (np.asarray(w[leaf]) == 1).all()
    assert (np.asarray(w["l3.router_bias"]) == 0).all()
    assert not [k for k in w if "conv_b" in k or k == "head"]
    for leaf in ("l0.in_proj", "l1.wq", "l0.w3", "embed", "l2.we_up"):
        assert abs(float(w[leaf].std()) - cfg["initializer_range"]) < 2e-3, leaf
    assert abs(float(w["l2.conv_w"].std()) - cfg["initializer_range"]) < 6e-3
    for leaf in ("l0.out_proj", "l1.wo", "l0.w2", "l3.we_down"):
        assert abs(float(w[leaf].std()) - cfg["residual_out_range"]) < 4e-4, leaf
    tokens, targets = ref.make_samples(cfg, jax.random.key(3), 16)
    assert tokens.shape == targets.shape == (16, cfg["seq_len"])
    assert (tokens[:, 1:] == targets[:, :-1]).all() and tokens.max() < cfg["vocab_size"]


def test_the_references_convolution_is_the_loop_and_leaks_nowhere():
    """``reference.short_conv`` (``lax.conv_general_dilated``, nothing of
    the program) against the sum written out, and its mixer: row 1 or a
    later position perturbed, row 0 and the earlier positions stay."""
    cfg, ref = _cfg(), _load("reference")
    r = jax.random.split(jax.random.key(4), 5)
    g = jax.random.normal(r[0], (2, 16, 8))
    w = jax.random.normal(r[1], (3, 8))
    want = np.zeros((2, 16, 8))
    for t in range(16):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += np.asarray(w[j], np.float64) * np.asarray(
                    g[:, t - 2 + j], np.float64)
    np.testing.assert_allclose(ref.short_conv(g, w), want, atol=1e-5)
    d = cfg["hidden_size"]
    lw = {"in_proj": jax.random.normal(r[2], (d, 3 * d)) * d ** -0.5,
          "conv_w": jax.random.normal(r[3], (3, d)),
          "out_proj": jnp.eye(d)}
    u = jax.random.normal(r[4], (2, 32, d))
    mix = jax.jit(lambda u: ref.conv_mixer(u, lw, cfg))
    base, bump = np.asarray(mix(u)), jax.random.normal(r[0], (2, 32, d))
    np.testing.assert_array_equal(np.asarray(mix(u.at[1].add(bump[1])))[0], base[0])
    later = np.asarray(mix(u.at[:, 12:].add(bump[:, 12:])))
    np.testing.assert_array_equal(later[:, :12], base[:, :12])
    assert np.abs(later[:, 12] - base[:, 12]).max() > 0.1


# ------------------------------------------------- the shares add up

def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """A routed convolution layer on 4 chips: each the program's layer told
    which 8 of the 32 experts it holds, the mixer whole on each and counted
    once: the four routed parts (experts 0-7, 8-15, 16-23, 24-31) on top of
    the mixer's output sum to the uncut reference's layer, each is the
    reference's part of that share, and every routed pair is on one chip."""
    from horovod_tpu.models import hybrid
    ref, adapter = _load("reference"), _load("adapter")
    uncut = _cfg(router_outputs=32, num_experts=32, num_experts_per_tok=4)
    d, f = uncut["hidden_size"], uncut["moe_intermediate_size"]
    r = jax.random.split(jax.random.key(7), 9)
    lw = {"norm1_w": 1 + 0.1 * jax.random.normal(r[0], (d,)),
          "norm2_w": 1 + 0.1 * jax.random.normal(r[1], (d,)),
          "in_proj": jax.random.normal(r[2], (d, 3 * d)) * d ** -0.5,
          "conv_w": jax.random.normal(r[3], (3, d)) * 0.5,
          "out_proj": jax.random.normal(r[4], (d, d)) * d ** -0.5,
          "router": jax.random.normal(r[5], (d, 32)),
          "router_bias": jnp.zeros((32,)),
          "we_gate": jax.random.normal(r[6], (32, d, f)) * d ** -0.5,
          "we_up": jax.random.normal(r[7], (32, d, f)) * d ** -0.5,
          "we_down": jax.random.normal(r[8], (32, f, d)) * f ** -0.5}
    x = jax.random.normal(jax.random.key(8), (2, 32, d))
    with jax.default_matmul_precision("highest"):
        whole = ref._layer(lw, x, "conv", False, uncut, lambda a: a)
        mixed = x + ref.conv_mixer(ref.rms_norm(x, lw["norm1_w"], uncut["norm_eps"]),
                                   lw, uncut)
        total, pairs = mixed, 0
        for chip in range(4):
            share_cfg = {**uncut, "num_experts": 8, "experts_first": 8 * chip}
            lcfg = adapter.program_config(share_cfg)
            assert (lcfg.experts_held, lcfg.experts_first) == (8, 8 * chip)
            share = {**lw, **{n: lw[n][8 * chip:8 * chip + 8]
                              for n in ("we_gate", "we_up", "we_down")}}
            y, _, stats = hybrid._layer("conv", False, lcfg)(x, share, 0.0, None)
            part = ref._layer(share, x, "conv", False, share_cfg, lambda a: a)
            scale = float(jnp.abs(whole).max())
            assert float(jnp.abs(y - part).max()) < 2e-5 * scale
            total, pairs = total + (y - mixed), pairs + float(stats[0])
    assert pairs == 2 * 32 * 4                  # every pair on some chip, once
    assert float(jnp.abs(total - whole).max()) < 2e-5 * scale
    assert float(jnp.abs(whole - mixed).max()) > 0.1 * scale   # the experts count


# ----------------------------------------------------- flops by hand

def test_lfm2_flops_from_shapes():
    cfg, flops = _cfg(toy=False), _load("flops")
    T = 8192
    assert flops.conv_params(cfg) == 4 * 2048 * 2048 == 16_777_216
    assert flops.attention_params(cfg) == 10_485_760
    assert flops.dense_params(cfg) == 44_040_192
    assert flops.expert_params(cfg) == 11_010_048
    assert flops.routed_layers(cfg) == 4
    assert flops.expected_pairs(cfg) == 8192.0          # a row; 16,384 a step of two
    assert flops.live_pairs(cfg) == T * (T + 1) // 2 == 33_558_528
    assert flops.attention_macs(cfg) == 2 * 33_558_528 * 2048
    per_position = (4 * 16_777_216 + 10_485_760 + 44_040_192 + 4 * 65_536
                    + 2048 * 16384)
    assert flops.projection_macs(cfg) == T * per_position
    assert flops.expert_macs(cfg) == 4 * 8192 * 11_010_048
    assert flops.train_flops_per_sample(cfg) == 6 * flops.forward_macs(cfg)
    assert abs(flops.forward_macs(cfg) / T / 1e6 - 216.27) < 0.01     # a token
    assert abs(2 * flops.train_flops_per_sample(cfg) / 1e12 - 21.26) < 0.01  # a step
    # the kernels' least: the attention layer 9.8 ms a step of two rows,
    # the grouped products 6.7 ms a layer at 16,384 pairs, both compute-bound
    f, b = flops.mask_flash_kernel_cost(cfg, 2)
    assert f == 2 * 7 * 33_558_528 * 2048 * 2
    assert abs(f / 197e12 * 1e3 - 9.768) < 0.001 and b / 819e9 < 0.1 * f / 197e12
    f, b = flops.moe_kernel_cost(cfg, 16384)
    assert abs(f / 197e12 * 1e3 - 6.714) < 0.001 and b / 819e9 < f / 197e12
    # the chain's bytes' bound: 268 MB, 0.33 ms a layer and forward pass
    assert flops.gated_conv_bytes(cfg, 2) == 4 * 16384 * 2048 * 2 == 268_435_456
    assert abs(268_435_456 / 819e9 * 1e3 - 0.328) < 0.001


def test_kernel_costs_by_hand_at_the_toy_sizes():
    """64 positions, 4 heads over 2 of 16, one attention layer of five; 4
    of 8 experts of width 32 at hidden 64, four routed layers."""
    cfg, flops = _cfg(), _load("flops")
    causal = 64 * 65 // 2
    assert flops.live_pairs(cfg) == causal == 2080
    f, b = flops.mask_flash_kernel_cost(cfg, 3)
    assert f == 3 * 2 * 7 * causal * 4 * 16
    q, kv, stats = 64 * 4 * 16 * 2, 64 * 2 * 16 * 2, 64 * 4 * 4
    forward = 2 * q + 2 * kv + stats            # q, o; k, v; lse
    backward = 4 * q + 4 * kv + 2 * stats       # q, o, do, dq; k, v, dk, dv; lse, delta
    assert b == 3 * (forward + backward)
    f, b = flops.moe_kernel_cost(cfg, 100)
    assert f == 2 * 11 * 100 * 64 * 32
    assert b == 4 * (4 * 3 * 64 * 32 * 2) + 3 * 100 * (2 * 64 + 3 * 32) * 2
    assert flops.routed_layers(cfg) == 4 and flops.expected_pairs(cfg) == 64 * 2 * 4 / 8
    mixers = 4 * 4 * 64 * 64 + (64 * 8 * 16 + 64 * 64)
    assert flops.projection_macs(cfg) == 64 * (mixers + 3 * 64 * 96 + 4 * 64 * 8
                                               + 64 * 256)
    assert flops.gated_conv_bytes(cfg, 2) == 4 * 2 * 64 * 64 * 2


# ----------------------------------------------------- the new readers

def _ctx(rows, steps=4, flops=None, device_ops=()):
    """A run's context whose scope table holds ``rows``: {(scope, pass):
    seconds of the traced stretch}."""
    said = []
    instructions = {f"i{k}": ["fusion", s, steps] for k, s in enumerate(rows.values())}
    where = {f"i{k}": (sc, p, "", "f32[8]") for k, (sc, p) in enumerate(rows)}
    return types.SimpleNamespace(
        config=_cfg(toy=False), flops=flops or _load("flops"), say=said.append,
        traced=types.SimpleNamespace(stamps=[0.0] * steps, global_batch=2, chips=1),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(device_ops=[["fusion", 1.0], *device_ops]),
        scope_table=scopes.Table(instructions, where, steps)), said


CONV, ATTN = "hvd_forward/hvd_conv_mixer", "hvd_forward/hvd_attention"
ROWS = {
    (CONV, "forward"): 0.05, (CONV, "recompute"): 0.05, (CONV, "backward"): 0.11,
    (CONV + "/hvd_gated_conv", "forward"): 0.006,
    (CONV + "/hvd_gated_conv", "recompute"): 0.006,
    (CONV + "/hvd_gated_conv", "backward"): 0.02,
    (ATTN + "/hvd_rope", "forward"): 0.002,
    (ATTN + "/hvd_flash_fwd", "forward"): 0.03, (ATTN + "/hvd_flash_dq", "backward"): 0.04,
    (ATTN + "/hvd_flash_dkv", "backward"): 0.05, (ATTN, "backward"): 0.05,
    ("hvd_forward/hvd_mlp", "forward"): 1.0}
FLASH = [["hvd_flash_fwd (custom-call)", 0.03], ["hvd_flash_dq (custom-call)", 0.04],
         ["hvd_flash_dkv (custom-call)", 0.05]]
GROUPED = [["hvd_moe_gmm_gate_up (custom-call)", 0.08],
           ["hvd_moe_tgmm_down (custom-call)", 0.12]]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_trace_readers_read_what_is_there_and_nothing_otherwise(metric,
                                                                    monkeypatch):
    """Present, absent (the parent commit under these files, another
    configuration's flops, no trace at all): a reader returns None and does
    not raise."""
    from horovod_tpu import metrics
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    # what the steps counted: four routed layer-steps of 16,500 pairs each
    # (the readers these files delegate to are loaded anew at every call, so
    # it is the registry that is stood in for, not a function of theirs)
    families = dict(metrics.registry().to_dict())
    families["hvd_moe_routed_total"] = {"series": [
        {"labels": {"what": "pairs"}, "value": 4 * 16500.0},
        {"labels": {"what": "layers"}, "value": 4.0}]}
    monkeypatch.setattr(metrics, "registry", lambda: types.SimpleNamespace(
        to_dict=lambda: families))
    absent, _ = _ctx({("hvd_forward/hvd_mlp", "forward"): 1.0})
    assert read(absent) is None
    untraced = types.SimpleNamespace(**{**vars(absent), "trace": None,
                                        "scope_table": None})
    assert read(untraced) is None
    ctx, said = _ctx(ROWS, device_ops=FLASH + GROUPED)
    value = read(ctx)
    if metric == "conv_mixer_ms":
        assert value == pytest.approx((0.21 + 0.032) / 4 * 1e3)
    elif metric == "gated_conv_ms":
        assert value == pytest.approx(0.032 / 4 * 1e3)
        assert "forward 1.500, recompute 1.500, backward 5.000" in said[-1]
        assert "bytes' bound 0.328 ms a layer and forward pass" in said[-1]
        bare, quiet = _ctx(ROWS, flops=types.SimpleNamespace())
        assert read(bare) == value and not quiet    # the time without the bound
    elif metric == "conv_hybrid_flash_roofline":
        f, _ = _load("flops").mask_flash_kernel_cost(ctx.config, 2)
        assert value == pytest.approx(100 * f / 197e12 * 4 / 0.12) and 0 < value < 100
        assert "compute-bound" in said[-1] and "hvd_flash_dkv (custom-call) 12.500" in said[-1]
    else:
        # four routed layers of the five kept: the accepted reader is handed
        # their count, not ``num_hidden_layers``
        f, _ = _load("flops").moe_kernel_cost(ctx.config, 16500.0)
        assert value == pytest.approx(100 * 4 * f / 197e12 * 4 / 0.20) and 0 < value < 100
        assert "compute-bound" in said[-1] and ctx.config["num_hidden_layers"] == 5
    if metric.endswith("roofline"):     # another configuration's flops: no cost
        other, _ = _ctx(ROWS, flops=types.SimpleNamespace(),
                        device_ops=FLASH + GROUPED)
        assert read(other) is None
