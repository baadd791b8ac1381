"""The ``kanana-2-30b-a3b`` configuration's files: the manifest's entries,
the cut and its count of parameters, every number of the catalog's config,
the published pairs' rotation by hand and the adapter's columns, the program
through the train step against the plain reference at the toy sizes (one
compiled toy step for the module), the three controls of the
configuration's own and the adapter's guard, the shares that add up to the
uncut layer and head, what a program without the kind says, the counts the
roofline rests on by hand, and the four new readers on made-up traces."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import check, registry, scopes

CONFIG = bench_tree.BENCH / "configs" / "kanana-2-30b-a3b"
CELL = "kanana-2-30b-a3b.s16384-b1.dp1"
MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
NEW_METRICS = ("mla_attention_ms", "mla_latent_ms", "mla_flash_roofline",
               "mla_xla_call_sites")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
TOLERANCE = 5e-5        # Solar's test's: float32 program against float32 reference


def _load(name):
    return registry.load_module(str(CONFIG / f"{name}.py"))


def _cfg(toy=True, **over):
    cfg = bench_tree.load(CONFIG / "config.json")
    if toy:
        cfg.update(cfg["toy"])
        cfg["dtype"]["compute"] = "float32"
    cfg.update(over)
    return cfg


def test_manifest_names_the_configuration_its_cell_and_its_metrics():
    """Appended after the accepted entries, in one piece; a configuration
    that comes later lies after these and changes nothing asserted here."""
    names = lambda section: [x["name"] for x in MANIFEST[section]]
    at = names("configs").index("kanana-2-30b-a3b")
    entry = MANIFEST["configs"][at]
    assert names("configs")[at - 1] == "mellum2-12b-a2.5b", "added at the end of its list"
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/kanana-2-30b-a3b/config.json"
    assert entry["source"] == _cfg(False)["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
        "blob/main/config.json")
    where = names("workloads").index(CELL)
    cell = MANIFEST["workloads"][where]
    assert names("workloads")[where - 1] == "mellum2-12b-a2.5b.s16384-b1.dp1"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-a3b", "host-fed.s16384-b1", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    first = names("per_layer").index(NEW_METRICS[0])
    assert names("per_layer")[first - 1] == "rope_tables"
    assert names("per_layer")[first:first + 4] == list(NEW_METRICS)
    for m in MANIFEST["per_layer"][first:first + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    sources = {m["name"]: (m["source"], m["unit"], m["layer"])
               for m in MANIFEST["per_layer"][first:first + 4]}
    assert sources["mla_xla_call_sites"] == ("program_counter", "count", "Kernels")
    assert sources["mla_flash_roofline"] == ("device_trace", "%", "Kernels")
    # of the accepted metrics' lists two took the new cell, after the cells
    # they had (``rope_ms`` would be the third: the accepted
    # test_bench_mellum.py holds its list to Mellum's cell alone, and no
    # accepted benchmark file is this PR's to edit)
    took = {m["name"]: m["workloads"] for m in MANIFEST["per_layer"][:first]
            if CELL in m.get("workloads", [])}
    assert list(took) == ["mlp_ms", "model_unscoped_pct"]
    for cells in took.values():
        assert cells.index(CELL) == cells.index("mellum2-12b-a2.5b.s16384-b1.dp1") + 1
    four = [w["name"] for w in MANIFEST["workloads"][:where + 1] if w["chips"] == 4]
    assert four == ["resnet50-synth.b128.dp4"] and where + 1 == 10 and at + 1 == 8
    loaded = registry.load_cell(str(bench_tree.BENCH), MANIFEST, CELL)
    assert loaded.traffic["per_chip_batch"] == 1 and "16,384" in loaded.traffic["why"]
    reported = {m["name"] for m in registry.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(NEW_METRICS) | {"mfu_pct", "busy_mfu_pct", "device_idle_pct",
                               "mlp_ms", "model_unscoped_pct"} <= reported
    assert not reported & {"mask_flash_roofline", "moe_experts_roofline",
                           "attention_ms", "window_attention_ms", "rope_tables",
                           "rope_ms"}


def test_config_carries_the_published_widths_and_states_its_cut():
    cfg, ref = _cfg(False), _load("reference")
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["router_outputs"],
            cfg["n_shared_experts"], cfg["rms_norm_eps"],
            cfg["routed_scaling_factor"]) == (
        2048, 32, 512, 128, 64, 128, 6144, 768, 6, 128, 2, 1e-6, 2.448)
    assert cfg["seq_len"] == 16384 and cfg["loss_chunk"] == 512
    assert cfg["reduced"] == REDUCED
    assert cfg["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert cfg["vocab_size"] * 8 == 128256 and cfg["n_routed_experts"] * 8 == 128
    assert cfg["deployment"] == (
        "8 chips share each layer: 16 of 128 experts and 1/8 of the vocabulary "
        "a chip, attention, router and shared experts whole on each; the 43 "
        "layers left out lie on further pipeline stages")
    assert len(cfg["deployment"]) <= 200
    assert cfg["kept_layers"] == [0, 1, 2, 3, 4]
    assert [ref.is_dense(cfg, n) for n in range(5)] == [True] + [False] * 4
    said = " ".join(cfg["assumed"])
    for words in ("n_group 1 and topk_group 1", "1e-20", "one SwiGLU of 2 x 768",
                  "head_dim 64 in the source is the rotary width",
                  "0.00204 = 0.02 / sqrt(2 x 48 layers)", "learning rate of 1e-6",
                  "what the 112 absent experts would add is left out",
                  "turns by halves"):
        assert words in said, words
    assert abs(cfg["residual_out_range"] - 0.02 / (2 * 48) ** 0.5) < 3e-6
    assert "hot_experts_here" not in cfg        # the routing is not pinned
    # the issue's arithmetic
    shapes = ref.weight_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items() if keep(k))
    n = count(lambda k: True)
    assert n == 575_955_968 and abs(n * 16 / 1e9 - 9.22) < 0.01
    assert abs(n * 12 / 1e9 - 6.91) < 0.01
    attention = count(lambda k: k in ("l0.wq", "l0.wkv_a", "l0.kv_norm",
                                      "l0.wkv_b", "l0.wo"))
    assert attention == 26_345_984
    assert count(lambda k: k.startswith("l0.")) == 64_098_816
    assert [count(lambda k: k.startswith(f"l{i}.")) for i in range(1, 5)] == [
        111_547_008] * 4
    assert count(lambda k: k.startswith("l1.we_")) == 75_497_472
    assert count(lambda k: k in ("l2.w1", "l2.w2")) == 9_437_184
    assert count(lambda k: k in ("embed", "head")) == 65_667_072
    from horovod_tpu.models import llama
    lcfg = _load("adapter").program_config(cfg)
    assert llama.count_params(lcfg) == n
    assert lcfg.layer_kinds == ("mla",) * 5 and lcfg.first_dense_layers == 1
    assert (lcfg.trunk_norm, lcfg.kv_lora_rank, lcfg.qk_nope_head_dim,
            lcfg.qk_rope_head_dim, lcfg.v_head_dim, lcfg.router_score,
            lcfg.n_shared_experts, lcfg.n_experts, lcfg.experts_held,
            lcfg.expert_top_k, lcfg.d_ff, lcfg.dense_d_ff,
            lcfg.routed_scaling_factor) == (
        "rmsnorm", 512, 128, 64, 128, "sigmoid", 2, 128, 16, 6, 768, 6144, 2.448)
    assert dict(lcfg.rope_tables) == {"mla": llama.RopeTable(theta=1000000)}


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` as published but the keys that are cut."""
    import json
    cfg = _cfg(False)
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 128256}
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(guide)]
        published = next(r for r in rows
                         if r["name"] == "kanana-2-30b-a3b-instruct-2601")["config"]
    except OSError:
        pass                # no guide beside this checkout: the copy above
    for key, value in published.items():
        if key in REDUCED:
            assert cfg[key] != value and cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    toy = cfg["toy"]
    assert toy["qk_nope_head_dim"] != toy["v_head_dim"] or (
        toy["qk_head_dim"] != toy["v_head_dim"])        # D != Dv
    assert toy["qk_rope_head_dim"] * 2 == toy["qk_nope_head_dim"]
    assert toy["qk_head_dim"] == toy["qk_nope_head_dim"] + toy["qk_rope_head_dim"]


def test_published_pairs_are_turned_as_one_position_by_hand():
    """``rope_interleave``: column pairs ``(x[2j], x[2j+1])`` turned by ``p
    theta ** (-j / 32)``; position 3 of a 64-wide head by hand; the
    control's halves are another rotation; and the program's table holds
    the same frequencies."""
    from horovod_tpu.models import llama
    cfg, ref = _cfg(False), _load("reference")
    inv = ref.inv_freq(cfg)
    assert inv.shape == (32,) and inv[0] == 1.0
    assert inv[1] == np.float32(1e6 ** (-1 / 32))
    assert inv[31] == np.float32(1e6 ** (-31 / 32))
    got, factor = llama.rope_inv_freq(llama.RopeTable(theta=1000000), 64)
    np.testing.assert_array_equal(got, inv)
    assert factor == 1.0
    x = np.asarray(jax.random.normal(jax.random.key(0), (1, 5, 2, 64)), np.float64)
    turned = np.asarray(ref.rope_pairs(jnp.asarray(x, jnp.float32), cfg))
    p = 3
    for j in (0, 1, 17, 31):
        a, b = x[0, p, :, 2 * j], x[0, p, :, 2 * j + 1]
        angle = p * float(inv[j])
        np.testing.assert_allclose(turned[0, p, :, 2 * j],
                                   a * np.cos(angle) - b * np.sin(angle), atol=1e-5)
        np.testing.assert_allclose(turned[0, p, :, 2 * j + 1],
                                   a * np.sin(angle) + b * np.cos(angle), atol=1e-5)
    np.testing.assert_array_equal(turned[0, 0], np.float32(x[0, 0]))   # position 0
    halves = np.asarray(ref.rope_pairs(jnp.asarray(x, jnp.float32), cfg, halves=True))
    assert np.abs(halves - turned).max() > 0.1
    # the program turns by halves what the adapter laid out as halves: the
    # same numbers at the permuted columns
    cos, sin = llama.rope_table(llama.RopeTable(theta=1000000), 64, 5)
    evens_odds = np.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ours = np.asarray(llama.rotate(jnp.asarray(evens_odds, jnp.float32), cos, sin))
    np.testing.assert_allclose(
        np.concatenate([turned[..., 0::2], turned[..., 1::2]], -1), ours, atol=1e-5)


def test_adapters_columns_are_a_permutation_and_come_back_exactly():
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    w = ref.make_weights(cfg, jax.random.key(4))
    columns = adapter._columns(cfg)
    h, dn, dr = 4, 16, 8
    assert sorted(columns) == ["wkv_a", "wkv_b", "wq"]
    for name, c in columns.items():
        assert sorted(c) == list(range(w[f"l1.{name}"].shape[1])), name
    # head 1's first column without positions; then its rotary evens, odds
    assert columns["wq"][dn] == dn + dr and columns["wq"][h * dn] == dn
    assert list(columns["wq"][h * dn:h * dn + dr]) == [16, 18, 20, 22, 17, 19, 21, 23]
    assert list(columns["wkv_a"][32:]) == [32, 34, 36, 38, 33, 35, 37, 39]
    assert columns["wkv_b"][dn] == 2 * dn and columns["wkv_b"][h * dn] == dn
    params = adapter._to_program(w, cfg)
    assert set(params["layers"]) == {"dense_mla", "mla"}
    assert params["layers"]["dense_mla"]["w1"].shape == (1, 64, 2 * 96)
    assert params["layers"]["mla"]["w1"].shape == (2, 64, 2 * 2 * 32)
    assert "router" not in params["layers"]["dense_mla"]
    back = adapter._to_flat(params, cfg)
    assert set(back) == set(w)
    for k in w:
        np.testing.assert_array_equal(back[k], w[k])


# -------------------------------------- one compiled toy step a module

def _three_steps(program, ref, cfg, key, batch=2):
    batches = [ref.make_samples(cfg, jax.random.fold_in(key, j), batch)
               for j in range(check.STEPS)]
    state, losses, grad, stats = program.init(key), [], None, None
    for b in batches:
        params, opt_state, loss, stats = program._step(*state, program.place(b))
        state = (params, opt_state)
        if grad is None:
            grad = check.leaf_norms(program.first_gradient(state))
        losses.append(loss)
    w0 = ref.make_weights(cfg, key)
    got = jax.device_get({
        "losses": losses, "grad_norms": grad,
        "update_norms": check.leaf_norms(
            {k: v - w0[k] for k, v in program.params(state).items()})})
    return got, batches, state, np.asarray(stats)


@pytest.fixture(scope="module")
def toy(hvd):
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    program = adapter.build(cfg, ref, jax.devices()[:1], 2)
    key = jax.random.key(11)
    got, batches, state, stats = _three_steps(program, ref, cfg, key)
    return types.SimpleNamespace(cfg=cfg, ref=ref, program=program, key=key,
                                 got=got, batches=batches, state=state,
                                 stats=stats)


def _controlled(ref, control):
    """The reference with one of its controls in its loss."""
    return types.SimpleNamespace(
        make_weights=ref.make_weights,
        loss=lambda cfg, w, batch: ref.loss(cfg, w, batch, control=control))


def test_toy_model_through_the_train_step_follows_the_reference(toy):
    """Loss, first gradient leaf by leaf and update of three steps through
    ``make_llama_train_step``, on seeded weights, float32."""
    want = check.Reference(toy.ref, toy.cfg, jax.devices()[:1]).run(
        toy.key, toy.batches)
    assert set(toy.got["grad_norms"]) == set(want["grad_norms"]) == set(
        toy.ref.weight_shapes(toy.cfg))
    for name, (value, where) in check.compare(toy.got, want).items():
        assert value < TOLERANCE, (name, value, where)


@pytest.mark.parametrize("control", ["no_latent_norm", "scale_128", "rope_halves"])
def test_each_control_of_its_own_is_another_model(toy, control):
    """More than the tolerance away, and the attention's own leaves are
    what sees it."""
    other = check.Reference(_controlled(toy.ref, control), toy.cfg,
                            jax.devices()[:1]).run(toy.key, toy.batches)
    numbers = check.compare(toy.got, other)
    worst = max(numbers, key=lambda k: numbers[k][0])
    assert numbers[worst][0] > 10 * TOLERANCE, (control, numbers)
    assert numbers["grad_norm_gap"][1].split(".")[-1] in (
        "wq", "wkv_a", "kv_norm", "wkv_b", "wo"), numbers


def test_toy_step_names_its_parts_and_counts_the_routed_layers_alone(toy):
    text = toy.program.compiled(toy.state, toy.program.place(toy.batches[0])).as_text()
    for scope in ("hvd_mla_attention", "hvd_mla_latent", "hvd_rope", "hvd_mlp",
                  "hvd_moe_route", "hvd_moe_experts", "hvd_moe_shared",
                  "hvd_head", "hvd_embed"):
        assert scope in text, scope
    assert "hvd_attention/" not in text and "hvd_window_attention" not in text
    # the routing statistics a step hands on: the 2 routed layers of 3
    pairs, rows, fullest, layers = toy.stats
    assert layers == 2 and rows == pairs and 0 < fullest < pairs
    even = 2 * 2 * toy.cfg["seq_len"] * 2 * 4 / 8
    assert 0.5 * even < pairs < 1.5 * even


def test_guard_reads_the_program_near_the_reference_and_the_controls_far(toy):
    """``mla_o_gap`` at the toy sizes: float32 against float32 is rounding;
    each control is percents away."""
    params = toy.state[0]
    sound = float(toy.program.mla_o_gap(params, toy.key))
    assert sound < 1e-5
    for control in ("no_latent_norm", "scale_128", "rope_halves"):
        assert float(toy.program.mla_o_gap(params, toy.key, control)) > 1e-3, control
    assert toy.ref.MLA_O_GAP < 0.05


def test_a_program_without_the_kind_says_so_at_once(monkeypatch):
    """The parent commit under these files: a ValueError from the
    configuration's kind, before anything is built."""
    from horovod_tpu.models import hybrid
    adapter = _load("adapter")
    monkeypatch.setattr(hybrid, "KINDS", ("mamba", "window", "full", "gmu",
                                          "cross", "mamba2", "attention", "kda",
                                          "swa"))
    with pytest.raises(ValueError, match=r"has no 'mla'"):
        adapter.program_config(_cfg())
    with pytest.raises(ValueError, match=r"has no 'mla'"):
        adapter.build(_cfg(), _load("reference"), jax.devices()[:1], 1)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="maps the published kanana keys"):
        adapter.program_config(_cfg(q_lora_rank=1536))


def test_weights_are_made_as_the_configuration_says():
    cfg, ref = _cfg(), _load("reference")
    w = ref.make_weights(cfg, jax.random.key(2))
    assert set(w) == set(ref.weight_shapes(cfg))
    assert all(w[k].shape == s for k, s in ref.weight_shapes(cfg).items())
    for leaf in ("l0.norm1_w", "l2.norm2_w", "l1.kv_norm", "final_norm_w"):
        assert (np.asarray(w[leaf]) == 1).all()
    assert (np.asarray(w["l1.router_bias"]) == 0).all()
    assert "l0.router" not in w and "l0.we_gate" not in w and "l1.we_gate" in w
    assert w["l0.w1"].shape == (64, 2 * 96) and w["l1.w1"].shape == (64, 2 * 2 * 32)
    assert abs(float(w["l0.wq"].std()) - cfg["initializer_range"]) < 2e-3
    assert abs(float(w["embed"].std()) - cfg["embedding_range"]) < 0.05
    for leaf in ("l0.wo", "l0.w2", "l2.w2", "l1.we_down"):
        assert abs(float(w[leaf].std()) - cfg["residual_out_range"]) < 4e-4
    tokens, targets = ref.make_samples(cfg, jax.random.key(3), 16)
    assert tokens.shape == targets.shape == (16, cfg["seq_len"])
    assert (tokens[:, 1:] == targets[:, :-1]).all() and tokens.max() < cfg["vocab_size"]


# ------------------------------------------------- the shares add up

def test_the_eight_expert_shares_add_up_to_the_uncut_layer(hvd):
    """8 chips of an eighth of the experts each (the program's layer, told
    which experts it holds), with the shared experts counted once, sum to
    the uncut reference's feed-forward, scaling factor and all, and every
    routed pair is on one chip.  Attention and the dense layer are whole on
    every chip: the same on each, so counted once."""
    from horovod_tpu.models import llama, moe
    ref = _load("reference")
    cfg = _cfg(router_outputs=16, n_routed_experts=16, num_experts_per_tok=4)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    r = jax.random.split(jax.random.key(7), 7)
    lw = {"router": jax.random.normal(r[0], (d, 16)),
          "router_bias": jnp.zeros((16,)),
          "we_gate": jax.random.normal(r[1], (16, d, f)) * d ** -0.5,
          "we_up": jax.random.normal(r[2], (16, d, f)) * d ** -0.5,
          "we_down": jax.random.normal(r[3], (16, f, d)) * f ** -0.5,
          "w1": jax.random.normal(r[5], (d, 4 * f)) * d ** -0.5,
          "w2": jax.random.normal(r[6], (2 * f, d)) * f ** -0.5}
    x = jax.random.normal(r[4], (2, 32, d))
    with jax.default_matmul_precision("highest"):
        whole = ref.feed_forward(x, lw, cfg)
        shared = ref.swiglu(x, lw["w1"], lw["w2"])
        np.testing.assert_allclose(moe.shared_expert(x, lw["w1"], lw["w2"]),
                                   shared, atol=1e-5)
        total, pairs = shared, 0
        for chip in range(8):
            lcfg = llama.LlamaConfig(
                d_model=d, d_ff=f, n_experts=16, expert_top_k=4,
                moe_dispatch="dropless", experts_held=2, experts_first=2 * chip,
                router_score="sigmoid", routed_scaling_factor=2.448,
                dtype=jnp.float32)
            share = {**lw, **{n: lw[n][2 * chip:2 * chip + 2]
                              for n in ("we_gate", "we_up", "we_down")}}
            y, stats = moe.dropless_moe_layer(x, share, lcfg, llama.ParallelSpec())
            part = ref.feed_forward(x, share, {**cfg, "n_routed_experts": 2,
                                               "experts_first": 2 * chip}) - shared
            assert float(jnp.abs(y - part).max()) < 2e-5 * float(jnp.abs(whole).max())
            total, pairs = total + y, pairs + float(stats[0])
    assert pairs == 2 * 32 * 4                  # every pair on some chip, once
    assert float(jnp.abs(total - whole).max()) < 2e-5 * float(jnp.abs(whole).max())


def test_the_eight_vocabulary_slices_side_by_side_are_the_uncut_head():
    """The loss over an eighth of the vocabulary is a smaller vocabulary's;
    what ties it to the model: the eight slices' logits side by side are the
    uncut head's, and their log-sum-exps, combined as a vocabulary-parallel
    head combines them, with the target's logit from the slice that holds
    it, give the uncut cross-entropy."""
    cfg = _cfg()
    v, d = cfg["vocab_size"] // 8, cfg["hidden_size"]
    V = 8 * v
    r = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(r[0], (2, 64, d))
    head = jax.random.normal(r[1], (V, d)) * 0.2
    targets = jax.random.randint(r[2], (2, 64), 0, V)
    with jax.default_matmul_precision("highest"):
        uncut = x @ head.T
        whole = -jnp.take_along_axis(jax.nn.log_softmax(uncut, axis=-1),
                                     targets[..., None], -1).mean()
        slices = [x @ head[c * v:(c + 1) * v].T for c in range(8)]
        np.testing.assert_allclose(jnp.concatenate(slices, -1), uncut, atol=1e-5)
        lses, picked = [], 0.0
        for c, logits in enumerate(slices):
            lses.append(jax.nn.logsumexp(logits, axis=-1))
            local = jnp.clip(targets - c * v, 0, v - 1)
            inside = (targets >= c * v) & (targets < (c + 1) * v)
            picked = picked + jnp.where(
                inside, jnp.take_along_axis(logits, local[..., None], -1)[..., 0], 0.0)
        combined = (jax.nn.logsumexp(jnp.stack(lses), axis=0) - picked).mean()
    assert abs(float(combined - whole)) < 1e-5 * abs(float(whole))


# ----------------------------------------------------- flops by hand

def test_kanana_flops_from_shapes():
    cfg, flops = _cfg(toy=False), _load("flops")
    T = 16384
    assert flops.attention_params(cfg) == 26_345_984 - 512      # no norm
    assert flops.expert_params(cfg) == 4_718_592
    assert flops.expected_pairs(cfg) == 12288.0
    assert flops.live_pairs(cfg) == T * (T + 1) // 2 == 134_225_920
    from horovod_tpu.ops import flash_attention as fa
    assert flops.live_pairs({**cfg, "seq_len": 2048}) == int(
        fa.dense_mask(fa.causal_ranges(2048), 2048).sum())
    assert flops.attention_macs(cfg) == 5 * 134_225_920 * 32 * 320
    assert flops.projection_macs(cfg) == T * (
        5 * 26_345_472 + 37_748_736 + 4 * (262_144 + 9_437_184)
        + 2048 * 16032)
    assert flops.expert_macs(cfg) == 4 * 12288 * 4_718_592
    assert flops.train_flops_per_sample(cfg) == 6 * flops.forward_macs(cfg)
    # latent attention is most of the model's work at this row
    assert 0.6 < flops.attention_macs(cfg) / flops.forward_macs(cfg) < 0.8
    # the kernels' least: each product once at 192 + 128
    f, b = flops.mla_flash_kernel_cost(cfg, 1)
    assert f == 2 * 134_225_920 * 32 * 5 * (320 + 832)
    assert abs(f / 197e12 * 1e3 - 251.2) < 0.1 and b / 819e9 < 0.05 * f / 197e12
    f, b = flops.moe_kernel_cost(cfg, 12288)
    assert f == 2 * 11 * 12288 * 2048 * 768 and b / 819e9 < f / 197e12 * 2


def test_the_kernels_cost_by_hand_at_the_toy_sizes():
    """64 positions, 4 heads, scores 16 + 8 wide, values 16, three layers."""
    cfg, flops = _cfg(), _load("flops")
    causal = 64 * 65 // 2
    f, b = flops.mla_flash_kernel_cost(cfg, 3)
    assert f == 2 * causal * 4 * 3 * 3 * ((24 + 16) + (24 + 16 + 16 + 24 + 24))
    rows = 3 * 64 * 2
    q, kn, v, kpe, stats = rows * 4 * 24, rows * 4 * 16, rows * 4 * 16, rows * 8, 3 * 64 * 4 * 4
    forward = q + kn + kpe + v + v + stats              # q, k_nope, k_pe, v; o, lse
    backward = 2 * (q + kn + kpe + v) + 2 * v + 2 * stats   # those and their gradients; o, do; lse, delta
    assert b == 3 * (forward + backward)
    # the shared rotary key is read as one head: 4 heads' copies would be more
    assert kpe * 4 == rows * 4 * 8


# ----------------------------------------------------- the new readers

def _ctx(rows, steps=4, flops=None):
    """A run's context whose scope table holds ``rows``: {(scope, pass):
    seconds of the traced stretch}."""
    said = []
    instructions = {f"i{k}": ["fusion", s, steps] for k, s in enumerate(rows.values())}
    where = {f"i{k}": (sc, p, "", "f32[8]") for k, (sc, p) in enumerate(rows)}
    return types.SimpleNamespace(
        config=_cfg(toy=False), flops=flops or _load("flops"), say=said.append,
        traced=types.SimpleNamespace(stamps=[0.0] * steps, global_batch=1, chips=1),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(device_ops=[["fusion", 1.0]]),
        scope_table=scopes.Table(instructions, where, steps)), said


MLA = "hvd_forward/hvd_mla_attention"
ROWS = {
    (MLA, "forward"): 0.04, (MLA, "backward"): 0.08,
    (MLA + "/hvd_mla_latent", "forward"): 0.01,
    (MLA + "/hvd_mla_latent", "recompute"): 0.01,
    (MLA + "/hvd_mla_latent", "backward"): 0.02,
    (MLA + "/hvd_rope", "forward"): 0.004,
    (MLA + "/hvd_flash_fwd", "forward"): 0.4,
    (MLA + "/hvd_flash_dq", "backward"): 0.6,
    (MLA + "/hvd_flash_dkv", "backward"): 0.8,
    ("hvd_forward/hvd_attention/hvd_flash_fwd", "forward"): 5.0,   # another kind's
    ("hvd_forward/hvd_mlp", "forward"): 1.0}


@pytest.mark.parametrize("metric", ["mla_attention_ms", "mla_latent_ms",
                                    "mla_flash_roofline"])
def test_new_trace_readers_read_what_is_there_and_nothing_otherwise(metric):
    """Present, absent (the parent commit under these files, another
    configuration's flops, no trace at all): a reader returns None and does
    not raise."""
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    absent, _ = _ctx({("hvd_forward/hvd_mlp", "forward"): 1.0})
    assert read(absent) is None
    untraced = types.SimpleNamespace(**{**vars(absent), "trace": None,
                                        "scope_table": None})
    assert read(untraced) is None
    ctx, said = _ctx(ROWS)
    value = read(ctx)
    if metric == "mla_attention_ms":
        assert value == pytest.approx((0.12 + 0.04 + 0.004 + 1.8) / 4 * 1e3)
    elif metric == "mla_latent_ms":
        assert value == pytest.approx(0.04 / 4 * 1e3)
    else:
        f, _ = _load("flops").mla_flash_kernel_cost(ctx.config, 1)
        assert value == pytest.approx(100 * f / 197e12 * 4 / 1.8) and 0 < value < 100
        lines = " | ".join(said)
        assert "compute-bound" in lines and "hvd_flash_dkv 200.000" in lines
        other, _ = _ctx(ROWS, flops=types.SimpleNamespace())
        assert read(other) is None      # another configuration's flops: no cost


def test_mla_xla_call_sites_is_the_programs_counter(monkeypatch):
    from horovod_tpu import metrics
    from horovod_tpu.models import hybrid, llama
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics",
                           "mla_xla_call_sites")
    monkeypatch.setattr(metrics, "ACTIVE", True)
    ctx, said = _ctx({})
    before = read(ctx) or 0
    lcfg = _load("adapter").program_config(_cfg())
    layers = jax.eval_shape(lambda k: llama.init_params(lcfg, k)["layers"],
                            jax.random.key(0))
    jax.eval_shape(lambda h, ls: hybrid.layer_stack(h, ls, lcfg),
                   jax.ShapeDtypeStruct((1, 64, 64), jnp.float32), layers)
    # on the CPU both of the toy's traced layers (the dense one and the
    # routed ones' one function) take XLA's fallback, joined
    assert read(ctx) == before + 2
    assert "xla joined" in said[-1]
    kinds = {s["labels"]["kind"]: s["value"] for s in
             metrics.registry().to_dict()["hvd_layer_kind_total"]["series"]}
    assert kinds.get("mla", 0) >= 3
    tables = {(s["labels"]["kind"], s["labels"]["type"]) for s in
              metrics.registry().to_dict()["hvd_rope_tables_total"]["series"]}
    assert ("mla", "default") in tables
    families = metrics.registry().to_dict()
    families.pop("hvd_mla_call_total", None)    # a program that has no such counter
    monkeypatch.setattr(metrics, "registry", lambda: types.SimpleNamespace(
        to_dict=lambda: families))
    assert read(ctx) is None


# Mellum's toy's step as the parent commit lowers and runs it (e3b3200, jax
# 0.9.0, on the CPU): its text, first loss and first gradient to the bit
# (Solar's and Phi's stand in test_bench_mellum.py, SDAR's and Granite's in
# test_bench_solar.py, both pinned lowered texts in
# test_bench_phi4flash.py).  ``routed_scaling_factor`` 1.0 and no leading
# dense layer compute what was computed: models/hybrid.py, models/moe.py,
# ops/flash_attention.py and LlamaConfig are that program's too; a change to
# one changes this and states it here.
PARENTS_TOY = {"lowered": "462cfab8794e4b3f", "loss": "0x1.6676380000000p+2",
               "grads": "d5afbf73819407c8"}


def test_mellums_toy_step_is_the_parents_to_the_bit(hvd):
    import hashlib
    cdir = bench_tree.BENCH / "configs" / "mellum2-12b-a2.5b"
    cfg = bench_tree.load(cdir / "config.json")
    cfg.update(cfg["toy"])
    cfg["dtype"]["compute"] = "float32"
    ref = registry.load_module(str(cdir / "reference.py"))
    prog = registry.load_module(str(cdir / "adapter.py")).build(
        cfg, ref, jax.devices()[:1], 2)
    batch = prog.place(ref.make_samples(cfg, jax.random.key(1), 2))
    state = prog.init(jax.random.key(0))
    text = prog._step.lower(*state, batch).as_text()
    assert "hvd_mla_attention" not in text and "dense_" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS_TOY["lowered"]
    state, loss = prog.step(state, batch)
    assert float(loss).hex() == PARENTS_TOY["loss"]
    g = prog.first_gradient(state)
    bits = b"".join(bytes(memoryview(jax.device_get(g[k]))) for k in sorted(g))
    assert hashlib.sha256(bits).hexdigest()[:16] == PARENTS_TOY["grads"]
