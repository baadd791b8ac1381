"""The ``mellum2-12b-a2.5b`` configuration's files: the manifest's entries,
the cut and its count of parameters, every number of the catalog's config,
the program through the train step against the plain reference at the toy
sizes, the two controls of the configuration's own, the shares that add up
to the uncut layer and loss, what a program without the kind says, the
counts the rooflines rest on by hand, the five new readers on made-up
traces, and Solar's and Phi's toy programs, which the trunk's new kind and
the config's new field leave as they were (SDAR's and Granite's are held in
test_bench_solar.py, both pinned lowered texts in test_bench_phi4flash.py)."""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import check, registry, scopes

CONFIG = bench_tree.BENCH / "configs" / "mellum2-12b-a2.5b"
CELL = "mellum2-12b-a2.5b.s16384-b1.dp1"
MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
NEW_METRICS = ("window_attention_ms", "rope_ms", "swa_flash_roofline",
               "swa_moe_experts_roofline", "rope_tables")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
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
    at = names("configs").index("mellum2-12b-a2.5b")
    entry = MANIFEST["configs"][at]
    assert names("configs")[at - 1] == "solar-open2-250b", "added at the end of its list"
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/mellum2-12b-a2.5b/config.json"
    where = names("workloads").index(CELL)
    cell = MANIFEST["workloads"][where]
    assert names("workloads")[where - 1] == "solar-open2-250b.s8192-b1.dp1"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-a2.5b", "host-fed.s16384-b1", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    first = names("per_layer").index(NEW_METRICS[0])
    assert names("per_layer")[first - 1] == "moe_shared_ms"
    assert names("per_layer")[first:first + 5] == list(NEW_METRICS)
    for m in MANIFEST["per_layer"][first:first + 5]:
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    sources = {m["name"]: (m["source"], m["unit"], m["layer"])
               for m in MANIFEST["per_layer"][first:first + 5]}
    assert sources["rope_tables"] == ("program_counter", "count", "Model")
    assert sources["swa_flash_roofline"] == ("device_trace", "%", "Kernels")
    # of the accepted metrics' lists two took the new cell, after the cells
    # they had
    took = {m["name"]: m["workloads"] for m in MANIFEST["per_layer"][:first]
            if CELL in m.get("workloads", [])}
    assert list(took) == ["mlp_ms", "model_unscoped_pct"]
    for cells in took.values():
        assert cells.index(CELL) == cells.index("solar-open2-250b.s8192-b1.dp1") + 1
    four = [w["name"] for w in MANIFEST["workloads"][:where + 1] if w["chips"] == 4]
    assert four == ["resnet50-synth.b128.dp4"] and where + 1 == 9 and at + 1 == 7
    loaded = registry.load_cell(str(bench_tree.BENCH), MANIFEST, CELL)
    assert loaded.traffic == {**bench_tree.load(
        bench_tree.BENCH / "traffic" / "host-fed.s8192-b1.json"),
        "why": loaded.traffic["why"]}
    assert "16,384" in loaded.traffic["why"]
    # the cell reports the accepted metrics that list no cells, its own five
    # and the two lists it joined
    reported = {m["name"] for m in registry.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(NEW_METRICS) | {"mfu_pct", "busy_mfu_pct", "device_idle_pct",
                               "mlp_ms", "model_unscoped_pct"} <= reported
    assert not reported & {"mask_flash_roofline", "moe_experts_roofline",
                           "attention_ms", "head_ms", "moe_imbalance"}


def test_config_carries_the_published_widths_and_states_its_cut():
    cfg, ref = _cfg(False), _load("reference")
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["num_experts_per_tok"],
            cfg["router_outputs"], cfg["rms_norm_eps"]) == (
        2304, 896, 128, 32, 4, 1024, 8, 64, 1e-6)
    assert cfg["seq_len"] == 16384 and cfg["loss_chunk"] == 512
    assert cfg["reduced"] == REDUCED
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert cfg["vocab_size"] * 4 == 98304 and cfg["num_experts"] * 4 == 64
    assert cfg["deployment"] == (
        "4 chips share each layer: 16 of 64 experts and 1/4 of the vocabulary a "
        "chip, attention whole on each; the 24 layers left out lie on six "
        "further pipeline stages") and len(cfg["deployment"]) <= 200
    assert cfg["kept_layers"] == [0, 1, 2, 3] and len(cfg["layer_types"]) == 28
    assert ref.kept_types(cfg) == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    said = " ".join(cfg["assumed"])
    for words in ("no MTP head", "no q/k norm and no auxiliary router loss",
                  "0.00267 = 0.02 / sqrt(2 x 28 layers)", "learning rate of 1e-6",
                  "low = floor(c(32)) = 18, high = ceil(c(1)) = 35",
                  "what the 48 absent experts would add is left out"):
        assert words in said, words
    assert abs(cfg["residual_out_range"] - 0.02 / (2 * 28) ** 0.5) < 3e-6
    assert "hot_experts_here" not in cfg        # the routing is not pinned
    # the issue's arithmetic
    shapes = ref.weight_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items() if keep(k))
    n = count(lambda k: True)
    assert n == 595_153_152 and abs(n * 16 / 1e9 - 9.52) < 0.01
    assert abs(n * 12 / 1e9 - 7.14) < 0.01
    assert [count(lambda k: k.startswith(f"l{i}.")) for i in range(4)] == [120_476_160] * 4
    attention = count(lambda k: k in ("l0.wqkv", "l0.wo"))
    assert attention == 2304 * 4096 * 2 + 2304 * 512 * 2 == 21_233_664
    assert count(lambda k: k == "l3.router") == 147_456
    assert count(lambda k: k.startswith("l1.we_")) == 99_090_432 == 16 * 3 * 2304 * 896
    assert count(lambda k: k in ("embed", "head")) == 113_246_208
    from horovod_tpu.models import llama
    lcfg = _load("adapter").program_config(cfg)
    assert llama.count_params(lcfg) == n
    assert lcfg.layer_kinds == ("swa", "swa", "swa", "attention")
    assert (lcfg.trunk_norm, lcfg.head_dim, lcfg.sliding_window, lcfg.attn_gate,
            lcfg.router_score, lcfg.n_shared_experts, lcfg.n_experts,
            lcfg.experts_held, lcfg.expert_top_k, lcfg.qk_norm) == (
        "rmsnorm", 128, 1024, False, "softmax", 0, 64, 16, 8, False)
    tables = dict(lcfg.rope_tables)
    assert tables["swa"] == llama.RopeTable(theta=500000)
    assert tables["attention"] == llama.RopeTable(
        theta=500000, rope_type="yarn", factor=16,
        original_max_position_embeddings=8192, beta_fast=32, beta_slow=1,
        attention_factor=1.2772588722239782)


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` as published but the keys that are cut."""
    import json
    cfg = _cfg(False)
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168, "layer_types": kinds * 7,
        "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
        "max_window_layers": 0, "model_type": "mellum",
        "moe_intermediate_size": 896, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(guide)]
        published = next(r for r in rows
                         if r["name"] == "Mellum2-12B-A2.5B-Instruct")["config"]
    except OSError:
        pass                # no guide beside this checkout: the copy above
    for key, value in published.items():
        if key in REDUCED:
            assert cfg[key] != value and cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the toy lays a whole group over the rotary parameters, with a ramp
    # that rises inside its 16 frequencies and an original length under the row
    toy = cfg["toy"]["rope_parameters"]["full_attention"]
    assert set(toy) == set(published["rope_parameters"]["full_attention"])
    assert toy["original_max_position_embeddings"] < cfg["toy"]["seq_len"]
    assert cfg["toy"]["sliding_window"] < cfg["toy"]["seq_len"]
    low, high = _load("reference").yarn_range(toy, cfg["toy"]["head_dim"])
    assert 0 <= low < high - 1 < 15


def test_reference_tables_are_the_equations_at_the_published_numbers():
    """The reference's own tables (nothing of ``horovod_tpu`` in them):
    ``low`` 18, ``high`` 35, five frequencies by hand, the factor on cos and
    sin both; and the program's are the same numbers."""
    from horovod_tpu.models import llama
    cfg, ref = _cfg(False), _load("reference")
    p = cfg["rope_parameters"]["full_attention"]
    assert ref.yarn_range(p, 128) == (18, 35)
    inv, factor = ref.inv_freq(p, 128)
    by_hand = {0: 1.0, 18: 0.024955408670558694,
               26: 0.004839421345719893 * (8 / 17 / 16 + 9 / 17),
               35: 0.0007644969883171747 / 16, 63: 2.455140791131609e-06 / 16}
    for j, value in by_hand.items():
        assert inv[j] == np.float32(value), j
    assert factor == 1.2772588722239782 == 0.1 * np.log(16.0) + 1.0
    plain, one = ref.inv_freq(cfg["rope_parameters"]["sliding_attention"], 128)
    assert one == 1.0 and plain[63] == np.float32(2.455140791131609e-06)
    tables = dict(_load("adapter").program_config(cfg).rope_tables)
    for kind, (want, f) in (("attention", (inv, factor)), ("swa", (plain, one))):
        got, g = llama.rope_inv_freq(tables[kind], 128)
        np.testing.assert_array_equal(got, want)
        assert g == f
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 128))
    roped = ref.rope(x, p)
    angles = np.arange(8)[:, None] * inv.astype(np.float64)
    c, s = (f(angles)[None, :, None] * factor for f in (np.cos, np.sin))
    x1, x2 = np.asarray(x[..., :64], np.float64), np.asarray(x[..., 64:], np.float64)
    np.testing.assert_allclose(
        roped, np.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1), atol=1e-5)


def _three_steps(program, ref, cfg, key, batch=4):
    batches = [ref.make_samples(cfg, jax.random.fold_in(key, j), batch)
               for j in range(check.STEPS)]
    state, losses, grad = program.init(key), [], None
    for b in batches:
        state, loss = program.step(state, program.place(b))
        if grad is None:
            grad = check.leaf_norms(program.first_gradient(state))
        losses.append(loss)
    w0 = ref.make_weights(cfg, key)
    got = jax.device_get({
        "losses": losses, "grad_norms": grad,
        "update_norms": check.leaf_norms(
            {k: v - w0[k] for k, v in program.params(state).items()})})
    return got, batches, state


def _controlled(ref, control):
    """The reference with one of its controls in its loss."""
    return types.SimpleNamespace(
        make_weights=ref.make_weights,
        loss=lambda cfg, w, batch: ref.loss(cfg, w, batch, control=control))


def test_toy_model_through_the_train_step_follows_the_reference(hvd):
    """Loss, first gradient leaf by leaf and update of three steps through
    ``make_llama_train_step``, on seeded weights, float32; and the two
    controls of the configuration's own (the full layer under the sliding
    table, the sliding layers without their window) are other models by
    more than the tolerance."""
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    program = adapter.build(cfg, ref, jax.devices()[:1], 4)
    key = jax.random.key(11)
    got, batches, state = _three_steps(program, ref, cfg, key)
    want = check.Reference(ref, cfg, jax.devices()[:1]).run(key, batches)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == set(
        ref.weight_shapes(cfg))
    for name, (value, where) in check.compare(got, want).items():
        assert value < TOLERANCE, (name, value, where)
    for control in ("no_yarn", "no_window"):
        other = check.Reference(_controlled(ref, control), cfg,
                                jax.devices()[:1]).run(key, batches)
        numbers = check.compare(got, other)
        worst = max(numbers, key=lambda k: numbers[k][0])
        assert numbers[worst][0] > 10 * TOLERANCE, (control, numbers)
        # the attention's own leaves are what sees it
        assert numbers["grad_norm_gap"][1].split(".")[-1] in ("wqkv", "wo"), numbers
    text = program.compiled(state, program.place(batches[0])).as_text()
    for scope in ("hvd_window_attention", "hvd_attention", "hvd_rope", "hvd_mlp",
                  "hvd_moe_route", "hvd_moe_experts", "hvd_head", "hvd_embed"):
        assert scope in text, scope
    assert "hvd_moe_shared" not in text
    # the routing statistics a step hands on: 4 layers, pairs within reason
    _, _, _, stats = program._step(*state, program.place(batches[0]))
    pairs, rows, fullest, layers = np.asarray(stats)
    assert layers == 4 and rows == pairs and 0 < fullest < pairs
    even = 4 * 4 * cfg["seq_len"] * cfg["num_experts_per_tok"] * 4 / 8
    assert 0.5 * even < pairs < 1.5 * even


def test_a_program_without_the_kind_says_so_at_once(monkeypatch):
    """The parent commit under these files: a ValueError from the
    configuration's kinds, before anything is built."""
    from horovod_tpu.models import hybrid
    adapter = _load("adapter")
    monkeypatch.setattr(hybrid, "KINDS", ("mamba", "window", "full", "gmu",
                                          "cross", "mamba2", "attention", "kda"))
    with pytest.raises(ValueError, match=r"has no \['swa'\]"):
        adapter.program_config(_cfg())
    with pytest.raises(ValueError, match=r"has no \['swa'\]"):
        adapter.build(_cfg(), _load("reference"), jax.devices()[:1], 1)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="maps the published mellum keys"):
        adapter.program_config(_cfg(norm_topk_prob=False))


def test_weights_are_made_as_the_configuration_says():
    cfg, ref = _cfg(), _load("reference")
    w = ref.make_weights(cfg, jax.random.key(2))
    assert set(w) == set(ref.weight_shapes(cfg))
    assert all(w[k].shape == s for k, s in ref.weight_shapes(cfg).items())
    for leaf in ("l0.norm1_w", "l2.norm2_w", "final_norm_w"):
        assert (np.asarray(w[leaf]) == 1).all()
    assert not [k for k in w if "bias" in k or "q_norm" in k or "mtp" in k]
    assert abs(float(w["l0.wqkv"].std()) - cfg["initializer_range"]) < 2e-3
    assert abs(float(w["head"].std()) - cfg["initializer_range"]) < 2e-3
    assert abs(float(w["embed"].std()) - cfg["embedding_range"]) < 0.05
    for leaf in ("l0.wo", "l3.wo", "l1.we_down"):
        assert abs(float(w[leaf].std()) - cfg["residual_out_range"]) < 4e-4
    tokens, targets = ref.make_samples(cfg, jax.random.key(3), 16)
    assert tokens.shape == targets.shape == (16, cfg["seq_len"])
    assert (tokens[:, 1:] == targets[:, :-1]).all() and tokens.max() < cfg["vocab_size"]


# ------------------------------------------------- the shares add up

def test_the_four_expert_shares_add_up_to_the_uncut_layer(hvd):
    """4 chips of a quarter of the experts each (the program's layer, told
    which experts it holds) sum to the uncut reference's feed-forward, and
    every routed pair is on one chip."""
    from horovod_tpu.models import llama, moe
    ref = _load("reference")
    cfg = _cfg(router_outputs=16, num_experts=16, num_experts_per_tok=4)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    r = jax.random.split(jax.random.key(7), 5)
    lw = {"router": jax.random.normal(r[0], (d, 16)),
          "we_gate": jax.random.normal(r[1], (16, d, f)) * d ** -0.5,
          "we_up": jax.random.normal(r[2], (16, d, f)) * d ** -0.5,
          "we_down": jax.random.normal(r[3], (16, f, d)) * f ** -0.5}
    x = jax.random.normal(r[4], (2, 32, d))
    with jax.default_matmul_precision("highest"):
        whole = ref.feed_forward(x, lw, cfg)
        total, pairs = 0, 0
        for chip in range(4):
            lcfg = llama.LlamaConfig(
                d_model=d, d_ff=f, n_experts=16, expert_top_k=4,
                moe_dispatch="dropless", experts_held=4, experts_first=4 * chip,
                router_score="softmax", dtype=jnp.float32)
            share = {**lw, **{n: lw[n][4 * chip:4 * chip + 4]
                              for n in ("we_gate", "we_up", "we_down")}}
            y, stats = moe.dropless_moe_layer(x, share, lcfg, llama.ParallelSpec())
            part = ref.feed_forward(x, share, {**cfg, "num_experts": 4,
                                               "experts_first": 4 * chip})
            assert float(jnp.abs(y - part).max()) < 2e-5 * float(jnp.abs(whole).max())
            total, pairs = total + y, pairs + float(stats[0])
    assert pairs == 2 * 32 * 4                  # every pair on some chip, once
    assert float(jnp.abs(total - whole).max()) < 2e-5 * float(jnp.abs(whole).max())


def test_the_four_vocabulary_shares_combine_to_the_uncut_loss():
    """The loss over a quarter of the vocabulary is a smaller vocabulary's;
    what ties it to the model: the four slices' log-sum-exps, combined as a
    vocabulary-parallel head combines them, and the target's logit from the
    slice that holds it, give the uncut cross-entropy."""
    cfg = _cfg()
    V, d = 4 * cfg["vocab_size"], cfg["hidden_size"]
    r = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(r[0], (2, 64, d))
    head = jax.random.normal(r[1], (V, d)) * 0.2
    targets = jax.random.randint(r[2], (2, 64), 0, V)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ head.T, axis=-1)
        whole = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
        lses, picked = [], 0.0
        for chip in range(4):
            lo = chip * cfg["vocab_size"]
            logits = x @ head[lo:lo + cfg["vocab_size"]].T
            lses.append(jax.nn.logsumexp(logits, axis=-1))
            local = jnp.clip(targets - lo, 0, cfg["vocab_size"] - 1)
            inside = (targets >= lo) & (targets < lo + cfg["vocab_size"])
            picked = picked + jnp.where(
                inside, jnp.take_along_axis(logits, local[..., None], -1)[..., 0], 0.0)
        combined = (jax.nn.logsumexp(jnp.stack(lses), axis=0) - picked).mean()
    assert abs(float(combined - whole)) < 1e-5 * abs(float(whole))


# ----------------------------------------------------- flops by hand

def test_mellum_flops_from_shapes():
    cfg, flops = _cfg(toy=False), _load("flops")
    T = 16384
    assert flops.attention_params(cfg) == 21_233_664
    assert flops.expert_params(cfg) == 6_193_152
    assert flops.expected_pairs(cfg) == 32768.0
    # a sliding row sees min(i + 1, 1024) keys: 12% of the causal triangle
    assert flops.live_pairs(cfg, "sliding_attention") == (
        1024 * 1025 // 2 + (T - 1024) * 1024) == 16_253_440
    assert flops.live_pairs(cfg, "full_attention") == T * (T + 1) // 2 == 134_225_920
    assert abs(16_253_440 / 134_225_920 - 0.121) < 0.001
    from horovod_tpu.ops import flash_attention as fa
    for layer_type, ranges in (("sliding_attention", fa.window_ranges(2048, 1024)),
                               ("full_attention", fa.causal_ranges(2048))):
        assert flops.live_pairs({**cfg, "seq_len": 2048}, layer_type) == int(
            fa.dense_mask(ranges, 2048).sum())
    assert flops.attention_macs(cfg) == (3 * 16_253_440 + 134_225_920) * 2 * 4096
    # a token: the head 56.6 M multiply-adds beside 226.6 M in the trunk
    assert 2304 * 24576 == 56_623_104
    per_token = flops.forward_macs(cfg) / T
    assert abs(per_token / 1e6 - 283.2) < 0.1
    assert flops.projection_macs(cfg) == T * (4 * (21_233_664 + 147_456) + 56_623_104)
    assert flops.expert_macs(cfg) == 4 * 32768 * 6_193_152
    assert flops.train_flops_per_sample(cfg) == 6 * flops.forward_macs(cfg)
    assert abs(flops.train_flops_per_sample(cfg) / 1e12 - 27.84) < 0.01
    # the kernels' least: compute-bound, 68.5 ms of flash and 7.55 ms a layer
    f, b = flops.mask_flash_kernel_cost(cfg, 1)
    assert f == 18 * (3 * 16_253_440 + 134_225_920) * 4096
    assert abs(f / 197e12 * 1e3 - 68.48) < 0.01 and b / 819e9 < 0.1 * f / 197e12
    f, b = flops.moe_kernel_cost(cfg, 32768)
    assert abs(f / 197e12 * 1e3 - 7.554) < 0.001 and b / 819e9 < f / 197e12


def test_both_kernel_costs_by_hand_at_the_toy_sizes():
    """64 positions under a window of 16; 4 heads over 2 of 32; three
    sliding layers and a full one; 4 of 8 experts of width 32 at hidden
    64."""
    cfg, flops = _cfg(), _load("flops")
    window = 16 * 17 // 2 + 48 * 16
    causal = 64 * 65 // 2
    assert flops.live_pairs(cfg, "sliding_attention") == window == 904
    assert flops.live_pairs(cfg, "full_attention") == causal == 2080
    f, b = flops.mask_flash_kernel_cost(cfg, 3)
    assert f == 3 * 2 * 9 * (3 * window + causal) * 4 * 32
    q, kv, stats = 64 * 4 * 32 * 2, 64 * 2 * 32 * 2, 64 * 4 * 4
    forward = 2 * q + 2 * kv + stats            # q, o; k, v; lse
    backward = 4 * q + 4 * kv + 2 * stats       # q, o, do, dq; k, v, dk, dv; lse, delta
    assert b == 3 * 4 * (forward + backward)
    f, b = flops.moe_kernel_cost(cfg, 100)
    assert f == 2 * 11 * 100 * 64 * 32
    assert b == 4 * (4 * 3 * 64 * 32 * 2) + 3 * 100 * (2 * 64 + 3 * 32) * 2


# ----------------------------------------------------- the new readers

def _ctx(rows, steps=4, flops=None, device_ops=()):
    """A run's context whose scope table holds ``rows``: {(scope, pass):
    seconds of the traced stretch}."""
    said = []
    instructions = {f"i{k}": ["fusion", s, steps] for k, s in enumerate(rows.values())}
    where = {f"i{k}": (sc, p, "", "f32[8]") for k, (sc, p) in enumerate(rows)}
    return types.SimpleNamespace(
        config=_cfg(toy=False), flops=flops or _load("flops"), say=said.append,
        traced=types.SimpleNamespace(stamps=[0.0] * steps, global_batch=1, chips=1),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(device_ops=[["fusion", 1.0], *device_ops]),
        scope_table=scopes.Table(instructions, where, steps)), said


SWA, FULL = "hvd_forward/hvd_window_attention", "hvd_forward/hvd_attention"
ROWS = {
    (SWA, "forward"): 0.03, (SWA + "/hvd_rope", "forward"): 0.004,
    (SWA + "/hvd_rope", "recompute"): 0.004, (SWA + "/hvd_rope", "backward"): 0.008,
    (SWA + "/hvd_flash_fwd", "forward"): 0.02, (SWA + "/hvd_flash_dq", "backward"): 0.03,
    (SWA + "/hvd_flash_dkv", "backward"): 0.03, (FULL + "/hvd_rope", "forward"): 0.002,
    (FULL + "/hvd_flash_fwd", "forward"): 0.06, (FULL + "/hvd_flash_dq", "backward"): 0.08,
    (FULL + "/hvd_flash_dkv", "backward"): 0.10, (FULL, "backward"): 0.05,
    ("hvd_forward/hvd_mlp", "forward"): 1.0}
FLASH = [["hvd_flash_fwd (custom-call)", 0.08], ["hvd_flash_dq (custom-call)", 0.11],
         ["hvd_flash_dkv (custom-call)", 0.13]]
GROUPED = [["hvd_moe_gmm_gate_up (custom-call)", 0.06],
           ["hvd_moe_tgmm_down (custom-call)", 0.10]]


@pytest.mark.parametrize("metric", ["window_attention_ms", "rope_ms",
                                    "swa_flash_roofline",
                                    "swa_moe_experts_roofline"])
def test_new_trace_readers_read_what_is_there_and_nothing_otherwise(metric,
                                                                    monkeypatch):
    """Present, absent (the parent commit under these files, another
    configuration's flops, no trace at all): a reader returns None and does
    not raise."""
    from horovod_tpu import metrics
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    # what the steps counted: four layer-steps of 33,000 pairs (the readers
    # this file's delegate to are loaded anew at every call, so it is the
    # registry that is stood in for, not a function of theirs)
    families = dict(metrics.registry().to_dict())
    families["hvd_moe_routed_total"] = {"series": [
        {"labels": {"what": "pairs"}, "value": 4 * 33000.0},
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
    if metric == "window_attention_ms":
        assert value == pytest.approx((0.03 + 0.016 + 0.08) / 4 * 1e3)
    elif metric == "rope_ms":
        assert value == pytest.approx(0.018 / 4 * 1e3)
    elif metric == "swa_flash_roofline":
        f, _ = _load("flops").mask_flash_kernel_cost(ctx.config, 1)
        assert value == pytest.approx(100 * f / 197e12 * 4 / 0.32) and 0 < value < 100
        lines = " | ".join(said)
        assert "compute-bound" in lines and "hvd_flash_dkv (custom-call) 32.500" in lines
        assert "window calls 20.000, causal call 60.000" in lines
    else:
        f, _ = _load("flops").moe_kernel_cost(ctx.config, 33000.0)
        assert value == pytest.approx(100 * 4 * f / 197e12 * 4 / 0.16) and 0 < value < 100
        assert "compute-bound" in said[-1]
    if metric.startswith("swa"):        # another configuration's flops: no cost
        other, _ = _ctx(ROWS, flops=types.SimpleNamespace(),
                        device_ops=FLASH + GROUPED)
        assert read(other) is None


def test_rope_tables_is_the_programs_counter(monkeypatch):
    from horovod_tpu import metrics
    from horovod_tpu.models import hybrid, llama
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", "rope_tables")
    monkeypatch.setattr(metrics, "ACTIVE", True)
    ctx, said = _ctx({})
    before = read(ctx) or 0
    lcfg = _load("adapter").program_config(_cfg())
    layers = jax.eval_shape(lambda k: llama.init_params(lcfg, k)["layers"],
                            jax.random.key(0))
    jax.eval_shape(lambda h, ls: hybrid.layer_stack(h, ls, lcfg),
                   jax.ShapeDtypeStruct((1, 64, 64), jnp.float32), layers)
    assert read(ctx) == before + 2
    assert "attention yarn" in said[-1] and "swa default" in said[-1]
    families = metrics.registry().to_dict()
    families.pop("hvd_rope_tables_total", None)  # a program that has no such counter
    monkeypatch.setattr(metrics, "registry", lambda: types.SimpleNamespace(
        to_dict=lambda: families))
    assert read(ctx) is None


# Solar's and Phi's toys' steps as the parent commit lowers and runs them
# (8d123ba, jax 0.9.0, on the CPU): their text, first loss and first gradient
# to the bit.  models/hybrid.py, models/moe.py and LlamaConfig are those
# programs' too; a change to one changes these and states it here.
PARENTS_TOYS = {
    "solar-open2-250b": {"lowered": "45058e42c3a03eff",
                         "loss": "0x1.6432300000000p+2",
                         "grads": "0eb3e3367bed413d"},
    "phi4-mini-flash": {"lowered": "1e8b5d77c561ecdb",
                        "loss": "0x1.63bfe00000000p+2",
                        "grads": "d9c7a124c188ae65"},
}


@pytest.mark.parametrize("config", sorted(PARENTS_TOYS))
def test_accepted_toys_steps_are_the_parents_to_the_bit(hvd, config):
    cdir = bench_tree.BENCH / "configs" / config
    cfg = bench_tree.load(cdir / "config.json")
    cfg.update(cfg["toy"])
    cfg["dtype"]["compute"] = "float32"
    ref = registry.load_module(str(cdir / "reference.py"))
    prog = registry.load_module(str(cdir / "adapter.py")).build(
        cfg, ref, jax.devices()[:1], 2)
    batch = prog.place(ref.make_samples(cfg, jax.random.key(1), 2))
    state = prog.init(jax.random.key(0))
    text = prog._step.lower(*state, batch).as_text()
    assert "hvd_rope" not in text and "hvd_window_attention" not in text
    want = PARENTS_TOYS[config]
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want["lowered"]
    state, loss = prog.step(state, batch)
    assert float(loss).hex() == want["loss"]
    g = prog.first_gradient(state)
    bits = b"".join(bytes(memoryview(jax.device_get(g[k]))) for k in sorted(g))
    assert hashlib.sha256(bits).hexdigest()[:16] == want["grads"]
