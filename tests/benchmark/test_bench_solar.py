"""The ``solar-open2-250b`` configuration's files: the manifest's entries,
the cut and its count of parameters, every number of the catalog's config,
the program through the train step against the plain reference at the toy
sizes, the shares that add up to the uncut layer, the adapter's guard,
what a program without the kind says, the counts the roofline rests on by
hand, the four new readers on made-up traces, and SDAR's and Granite's toy
programs, which the trunk's new kind, the router's new scores and the
config's new fields leave as they were (Phi's is held in
test_bench_granite.py)."""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import check, registry, scopes

CONFIG = bench_tree.BENCH / "configs" / "solar-open2-250b"
CELL = "solar-open2-250b.s8192-b1.dp1"
MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
NEW_METRICS = ("kda_scan_roofline", "kda_mixer_ms", "kda_xla_call_sites",
               "moe_shared_ms")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "num_attention_heads", "num_key_value_heads", "linear_attn_config"]


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
    at = names("configs").index("solar-open2-250b")
    entry = MANIFEST["configs"][at]
    assert names("configs")[at - 1] == "granite-4.0-h-micro", "added at the end of its list"
    assert entry["reduced"] == REDUCED
    where = names("workloads").index(CELL)
    cell = MANIFEST["workloads"][where]
    assert names("workloads")[where - 1] == "granite-4.0-h-micro.s8192-b1.dp1"
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "host-fed.s8192-b1", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    first = names("per_layer").index(NEW_METRICS[0])
    assert names("per_layer")[first - 1] == "ssd_xla_call_sites"
    assert names("per_layer")[first:first + 4] == list(NEW_METRICS)
    for m in MANIFEST["per_layer"][first:first + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
    # of the accepted metrics' lists two took the new cell, after the cells
    # they had
    took = {m["name"]: m["workloads"] for m in MANIFEST["per_layer"][:first]
            if CELL in m.get("workloads", [])}
    assert list(took) == ["mlp_ms", "model_unscoped_pct"]
    for cells in took.values():
        assert cells.index(CELL) == cells.index("granite-4.0-h-micro.s8192-b1.dp1") + 1
    four = [w["name"] for w in MANIFEST["workloads"][:where + 1] if w["chips"] == 4]
    assert four == ["resnet50-synth.b128.dp4"] and where + 1 == 8 and at + 1 == 6
    loaded = registry.load_cell(str(bench_tree.BENCH), MANIFEST, CELL)
    assert loaded.traffic["per_chip_batch"] == 1 and loaded.traffic["pool_batches"] == 16


def test_config_carries_the_published_widths_and_states_its_cut():
    cfg, ref = _cfg(False), _load("reference")
    z = ref.sizes(cfg)
    assert (z["d"], z["f"], z["dh"], z["dk"], z["kc"], z["e"], z["top"],
            z["shared"]) == (4096, 1280, 128, 128, 4, 320, 8, 1)
    assert (z["h"], z["hkv"], z["hs"], z["held"]) == (8, 1, 8, 8)
    assert cfg["kda_chunk_size"] == 64 and cfg["seq_len"] == 8192
    assert cfg["reduced"] == REDUCED
    assert cfg["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608,
        "num_attention_heads": 64, "num_key_value_heads": 8,
        "linear_attn_config": {**cfg["linear_attn_config"], "num_heads": 64}}
    assert cfg["vocab_size"] * 8 == 196608 and cfg["n_routed_experts"] * 40 == 320
    assert "40 chips share each layer" in cfg["deployment"] and len(cfg["deployment"]) <= 200
    assert "deployment, in full: 40 chips share each layer: five data-parallel groups of eight" in cfg["assumed"][0]
    assert cfg["kept_layers"] == [0, 1, 2, 3] and len(cfg["gqa_layers"]) == 12
    assert ref.kept_kinds(cfg) == ["attention", "kda", "kda", "kda"]
    said = " ".join(cfg["assumed"])
    for words in ("the form G1 of arXiv 2505.06708, Qwen3-Next's",
                  "kda_use_full_proj false read as Kimi Linear's low-rank (rank head_dim) decay and gate projections",
                  "the router's score function by its family",
                  "0.00204 = 0.02 / sqrt(2 x 48 layers)", "learning rate of 1e-6"):
        assert words in said, words
    assert abs(cfg["residual_out_range"] - 0.02 / (2 * 48) ** 0.5) < 2e-6
    # the issue's arithmetic, in millions of parameters
    shapes = ref.weight_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for k, s in shapes.items() if keep(k))
    n = count(lambda k: True)
    assert n == 840_872_600 and abs(n / 1e6 - 840.7) < 0.2
    assert abs(n * 16 / 1e9 - 13.45) < 0.01 and abs(n * 12 / 1e9 - 10.09) < 0.01
    per_layer = [count(lambda k: k.startswith(f"l{i}.")) for i in range(4)]
    assert per_layer == [156_508_480] + [161_011_144] * 3
    mixer = lambda i: count(lambda k: k.startswith(f"l{i}.") and k.split(".")[1] in (
        "wqkv", "wgate", "wo", "conv_w", "f_a", "f_b", "dt_bias", "A_log",
        "b_proj", "g_a", "g_b", "o_norm"))
    assert abs(mixer(0) / 1e6 - 13.6) < 0.05 and abs(mixer(1) / 1e6 - 18.1) < 0.05
    shared_and_router = count(lambda k: k.split(".")[-1] in ("w1", "w2", "router")
                              and k.startswith("l0."))
    assert abs(shared_and_router / 1e6 - 17.04) < 0.01
    assert int(np.prod(shapes["l0.we_gate"])) * 3 // 8 == 15_728_640
    assert count(lambda k: k in ("embed", "head")) == 201_326_592
    from horovod_tpu.models import llama
    lcfg = _load("adapter").program_config(cfg)
    assert llama.count_params(lcfg) == n
    assert lcfg.layer_kinds == ("attention", "kda", "kda", "kda")
    assert (lcfg.trunk_norm, lcfg.head_dim, lcfg.ssm_heads, lcfg.ssm_state,
            lcfg.ssm_chunk, lcfg.attn_gate, lcfg.router_score,
            lcfg.n_shared_experts, lcfg.n_experts, lcfg.experts_held) == (
        "rmsnorm", 128, 8, 128, 64, True, "sigmoid", 1, 320, 8)


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` as published but the keys that are cut."""
    import json
    cfg = _cfg(False)
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
        "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 320,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(guide)]
        published = next(r for r in rows if r["name"] == "Solar-Open2-250B")["config"]
    except OSError:
        pass                # no guide beside this checkout: the copy above
    for key, value in published.items():
        if key in REDUCED:
            assert cfg[key] != value and cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # a group is copied whole; only the head count differs inside it
    lin = cfg["linear_attn_config"]
    assert {k: v for k, v in lin.items() if k != "num_heads"} == {
        k: v for k, v in published["linear_attn_config"].items() if k != "num_heads"}
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


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


def test_toy_model_through_the_train_step_follows_the_reference(hvd):
    """Loss, first gradient leaf by leaf and update of three steps through
    ``make_llama_train_step``, on seeded weights, float32."""
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    program = adapter.build(cfg, ref, jax.devices()[:1], 4)
    key = jax.random.key(11)
    got, batches, state = _three_steps(program, ref, cfg, key)
    want = check.Reference(ref, cfg, jax.devices()[:1]).run(key, batches)
    assert set(got["grad_norms"]) == set(want["grad_norms"]) == set(
        ref.weight_shapes(cfg))
    for name, (value, where) in check.compare(got, want).items():
        assert value < 5e-5, (name, value, where)
    text = program.compiled(state, program.place(batches[0])).as_text()
    for scope in ("hvd_kda_mixer", "hvd_kda_scan", "hvd_attention", "hvd_mlp",
                  "hvd_moe_route", "hvd_moe_experts", "hvd_moe_shared",
                  "hvd_head", "hvd_embed"):
        assert scope in text, scope
    # the routing statistics a step hands on: 3 layers, pairs within reason
    _, _, _, stats = program._step(*state, program.place(batches[0]))
    pairs, rows, fullest, layers = np.asarray(stats)
    assert layers == 3 and rows == pairs and 0 < fullest < pairs
    even = 3 * 4 * cfg["seq_len"] * cfg["num_experts_per_tok"] * 4 / 16
    assert 0.5 * even < pairs < 1.5 * even


def test_a_selection_bias_moves_the_choice_in_program_and_reference_alike(hvd):
    """The toy model with a non-zero bias in every layer: the program still
    follows the reference, and both differ from the unbiased model."""
    cfg, ref = _cfg(), _load("reference")
    biased = types.SimpleNamespace(**{k: getattr(ref, k) for k in dir(ref)
                                      if not k.startswith("__")})

    def make_weights(cfg_, key):
        w = ref.make_weights(cfg_, key)
        for name in w:
            if name.endswith("router_bias"):
                w[name] = 0.3 * jax.random.normal(jax.random.key(5), w[name].shape)
        return w

    biased.make_weights = make_weights
    key = jax.random.key(2)
    runs = {}
    for name, module in (("plain", ref), ("biased", biased)):
        program = _load("adapter").build(cfg, module, jax.devices()[:1], 2)
        got, batches, _ = _three_steps(program, module, cfg, key, batch=2)
        want = check.Reference(module, cfg, jax.devices()[:1]).run(key, batches)
        for number, (value, where) in check.compare(got, want).items():
            assert value < 5e-5, (name, number, value, where)
        runs[name] = got["losses"][0]
    assert abs(float(runs["plain"]) - float(runs["biased"])) > 1e-6


def _chunks_as_rows(real):
    """The scan with the carried state dropped: every chunk a row of its
    own, which starts from zero."""
    def scan(q, k, v, g, beta, chunk=64):
        rows = lambda a: a.reshape(-1, chunk, *a.shape[2:])
        return real(rows(q), rows(k), rows(v), rows(g), rows(beta),
                    chunk).reshape(v.shape)
    return scan


@pytest.mark.parametrize("fault", [None, "state dropped"])
def test_the_adapters_guard_reads_the_scan_against_the_walk(
        hvd, monkeypatch, capsys, fault):
    """``Program.init`` prints the scan's distance from the reference's
    position-by-position recurrence and stops a run whose chunks forget
    the state they were handed."""
    from horovod_tpu.ops import kda_scan as kd
    cfg, ref = _cfg(), _load("reference")
    program = _load("adapter").build(cfg, ref, jax.devices()[:1], 2)
    if fault:
        monkeypatch.setattr(kd, "kda_scan", _chunks_as_rows(kd.kda_scan))
        with pytest.raises(SystemExit, match="away from the reference's walk"):
            program.init(jax.random.key(4))
    else:
        program.init(jax.random.key(4))
    said = capsys.readouterr().out
    gap = float(said.split("check main kda_o_gap: ")[1].split()[0])
    assert (gap > 0.05) if fault else (gap < 1e-5), said
    assert f"(limit {ref.KDA_O_GAP:g}" in said
    assert "kda_o_gap" not in ref.LIMITS      # the harness knows no such number


def test_lower_precision_in_the_recurrence_moves_the_reference():
    """The control the chip reads: the decays and the carried state rounded
    to bfloat16 are another recurrence, by a few bfloat16 roundings and no
    more."""
    ref = _load("reference")
    r = jax.random.split(jax.random.key(3), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (unit(jax.random.normal(r[i], (1, 64, 4, 16))) for i in (0, 1))
    v = jax.random.normal(r[2], (1, 64, 4, 16))
    g = -jax.nn.softplus(jax.random.normal(r[3], (1, 64, 4, 16)) - 2)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(r[4], (1, 64, 4)))
    sound = ref.recurrence(q, k, v, g, beta)
    low = ref.recurrence(q, k, v, g, beta, jnp.bfloat16)
    gap = float(jnp.abs(low - sound).max() / jnp.abs(sound).max())
    assert 1e-4 < gap < 5e-2


def test_a_program_without_the_kind_says_so_at_once(monkeypatch):
    """The parent commit under these files: a ValueError from the
    configuration's kinds, before anything is built."""
    from horovod_tpu.models import hybrid
    adapter = _load("adapter")
    monkeypatch.setattr(hybrid, "KINDS", ("mamba", "window", "full", "gmu",
                                          "cross", "mamba2", "attention"))
    with pytest.raises(ValueError, match=r"has no \['kda'\]"):
        adapter.program_config(_cfg())
    monkeypatch.undo()
    with pytest.raises(ValueError, match="maps the published solar_open2 keys"):
        adapter.program_config(_cfg(use_gqa_gate=False))


def test_weights_are_made_as_the_configuration_says():
    cfg, ref = _cfg(), _load("reference")
    w = ref.make_weights(cfg, jax.random.key(2))
    assert set(w) == set(ref.weight_shapes(cfg))
    assert all(w[k].shape == s for k, s in ref.weight_shapes(cfg).items())
    A = np.exp(np.asarray(w["l1.A_log"]))
    assert (1 <= A).all() and (A <= 16).all()
    for leaf in ("l0.norm1_w", "l2.norm2_w", "l1.o_norm", "final_norm_w"):
        assert (np.asarray(w[leaf]) == 1).all()
    assert not np.asarray(w["l0.router_bias"]).any()
    step = np.asarray(jax.nn.softplus(w["l1.dt_bias"]))
    assert cfg["dt_min"] * 0.999 <= step.min() and step.max() <= cfg["dt_max"] * 1.001
    assert np.abs(w["l1.conv_w"]).max() <= 4 ** -0.5
    assert abs(float(w["l0.wqkv"].std()) - cfg["initializer_range"]) < 2e-3
    assert abs(float(w["head"].std()) - cfg["initializer_range"]) < 2e-3
    assert abs(float(w["embed"].std()) - cfg["embedding_range"]) < 0.05
    for leaf in ("l0.wo", "l1.wo", "l2.w2", "l0.we_down"):
        assert abs(float(w[leaf].std()) - cfg["residual_out_range"]) < 3e-4
    assert "l0.conv_w" not in w and "l1.wgate" not in w and "l0.f_a" not in w
    tokens, targets = ref.make_samples(cfg, jax.random.key(3), 16)
    assert tokens.shape == targets.shape == (16, cfg["seq_len"])
    assert (tokens[:, 1:] == targets[:, :-1]).all() and tokens.max() < cfg["vocab_size"]


# ------------------------------------------------- the shares add up

def test_the_forty_expert_shares_and_the_shared_expert_add_up(hvd):
    """40 chips of one routed expert each (the program's layer, told which
    expert it holds) plus the shared expert once are the uncut reference's
    feed-forward."""
    from horovod_tpu.models import llama, moe
    ref = _load("reference")
    cfg = _cfg(router_outputs=40, n_routed_experts=40, num_experts_per_tok=4)
    z = ref.sizes(cfg)
    r = jax.random.split(jax.random.key(7), 8)
    d, f = z["d"], z["f"]
    lw = {"router": jax.random.normal(r[0], (d, 40)),
          "router_bias": 0.2 * jax.random.normal(r[1], (40,)),
          "we_gate": jax.random.normal(r[2], (40, d, f)) * d ** -0.5,
          "we_up": jax.random.normal(r[3], (40, d, f)) * d ** -0.5,
          "we_down": jax.random.normal(r[4], (40, f, d)) * f ** -0.5,
          "w1": jax.random.normal(r[5], (d, 2 * f)) * d ** -0.5,
          "w2": jax.random.normal(r[6], (f, d)) * f ** -0.5}
    x = jax.random.normal(r[7], (2, 32, d))
    with jax.default_matmul_precision("highest"):
        whole = ref.feed_forward(x, lw, cfg)
        total = moe.shared_expert(x, lw["w1"], lw["w2"])
        pairs = 0
        for chip in range(40):
            lcfg = llama.LlamaConfig(
                d_model=d, d_ff=f, n_experts=40, expert_top_k=4,
                moe_dispatch="dropless", experts_held=1, experts_first=chip,
                router_score="sigmoid", dtype=jnp.float32)
            share = {**lw, **{n: lw[n][chip:chip + 1]
                              for n in ("we_gate", "we_up", "we_down")}}
            y, stats = moe.dropless_moe_layer(x, share, lcfg, llama.ParallelSpec())
            total, pairs = total + y, pairs + float(stats[0])
    assert pairs == 2 * 32 * 4                  # every pair on some chip, once
    assert float(jnp.abs(total - whole).max()) < 2e-5 * float(jnp.abs(whole).max())


@pytest.mark.parametrize("kind", ["kda", "attention"])
def test_the_eight_head_shares_partial_outputs_add_up(hvd, kind):
    """8 chips with an eighth of the heads each (the program's mixer on the
    share's columns of every projection and rows of ``wo``) sum to the
    uncut reference's mixer."""
    from horovod_tpu.models import hybrid, llama
    ref = _load("reference")
    lin = {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 8,
           "num_kv_heads": None}
    cfg = _cfg(linear_attn_config=lin, num_attention_heads=16,
               num_key_value_heads=8, head_dim=16)
    z = ref.sizes(cfg)
    d, dk, dh = z["d"], z["dk"], z["dh"]
    shapes = {k.split(".", 1)[1]: s for k, s in ref.weight_shapes(
        {**cfg, "gqa_layers": [0] if kind == "attention" else [], "kept_layers": [0]}
    ).items() if k.startswith("l0.")}
    r = jax.random.key(9)
    lw = {n: (jnp.ones(s) if n == "o_norm" else
              jax.random.normal(jax.random.fold_in(r, i), s) * 0.3)
          for i, (n, s) in enumerate(shapes.items())}
    u = jax.random.normal(jax.random.fold_in(r, 99), (2, 32, d))
    cols = lambda w, width, lo, n: w[..., lo * width:(lo + n) * width]
    with jax.default_matmul_precision("highest"):
        if kind == "kda":
            whole = ref._kda(u, lw, cfg, lambda a: a, jnp.float32)
        else:
            whole = ref._attention(u, lw, cfg, lambda a: a)
        total = 0
        for chip in range(8):
            if kind == "kda":
                q, k, v = jnp.split(lw["wqkv"], 3, axis=-1)
                cq, ck, cv = jnp.split(lw["conv_w"], 3, axis=-1)
                share = {
                    "wqkv": jnp.concatenate([cols(a, dk, chip, 1) for a in (q, k, v)], -1),
                    "conv_w": jnp.concatenate([cols(a, dk, chip, 1) for a in (cq, ck, cv)], -1),
                    "f_a": lw["f_a"], "f_b": cols(lw["f_b"], dk, chip, 1),
                    "dt_bias": cols(lw["dt_bias"], dk, chip, 1),
                    "A_log": lw["A_log"][chip:chip + 1],
                    "b_proj": lw["b_proj"][:, chip:chip + 1], "g_a": lw["g_a"],
                    "g_b": cols(lw["g_b"], dk, chip, 1), "o_norm": lw["o_norm"],
                    "wo": lw["wo"][chip * dk:(chip + 1) * dk]}
                lcfg = llama.LlamaConfig(
                    d_model=d, n_heads=2, n_kv_heads=1, head_dim=dh,
                    layer_kinds=("kda",), ssm_heads=1, ssm_state=dk, ssm_inner=dk,
                    ssm_conv=4, ssm_chunk=16, trunk_norm="rmsnorm",
                    norm_eps=cfg["rms_norm_eps"], dtype=jnp.float32)
                total = total + hybrid._kda(u, share, lcfg)
            else:
                q, k, v = jnp.split(lw["wqkv"], (16 * dh, 24 * dh), axis=-1)
                share = {"wqkv": jnp.concatenate(
                    [cols(q, dh, 2 * chip, 2), cols(k, dh, chip, 1),
                     cols(v, dh, chip, 1)], -1),
                    "wgate": cols(lw["wgate"], dh, 2 * chip, 2),
                    "wo": lw["wo"][2 * chip * dh:(2 * chip + 2) * dh]}
                part = {**cfg, "num_attention_heads": 2, "num_key_value_heads": 1}
                total = total + ref._attention(u, share, part, lambda a: a)
    assert float(jnp.abs(total - whole).max()) < 5e-5 * float(jnp.abs(whole).max())


# ----------------------------------------------------- flops by hand

def test_solar_flops_from_shapes():
    cfg, flops = _cfg(toy=False), _load("flops")
    T = 8192
    # (the mixer's matrices: the convolution, dt_bias, A_log and the norm are none)
    assert flops.mixer_params(cfg, "kda") == 18_120_704
    assert flops.mixer_params(cfg, "attention") == 13_631_488
    assert flops.expert_params(cfg) == 15_728_640
    assert flops.expected_pairs(cfg) == pytest.approx(1638.4)
    assert flops.live_pairs(cfg) == 33_558_528
    assert flops.attention_macs(cfg) == 33_558_528 * 8 * 2 * 128
    assert flops.recurrence_macs(cfg) == 3 * T * 8 * 3 * 128 * 128
    # the head: 100.7 M multiply-adds a token of about 250 M
    per_token = flops.forward_macs(cfg) / T
    assert 4096 * 24576 == 100_663_296 and 230e6 < per_token < 270e6
    assert flops.train_flops_per_sample(cfg) == 6 * flops.forward_macs(cfg)
    # 12.73 TFLOP a row; with the forward rerun under remat, 4/3 of it: the
    # issue's "about 16 TFLOP of products"
    assert abs(flops.train_flops_per_sample(cfg) / 1e12 - 12.73) < 0.01
    # the chunked form: 5.77 M multiply-adds a chunk and head
    assert flops.kda_chunk_macs(cfg) == 4 * 64 * 64 * 128 + 3 * 64 * 128 * 128
    f, b = flops.kda_kernel_cost(cfg, 1)
    assert f == 3 * 4 * 2 * flops.kda_chunk_macs(cfg) * 8 * 128
    assert f == 2 * flops.kda_kernel_cost({**cfg, "remat": False}, 1)[0] * 4 // 6
    assert abs(f / 1e9 - 128.85) < 0.01 and abs(b / 3 / 1e6 - 588.3) < 0.1


def test_kda_kernel_cost_by_hand_at_the_toy_sizes():
    """64 positions in 4 chunks of 16; 4 heads of 16; two kda layers,
    rerun under remat."""
    cfg, flops = _cfg(), _load("flops")
    c, k, hs = 16, 16, 4
    chunk = 2 * c * c * k + c * c * k + c * c * k + 3 * c * k * k
    assert flops.kda_chunk_macs(cfg) == chunk == 7 * 4096
    f, b = flops.kda_kernel_cost(cfg, 3)
    assert f == 3 * 2 * (2 * chunk) * hs * 4 * (2 + 2)
    wide, g, beta, st = 64 * 4 * 16 * 2, 64 * 4 * 16 * 4, 64 * 4 * 4, 4 * 4 * 16 * 16 * 4
    forward = 3 * wide + g + beta + wide + st
    backward = (3 * wide + g + beta + st + wide) + (3 * wide + g + beta)
    assert b == 3 * 2 * (2 * forward + backward)


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


SCAN = "hvd_forward/hvd_kda_mixer/hvd_kda_scan"


@pytest.mark.parametrize("metric", ["kda_scan_roofline", "kda_mixer_ms",
                                    "moe_shared_ms"])
def test_new_trace_readers_read_what_is_there_and_nothing_otherwise(metric):
    """Present, absent (the parent commit under these files, another
    configuration's flops, no trace at all) and zero time: a reader
    returns None and does not raise."""
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    absent, _ = _ctx({("hvd_forward/hvd_mlp", "forward"): 1.0})
    assert read(absent) is None
    zero, _ = _ctx({(SCAN, "forward"): 0.0, ("hvd_forward/hvd_mlp", "forward"): 1.0,
                    ("hvd_forward/hvd_mlp/hvd_moe_shared", "forward"): 0.0})
    assert read(zero) is None
    untraced = types.SimpleNamespace(**{**vars(absent), "trace": None,
                                        "scope_table": None})
    assert read(untraced) is None
    ctx, said = _ctx({
        (SCAN + "/hvd_kda_chunk_fwd", "forward"): 0.02,
        (SCAN + "/hvd_kda_chunk_fwd", "recompute"): 0.02,
        (SCAN + "/hvd_kda_chunk_bwd", "backward"): 0.06,
        (SCAN, "backward"): 0.02,
        ("hvd_forward/hvd_kda_mixer", "forward"): 0.08,
        ("hvd_forward/hvd_mlp/hvd_moe_shared", "forward"): 0.01,
        ("hvd_forward/hvd_mlp/hvd_moe_shared", "backward"): 0.03,
        ("hvd_forward/hvd_mlp", "forward"): 1.0})
    value = read(ctx)
    if metric == "kda_mixer_ms":
        assert value == pytest.approx(0.2 / 4 * 1e3)
        return
    if metric == "moe_shared_ms":
        assert value == pytest.approx(0.04 / 4 * 1e3)
        return
    f, b = _load("flops").kda_kernel_cost(ctx.config, 1)
    least = max(f / 197e12, b / 819e9)
    assert value == pytest.approx(100 * least * 4 / 0.12) and 0 < value < 100
    assert "memory-bound" in said[-1] and "hvd_kda_chunk_bwd backward 15.000" in said[-1]
    assert "xla backward 5.000" in said[-1]
    other, _ = _ctx({(SCAN, "forward"): 0.1}, flops=types.SimpleNamespace())
    assert read(other) is None


def test_xla_call_sites_are_the_counters_xla_series(monkeypatch):
    from horovod_tpu import metrics
    from horovod_tpu.ops import kda_scan as kd
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", "kda_xla_call_sites")
    ctx, _ = _ctx({})
    before = read(ctx)
    if metrics.ACTIVE and before is None:       # no scan built yet in this process
        before = 0
    ones = lambda *s: jnp.ones(s)
    jax.jit(lambda q: kd.kda_scan(q, ones(1, 16, 2, 8), ones(1, 16, 2, 8),
                                  -ones(1, 16, 2, 8), ones(1, 16, 2), 16))(
        ones(1, 16, 2, 8))
    if metrics.ACTIVE:
        assert read(ctx) == before + 1
    families = metrics.registry().to_dict()
    families.pop("hvd_kda_scan_total", None)    # a program that has no such counter
    monkeypatch.setattr(metrics, "registry", lambda: types.SimpleNamespace(
        to_dict=lambda: families))
    assert read(ctx) is None


# The SDAR and Granite toys' steps as the parent commit lowers and runs them
# (ba577a5, jax 0.9.0, on the CPU): their text, first loss and first gradient
# to the bit.  models/moe.py, models/hybrid.py and LlamaConfig are those
# programs' too; a change to one changes these and states it here.
PARENTS_TOYS = {
    "sdar-30b-a3b": {"lowered": "3e1d2e5bf85f60e6", "loss": "0x1.6372160000000p+2",
                     "grads": "c7367d80dc8af387"},
    "granite-4.0-h-micro": {"lowered": "51f7fe4f5a38d1a7",
                            "loss": "0x1.62d20c0000000p+2",
                            "grads": "473d2a2a7eacc209"},
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
    assert "hvd_kda" not in text and "hvd_moe_shared" not in text
    want = PARENTS_TOYS[config]
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want["lowered"]
    state, loss = prog.step(state, batch)
    assert float(loss).hex() == want["loss"]
    g = prog.first_gradient(state)
    bits = b"".join(bytes(memoryview(jax.device_get(g[k]))) for k in sorted(g))
    assert hashlib.sha256(bits).hexdigest()[:16] == want["grads"]
