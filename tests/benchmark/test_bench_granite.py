"""The ``granite-4.0-h-micro`` configuration's files: the manifest's
entries, the cut and its count of parameters, the program through the
train step against the plain reference at the toy sizes, what a program
without the kinds says, the counts the rooflines rest on by hand, the
three new readers on made-up traces, and the Phi toy's program, which the
trunk's new kinds and the config's new fields leave as it was."""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import check, registry, scopes

CONFIG = bench_tree.BENCH / "configs" / "granite-4.0-h-micro"
CELL = "granite-4.0-h-micro.s8192-b1.dp1"
MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
NEW_METRICS = ("ssd_scan_roofline", "ssd_mixer_ms", "ssd_xla_call_sites")


def _load(name):
    return registry.load_module(str(CONFIG / f"{name}.py"))


def _cfg(toy=True):
    cfg = bench_tree.load(CONFIG / "config.json")
    if toy:
        cfg.update(cfg["toy"])
        cfg["dtype"]["compute"] = "float32"
    return cfg


def test_manifest_names_the_configuration_its_cell_and_its_metrics():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "granite-4.0-h-micro")
    assert entry == MANIFEST["configs"][-1], "added at the end of its list"
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    cell = MANIFEST["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "granite-4.0-h-micro", "host-fed.s8192-b1", 1)
    assert [m["name"] for m in MANIFEST["per_layer"][-3:]] == list(NEW_METRICS)
    for m in MANIFEST["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
    # of the accepted metrics' lists two took the new cell, at their ends:
    # test_bench_model_scopes.py holds every configuration to ``hvd_forward``
    # (model_unscoped_pct) and one sublayer's scope beside it, no more
    took = [m["name"] for m in MANIFEST["per_layer"][:-3]
            if CELL in m.get("workloads", [])]
    assert took == ["mlp_ms", "model_unscoped_pct"]
    assert all(m["workloads"][-1] == CELL for m in MANIFEST["per_layer"]
               if m["name"] in took)
    loaded = registry.load_cell(str(bench_tree.BENCH), MANIFEST, CELL)
    assert loaded.traffic["per_chip_batch"] == 1 and loaded.traffic["pool_batches"] == 16


def test_config_carries_the_published_widths_and_states_its_cut():
    cfg, ref = _cfg(False), _load("reference")
    z = ref.sizes(cfg)
    assert (z["d"], z["f"], z["h"], z["hkv"], z["dh"]) == (2048, 8192, 32, 8, 64)
    assert (z["hs"], z["p"], z["di"], z["g"], z["n"], z["kc"], z["conv"]) == (
        64, 64, 4096, 1, 128, 4, 4352)
    assert cfg["mamba_chunk_size"] == 256 and cfg["seq_len"] == 8192
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["attention_multiplier"], cfg["logits_scaling"]) == (12, 0.22, 0.015625, 8)
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert cfg["vocab_size"] * 8 == 100352 and "8 chips" in cfg["deployment"]
    assert len(cfg["layer_types"]) == 40 and cfg["kept_layers"] == list(range(10))
    assert [i for i, k in enumerate(cfg["layer_types"]) if k == "attention"] == [5, 15, 25, 35]
    assert ref.kept_kinds(cfg) == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    shapes = ref.weight_shapes(cfg)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == 772_160_448 and abs(n * 16 / 1e9 - 12.35) < 0.01
    per_layer = [sum(int(np.prod(s)) for k, s in shapes.items()
                     if k.startswith(f"l{i}.")) for i in range(10)]
    assert per_layer == [76_182_976] * 5 + [60_821_504] + [76_182_976] * 4
    assert shapes["embed"] == (12544, 2048) and shapes["l0.in_proj"] == (2048, 8512)
    from horovod_tpu.models import llama
    lcfg = _load("adapter").program_config(cfg)
    assert llama.count_params(lcfg) == n
    assert lcfg.layer_kinds == ("mamba2",) * 5 + ("attention",) + ("mamba2",) * 4
    assert (lcfg.trunk_norm, lcfg.head_dim, lcfg.ssm_heads, lcfg.ssm_chunk) == (
        "rmsnorm", 64, 64, 256)


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The source's keys as published but the two that are cut."""
    cfg = _cfg(False)
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "shared_intermediate_size": 8192}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["normalization_function"], cfg["position_embedding_type"],
            cfg["model_type"]) == ("rmsnorm", "nope", "granitemoehybrid")


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
    text = program.compiled(state, program.place(batches[0])).as_text()
    for scope in ("hvd_ssd_mixer", "hvd_ssd_scan", "hvd_attention", "hvd_mlp",
                  "hvd_head", "hvd_embed"):
        assert scope in text, scope


def _chunks_as_rows(real):
    """The scan with the carried state dropped: every chunk a row of its
    own, which starts from zero."""
    def scan(x, delta, A, B, C, D, chunk=256):
        rows = lambda a: a.reshape(-1, chunk, *a.shape[2:])
        return real(rows(x), rows(delta), A, rows(B), rows(C), D,
                    chunk).reshape(x.shape)
    return scan


@pytest.mark.parametrize("fault", [None, "state dropped"])
def test_the_adapters_guard_reads_the_scan_against_the_walk(
        hvd, monkeypatch, capsys, fault):
    """``Program.init`` prints the scan's distance from the reference's
    position-by-position recurrence and stops a run whose chunks forget
    the state they were handed."""
    from horovod_tpu.ops import ssd_scan as sd
    cfg, ref = _cfg(), _load("reference")
    program = _load("adapter").build(cfg, ref, jax.devices()[:1], 2)
    if fault:
        monkeypatch.setattr(sd, "ssd_scan", _chunks_as_rows(sd.ssd_scan))
        with pytest.raises(SystemExit, match="away from the reference's walk"):
            program.init(jax.random.key(4))
    else:
        program.init(jax.random.key(4))
    said = capsys.readouterr().out
    gap = float(said.split("check main scan_y_gap: ")[1].split()[0])
    assert (gap > 0.1) if fault else (gap < 1e-5), said
    assert f"(limit {ref.SCAN_Y_GAP:g}" in said
    assert "scan_y_gap" not in ref.LIMITS      # the harness knows no such number


def test_lower_precision_in_the_recurrence_moves_the_reference():
    """The control the chip reads: the decay's sums and exponentials and
    the carried state rounded to bfloat16 are another recurrence, by a
    few bfloat16 roundings and no more."""
    ref = _load("reference")
    k = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(k[0], (1, 64, 8, 16))
    delta = jax.nn.softplus(jax.random.normal(k[1], (1, 64, 8)) - 2)
    A = -jnp.exp(jax.random.normal(k[2], (8,)))
    Bm, Cm = (jax.random.normal(k[i], (1, 64, 1, 16)) for i in (3, 4))
    sound = ref.recurrence(x, delta, A, Bm, Cm, jnp.ones((8,)))
    low = ref.recurrence(x, delta, A, Bm, Cm, jnp.ones((8,)), jnp.bfloat16)
    gap = float(jnp.abs(low - sound).max() / jnp.abs(sound).max())
    assert 1e-4 < gap < 3e-2


def test_a_program_without_the_kinds_says_so_at_once(monkeypatch):
    """The parent commit under these files: a ValueError from the
    configuration's kinds, before anything is built."""
    from horovod_tpu.models import hybrid
    adapter = _load("adapter")
    monkeypatch.setattr(hybrid, "KINDS", ("mamba", "window", "full", "gmu", "cross"))
    with pytest.raises(ValueError, match="has no .*attention.*mamba2"):
        adapter.program_config(_cfg())


def test_weights_are_made_as_the_configuration_says():
    cfg, ref = _cfg(), _load("reference")
    w = ref.make_weights(cfg, jax.random.key(2))
    assert set(w) == set(ref.weight_shapes(cfg))
    assert all(w[k].shape == s for k, s in ref.weight_shapes(cfg).items())
    A = np.exp(np.asarray(w["l0.A_log"]))
    assert (1 <= A).all() and (A <= 16).all() and A.std() > 1
    assert (np.asarray(w["l2.D"]) == 1).all() and (np.asarray(w["l0.gate_norm"]) == 1).all()
    step = np.asarray(jax.nn.softplus(w["l0.dt_bias"]))
    assert cfg["dt_min"] * 0.999 <= step.min() and step.max() <= cfg["dt_max"] * 1.001
    assert np.abs(w["l0.conv_w"]).max() <= cfg["mamba_d_conv"] ** -0.5
    assert abs(float(w["l1.wqkv"].std()) - cfg["initializer_range"]) < 2e-3
    assert abs(float(w["embed"].std()) - cfg["initializer_range"]) < 2e-3
    for leaf in ("l0.out_proj", "l1.wo", "l2.w2"):
        assert abs(float(w[leaf].std()) - cfg["residual_out_range"]) < 3e-4
    assert abs(cfg["residual_out_range"] - 0.02 / (2 * 40) ** 0.5) < 1e-6
    assert "l1.in_proj" not in w and "l0.wqkv" not in w and "l0.norm1_b" not in w
    tokens, targets = ref.make_samples(cfg, jax.random.key(3), 16)
    assert tokens.shape == targets.shape == (16, cfg["seq_len"])
    assert (tokens[:, 1:] == targets[:, :-1]).all() and tokens.max() < cfg["vocab_size"]


def test_granite_flops_from_shapes():
    cfg, flops = _cfg(toy=False), _load("flops")
    # the issue's arithmetic: a mamba layer's in_proj and out_proj 423 GFLOP
    # forward, the MLP 824, the attention layer 172 + 275, the head 421
    T = 8192
    assert abs(2 * T * flops.mixer_params(cfg, "mamba") / 1e9 - 423) < 1
    assert abs(2 * T * 3 * 2048 * 8192 / 1e9 - 824.6) < 1
    assert abs(2 * T * flops.mixer_params(cfg, "attention") / 1e9 - 172) < 1
    assert flops.live_pairs(cfg) == 33_558_528
    assert abs(2 * flops.attention_macs(cfg) / 1e9 - 275) < 1
    assert abs(2 * T * 2048 * 12544 / 1e9 - 421) < 1
    assert flops.recurrence_macs(cfg) == 9 * T * 64 * 2 * 64 * 128
    assert flops.train_flops_per_sample(cfg) == 6 * flops.forward_macs(cfg)
    assert abs(flops.train_flops_per_sample(cfg) / 1e12 - 39.23) < 0.01
    # the chunked form: 35 GFLOP a layer forward with C B^T once a group
    assert abs(2 * flops.ssd_chunk_macs(cfg) * 32 / 1e9 - 34.9) < 0.05
    f, b = flops.ssd_kernel_cost(cfg, 1)
    assert f == 9 * 4 * 2 * flops.ssd_chunk_macs(cfg) * 32
    assert f == 2 * flops.ssd_kernel_cost({**cfg, "remat": False}, 1)[0] * 4 // 6
    assert abs(b / 9 / 1e6 - 696.3) < 0.1


def test_ssd_kernel_cost_by_hand_at_the_toy_sizes():
    """64 positions in 4 chunks of 16; 8 heads of 16 over one group of 16
    states; two mamba layers, rerun under remat."""
    cfg, flops = _cfg(), _load("flops")
    q, p, n, hs = 16, 16, 16, 8
    chunk = q * q * n + hs * (q * q * p + q * p * n + q * n * p)
    assert flops.ssd_chunk_macs(cfg) == chunk == 4096 + 8 * 3 * 4096
    f, b = flops.ssd_kernel_cost(cfg, 3)
    assert f == 3 * 2 * (2 * chunk) * 4 * (2 + 2)
    x, bc, dl, st = 64 * 8 * 16 * 2, 2 * 64 * 16 * 2, 64 * 8 * 4, 4 * 8 * 16 * 16 * 4
    forward = x + bc + dl + x + st + 2 * 8 * 4
    backward = (x + bc + dl + st + x) + (x + dl + bc) + 4 * 8 * 4
    assert b == 3 * 2 * (2 * forward + backward)


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


SCAN = "hvd_forward/hvd_ssd_mixer/hvd_ssd_scan"


@pytest.mark.parametrize("metric", NEW_METRICS[:2])
def test_new_trace_readers_read_what_is_there_and_nothing_otherwise(metric):
    """Present, absent (the parent commit under these files, another
    configuration's flops, no trace at all) and zero time: a reader
    returns None and does not raise."""
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    absent, _ = _ctx({("hvd_forward/hvd_mlp", "forward"): 1.0})
    assert read(absent) is None
    zero, _ = _ctx({(SCAN, "forward"): 0.0, ("hvd_forward/hvd_mlp", "forward"): 1.0})
    assert read(zero) is None
    untraced = types.SimpleNamespace(**{**vars(absent), "trace": None,
                                        "scope_table": None})
    assert read(untraced) is None
    ctx, said = _ctx({
        (SCAN + "/hvd_ssd_chunk_fwd", "forward"): 0.02,
        (SCAN + "/hvd_ssd_chunk_fwd", "recompute"): 0.02,
        (SCAN + "/hvd_ssd_chunk_bwd", "backward"): 0.06,
        (SCAN, "backward"): 0.02,
        ("hvd_forward/hvd_ssd_mixer", "forward"): 0.08,
        ("hvd_forward/hvd_mlp", "forward"): 1.0})
    value = read(ctx)
    if metric == "ssd_mixer_ms":
        assert value == pytest.approx(0.2 / 4 * 1e3)
        return
    f, b = _load("flops").ssd_kernel_cost(ctx.config, 1)
    least = max(f / 197e12, b / 819e9)
    assert value == pytest.approx(100 * least * 4 / 0.12) and 0 < value < 100
    assert "memory-bound" in said[-1] and "hvd_ssd_chunk_bwd backward 15.000" in said[-1]
    assert "xla backward 5.000" in said[-1]
    other, _ = _ctx({(SCAN, "forward"): 0.1}, flops=types.SimpleNamespace())
    assert read(other) is None


def test_xla_call_sites_are_the_counters_xla_series(monkeypatch):
    from horovod_tpu import metrics
    from horovod_tpu.ops import ssd_scan as sd
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", "ssd_xla_call_sites")
    ctx, _ = _ctx({})
    before = read(ctx)
    assert before is not None and before >= 0
    x = jnp.ones((1, 16, 8, 16))
    jax.jit(lambda x: sd.ssd_scan(x, jnp.ones((1, 16, 8)), -jnp.ones((8,)),
                                  jnp.ones((1, 16, 1, 16)), jnp.ones((1, 16, 1, 16)),
                                  jnp.ones((8,)), 16))(x)
    if metrics.ACTIVE:
        assert read(ctx) == before + 1
    families = metrics.registry().to_dict()
    del families["hvd_ssd_kernel_total"]    # a program that has no such counter
    monkeypatch.setattr(metrics, "registry", lambda: types.SimpleNamespace(
        to_dict=lambda: families))
    assert read(ctx) is None


# The Phi toy's step as the parent commit lowers and runs it (91ab21f, jax
# 0.9.0, on the CPU): its text, its first loss and its first gradient to the
# bit.  models/hybrid.py and LlamaConfig are that program's too; a change to
# it changes these and states it here.
PHI_TOY = {"lowered": "1e8b5d77c561ecdb", "loss": "0x1.63bfe00000000p+2",
           "grads": "d9c7a124c188ae65"}


def test_phi_toys_step_is_the_parents_to_the_bit(hvd):
    cdir = bench_tree.BENCH / "configs" / "phi4-mini-flash"
    cfg = bench_tree.load(cdir / "config.json")
    cfg.update(cfg["toy"])
    cfg["dtype"]["compute"] = "float32"
    ref = registry.load_module(str(cdir / "reference.py"))
    prog = registry.load_module(str(cdir / "adapter.py")).build(
        cfg, ref, jax.devices()[:1], 2)
    batch = prog.place(ref.make_samples(cfg, jax.random.key(1), 2))
    state = prog.init(jax.random.key(0))
    text = prog._step.lower(*state, batch).as_text()
    assert "hvd_ssd" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PHI_TOY["lowered"]
    state, loss = prog.step(state, batch)
    assert float(loss).hex() == PHI_TOY["loss"]
    g = prog.first_gradient(state)
    bits = b"".join(bytes(memoryview(jax.device_get(g[k]))) for k in sorted(g))
    assert hashlib.sha256(bits).hexdigest()[:16] == PHI_TOY["grads"]
