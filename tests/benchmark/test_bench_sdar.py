"""The ``sdar-30b-a3b`` configuration's files: the program against the
plain reference at the toy sizes, the chip's share of the experts against
the uncut layer, the mask the adapter hands the kernels against the
reference's, and the counts that ``mfu_pct`` and the rooflines rest on."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tree
from harness import check, registry

CONFIG = bench_tree.BENCH / "configs" / "sdar-30b-a3b"
CELL = "sdar-30b-a3b.s4096-b2.dp1"


def _load(name):
    return registry.load_module(str(CONFIG / f"{name}.py"))


def _cfg(toy=True):
    cfg = bench_tree.load(CONFIG / "config.json")
    if toy:
        cfg.update(cfg["toy"])
        cfg["dtype"]["compute"] = "float32"
    return cfg


def test_toy_model_through_the_train_step_follows_the_reference(hvd):
    """Loss, first gradient and update of three steps through
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
        assert value < 2e-5, (name, value, where)
    # every layer routed pairs to the experts held, and computed them all
    stats = np.asarray(program._stats[-1])
    assert stats[0] == stats[1] > 0 and stats[3] == cfg["num_hidden_layers"]


def test_the_shares_of_one_expert_layer_add_up_to_the_uncut_layer():
    """Each chip's share (its experts of the router's outputs, through the
    program's layer) summed over the chips is the reference's layer with
    every expert held."""
    from horovod_tpu.models import llama, moe
    cfg, ref, adapter = _cfg(), _load("reference"), _load("adapter")
    shares = cfg["router_outputs"] // cfg["num_experts"]
    uncut = {**cfg, "num_experts": cfg["router_outputs"], "num_hidden_layers": 1}
    w = ref.make_weights(uncut, jax.random.key(5))
    lw = {n: w[f"l0.{n}"] * (0.0 if n == "wo" else 1.0) for n in ref._LAYER}
    L = cfg["seq_len"]
    x = jax.random.normal(jax.random.key(6), (2, 2 * L, cfg["hidden_size"]))
    positions = jnp.tile(jnp.arange(L), (2, 2))
    with jax.default_matmul_precision("highest"):
        want = ref._layer(lw, x, positions, uncut, lambda a: a) - x
    pre = ref._rmsnorm(x, lw["mlp_norm"], cfg["rms_norm_eps"])
    total, pairs = 0.0, 0.0
    for s in range(shares):
        lcfg = adapter.program_config({**cfg, "experts_first": s * cfg["num_experts"]})
        held = slice(s * cfg["num_experts"], (s + 1) * cfg["num_experts"])
        lp = {"router": lw["router"], **{n: lw[n][held] for n in
                                         ("we_gate", "we_up", "we_down")}}
        y, stats = moe.dropless_moe_layer(pre, lp, lcfg, llama.ParallelSpec())
        total, pairs = total + y, pairs + stats[0]
    np.testing.assert_allclose(total, want, atol=2e-6, rtol=2e-5)
    assert pairs == 2 * 2 * L * cfg["num_experts_per_tok"]   # every pair, once


def test_adapters_key_ranges_are_the_references_mask():
    from horovod_tpu.ops import flash_attention as fa
    ref, adapter = _load("reference"), _load("adapter")
    for L, bk in ((64, 4), (48, 8), (512, 4)):
        assert (fa.dense_mask(adapter.mask_ranges(L, bk), 2 * L)
                == ref.attention_mask(L, bk)).all()


def test_samples_are_noised_as_the_objective_says():
    cfg, ref = _cfg(), _load("reference")
    tokens, positions, targets, weights = ref.make_samples(
        cfg, jax.random.key(3), 16)
    L, bk, mask_id = cfg["seq_len"], cfg["block_length"], cfg["vocab_size"] - 1
    xt, x0 = tokens[:, :L], tokens[:, L:]
    assert (x0 == targets).all() and x0.max() < mask_id
    masked = xt == mask_id
    assert (xt[~masked] == x0[~masked]).all()
    m = masked.reshape(16, L // bk, bk).sum(-1)
    assert m.min() >= 1 and set(np.unique(m)) == set(range(1, bk + 1))
    assert np.allclose(weights, np.where(masked, bk / np.repeat(m, bk, 1), 0))
    assert np.allclose(weights.reshape(16, -1, bk).sum(-1), bk)   # Bk a block
    assert (positions[:, :L] == positions[:, L:]).all()
    assert (positions[0, :L] == np.arange(L)).all()
    again = ref.make_samples(cfg, jax.random.key(3), 16)
    assert all((a == b).all() for a, b in zip(again, (tokens, positions,
                                                      targets, weights)))


def test_weights_are_made_as_the_configuration_says():
    cfg, ref = _cfg(), _load("reference")
    w = ref.make_weights(cfg, jax.random.key(2))
    assert set(w) == set(ref.weight_shapes(cfg))
    assert all(w[k].shape == s for k, s in ref.weight_shapes(cfg).items())
    assert abs(float(w["embed"][:-1].std()) - cfg["embedding_range"]) < 0.02
    # of the mask token's choices the chip holds hot_experts_here, its first
    a = w["embed"][-1] / np.sqrt(np.mean(np.square(w["embed"][-1])))
    first, held, here = cfg["experts_first"], cfg["num_experts"], cfg["hot_experts_here"]
    for i in range(cfg["num_hidden_layers"]):
        hot = np.argsort(-np.asarray(a @ w[f"l{i}.router"]))[:cfg["num_experts_per_tok"]]
        assert sorted(h for h in hot if first <= h < first + held) == list(
            range(first, first + here))
    assert abs(float(w["l1.wq"].std()) - cfg["initializer_range"]) < 1e-3
    assert float(w["head"].std()) < 0.03 and (np.asarray(w["l0.q_norm"]) == 1).all()
    other = ref.make_weights(cfg, jax.random.key(3))
    assert not np.allclose(w["l0.router"], other["l0.router"])


def test_sdar_flops_from_shapes():
    cfg, flops, ref = _cfg(toy=False), _load("flops"), _load("reference")
    n = sum(int(np.prod(s)) for s in ref.weight_shapes(cfg).values())
    assert n == 645_623_296                       # 10.33 GB at 16 B each
    # a quarter of the 8192 x 8192 pairs: L^2 + L Bk of 4 L^2
    L, bk = cfg["seq_len"], cfg["block_length"]
    assert flops.live_pairs(cfg) == L * L + L * bk == 16_793_600
    toy = _cfg()
    assert flops.live_pairs(toy) == ref.attention_mask(
        toy["seq_len"], toy["block_length"]).sum()
    layers, positions = cfg["num_hidden_layers"], 2 * L
    assert flops.projection_params(cfg) == 18_874_368 + 262_144
    assert flops.expected_pairs(cfg) == positions      # 8 of 128, 16 held
    per_layer = lambda macs: 2 * macs / layers / 1e12  # forward TFLOP a layer
    assert abs(per_layer(2 * flops.attention_macs(cfg)) - 0.550) < 0.001
    assert abs(2 * 2 * positions * 18_874_368 / 1e12 - 0.618) < 0.001
    assert abs(2 * 2 * positions * 3 * 2048 * 768 / 1e12 - 0.155) < 0.001
    assert flops.train_flops_per_sample(cfg) == 6 * flops.forward_macs(cfg)
    assert 12.9e12 < flops.train_flops_per_sample(cfg) < 13.0e12
    # the kernels' own: nine products over the live pairs, both rows
    f, b = flops.mask_flash_kernel_cost(cfg, 2)
    assert f == 2 * 9 * 16_793_600 * 4096 * layers * 2 and b > 0
    # tiles visited at 512: 8 + 2 * 36 of 256, counted whole
    assert flops.tile_pairs(cfg) == 80 * 512 * 512
    f, b = flops.moe_kernel_cost(cfg, 16384)
    assert f == 2 * 11 * 16384 * 2048 * 768 and b > 4 * 16 * 3 * 2048 * 768 * 2


@pytest.mark.parametrize("metric", ["mask_flash_roofline", "moe_experts_ms",
                                    "flash_tiles_skipped_pct",
                                    "moe_rows_computed_ratio", "moe_imbalance"])
def test_new_readers_read_what_is_there_and_nothing_otherwise(metric):
    """On a program without the kernels' names or the counters (the parent
    commit under these files) a reader returns None and does not raise;
    with them it reads the number."""
    from horovod_tpu import metrics
    from horovod_tpu.models import moe
    from horovod_tpu.ops import flash_attention as fa
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    cfg, said = _cfg(toy=False), []
    stretch = types.SimpleNamespace(stamps=[0.0] * 4, global_batch=2, chips=1)
    ctx = types.SimpleNamespace(
        config=cfg, flops=_load("flops"), traced=stretch, say=said.append,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(device_ops=[["fusion", 1.0]]))
    bare = types.SimpleNamespace(**{**vars(ctx), "trace": None,
                                    "flops": types.SimpleNamespace()})
    if read.__globals__["SOURCE"] == "device_trace":
        assert read(bare) is None and read(ctx) is None
    moe.record_routing(np.array([16000.0, 16000.0, 1100.0, 1.0]))
    if metrics.ACTIVE:
        fa._count_tiles("fwd", np.array([0, 0, 1, 2]))
    ctx.trace.device_ops += [["hvd_flash_fwd (custom-call)", 0.2],
                             ["hvd_flash_dkv (custom-call)", 0.8],
                             ["ragged-dot-none (custom-call)", 0.02]]
    if metric == "moe_experts_ms":
        # since PR 35 the time under the experts' scope, whoever computes
        # it: the kinds alone no longer read, the instructions' names do
        assert read(ctx) is None
        stack = "jit(objective_step)/hvd_forward/transpose(jvp())/while/body"
        text = "HloModule jit_objective_step\n\nENTRY %main.1 () -> f32[] {\n" + "".join(
            f'  %{name} = f32[8]{{0}} custom-call(), metadata={{op_name="{stack}/{path}"}}\n'
            for name, path in [("hvd_moe_gmm_dh.3", "hvd_moe_experts/hvd_moe_gmm_dh/pallas_call"),
                               ("sort.2", "hvd_moe_experts/sort"),
                               ("fusion.9", "hvd_moe_route/top_k")]) + "}\n"
        ctx = types.SimpleNamespace(traced=stretch, say=said.append, trace=ctx.trace,
                                    hlo_text=lambda: text)
        ctx.trace.instructions = {"jit_objective_step": {
            "hvd_moe_gmm_dh.3": ["custom-call", 0.015, 24], "sort.2": ["sort", 0.005, 4],
            "fusion.9": ["fusion", 0.03, 24]}}
    value = read(ctx)
    assert value is not None and value > 0
    if metric == "mask_flash_roofline":
        least = 2 * 9 * 16_793_600 * 4096 * 6 * 2 / 197e12
        assert value == pytest.approx(100 * least * 4 / 1.0) and value < 100
        assert "compute-bound" in said[-1]
    if metric == "moe_experts_ms":
        assert value == pytest.approx(5.0)
