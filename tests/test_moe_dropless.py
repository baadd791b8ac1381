"""Routed experts that drop no token (models/moe.py, ``moe_dispatch =
"dropless"``): the experts a chip holds, whatever the routing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import llama, moe
from horovod_tpu.ops import grouped_matmul

CFG = llama.LlamaConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                        n_kv_heads=2, d_ff=16, n_experts=8, expert_top_k=2,
                        moe_dispatch="dropless", experts_held=4,
                        experts_first=2, dtype=jnp.float32)
PAR = llama.ParallelSpec()


def _params(cfg, seed=0):
    lp = moe.init_moe_layer_params(jax.random.key(seed), 1, cfg.d_model,
                                   cfg.d_ff, cfg.n_experts,
                                   n_held=cfg.experts_held)
    return {n: w[0] for n, w in lp.items()}


def _dense(x, lp, cfg):
    """Every held expert over every token, weighted by the router."""
    tokens = x.reshape(-1, x.shape[-1])
    p = jax.nn.softmax(tokens @ lp["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(p, cfg.expert_top_k)
    top_w = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(tokens)
    for e in range(lp["we_gate"].shape[0]):
        w_e = jnp.where(top_i == cfg.experts_first + e, top_w, 0.0).sum(-1)
        h = jax.nn.silu(tokens @ lp["we_gate"][e]) * (tokens @ lp["we_up"][e])
        y = y + w_e[:, None] * (h @ lp["we_down"][e])
    return y.reshape(x.shape)


def test_dropless_layer_is_the_held_experts_part_of_the_dense_layer():
    lp = _params(CFG)
    x = jax.random.normal(jax.random.key(1), (2, 48, CFG.d_model))
    y, stats = moe.dropless_moe_layer(x, lp, CFG, PAR)
    np.testing.assert_allclose(y, _dense(x, lp, CFG), atol=1e-5, rtol=1e-5)
    pairs, rows, fullest, layers = np.asarray(stats)
    top_i = jax.lax.top_k(x.reshape(-1, 32) @ lp["router"], 2)[1]
    held = (top_i >= 2) & (top_i < 6)
    assert pairs == rows == held.sum() and layers == 1
    assert fullest == max((top_i == e).sum() for e in range(2, 6))


def test_every_token_to_one_held_expert_and_none_is_lost():
    """All N tokens choose held expert 3 first and held expert 4 second:
    2 N pairs, twice what a chunk takes, every one computed."""
    lp = _params(CFG)
    router = np.zeros((CFG.d_model, CFG.n_experts), np.float32)
    lp["router"] = jnp.asarray(router)
    x = jax.random.normal(jax.random.key(2), (4, 256, CFG.d_model))
    x = x.at[..., 0].set(4.0)            # one feature every token shares
    lp["router"] = lp["router"].at[0, 3].set(2.0).at[0, 4].set(1.0)
    N = 4 * 256
    assert moe._chunk_rows(N, 2, 4, 8) < 2 * N      # more than one chunk
    y, stats = jax.jit(lambda x, lp: moe.dropless_moe_layer(
        x, lp, CFG, PAR))(x, lp)
    np.testing.assert_allclose(y, _dense(x, lp, CFG), atol=2e-5, rtol=2e-5)
    assert np.asarray(stats).tolist() == [2 * N, 2 * N, N, 1]


def test_no_token_to_a_held_expert_gives_zeros():
    lp = _params(CFG)
    x = jax.random.normal(jax.random.key(3), (1, 32, CFG.d_model))
    x = x.at[..., 0].set(4.0)
    lp["router"] = jnp.zeros_like(lp["router"]).at[0, 0].set(2.0).at[0, 7].set(1.0)
    y, stats = moe.dropless_moe_layer(x, lp, CFG, PAR)
    assert not np.asarray(y).any() and np.asarray(stats).tolist() == [0, 0, 0, 1]


@pytest.mark.parametrize("first,held", [(2, 4), (0, 8), (7, 1)])
def test_dropless_gradients_match_the_dense_layer(first, held):
    cfg = dataclasses.replace(CFG, experts_first=first, experts_held=held)
    lp = _params(cfg, seed=4)
    x = jax.random.normal(jax.random.key(5), (2, 64, cfg.d_model))
    t = jax.random.normal(jax.random.key(6), x.shape)

    def loss(f):
        return lambda x, lp: (f(x, lp) * t).sum()

    got = jax.grad(loss(lambda x, lp: moe.dropless_moe_layer(
        x, lp, cfg, PAR)[0]), (0, 1))(x, lp)
    want = jax.grad(loss(lambda x, lp: _dense(x, lp, cfg)), (0, 1))(x, lp)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-4)


@pytest.mark.parametrize("chunks", [1, 2], ids=["one-chunk", "two-chunks"])
def test_layer_through_the_combine_kernel_is_the_scatter_adds(
        chunks, pallas_interpret):
    """The layer's output and its five gradients with the combine as the
    Pallas kernel (interpreted; the model width a multiple of 128, 1,024
    tokens) against ``.at[].add``, to the last bit: both add a token's
    rows in the sorted rows' order.  Two chunks: every token sends both
    its pairs to held experts, 2,048 pairs for chunks of 1,536 rows, so
    the second chunk adds to what the first left."""
    pallas_interpret(False)
    cfg = dataclasses.replace(CFG, d_model=128)
    lp = _params(cfg, seed=7)
    x = jax.random.normal(jax.random.key(8), (2, 512, cfg.d_model))
    if chunks == 2:
        x = x.at[..., 0].set(6.0)
        lp["router"] = jnp.zeros_like(lp["router"]).at[0, 3].set(
            2.0).at[0, 4].set(1.0).at[1, 2:6].set(0.05)
    t = jax.random.normal(jax.random.key(9), x.shape)

    def run():
        def loss(x, lp):
            y, stats = moe.dropless_moe_layer(x, lp, cfg, PAR)
            return (y * t).sum(), (y, stats)

        (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True))(x, lp)
        return y, stats, grads

    def combines():
        fam = metrics.registry().to_dict().get("hvd_moe_gmm_kernel_total", {})
        return {s["labels"]["path"]: s["value"] for s in fam.get("series", [])
                if s["labels"]["kernel"] == "combine"}

    y, stats, grads = run()
    before = combines()
    pallas_interpret()
    y2, stats2, grads2 = run()
    if metrics.ACTIVE:           # out and dtok, and no scatter-add beside them
        after = combines()
        assert after.get("pallas", 0) - before.get("pallas", 0) == 2
        assert after.get("xla", 0) == before.get("xla", 0)
    rows = moe._chunk_rows(1024, 2, 4, 8)
    assert (np.asarray(stats)[0] > rows) == (chunks == 2)
    np.testing.assert_array_equal(stats, stats2)
    np.testing.assert_array_equal(y, y2)
    assert len(jax.tree_util.tree_leaves(grads)) == 5
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads2)):
        assert np.asarray(a).any()
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_layer_through_the_dispatch_kernel_is_the_gathers(
        dtype, monkeypatch, pallas_interpret):
    """The layer's output and its five gradients with a chunk's rows
    gathered by the Pallas dispatch (interpreted; a width of 256, 1,024
    tokens, so the grouped products are the kernels' too) against the same
    kernels on ``tokens[tok]`` and ``dout[tok]``, to the last bit: a
    gather moves bits, and the cotangent's rows are rounded to the rows'
    dtype once on either path (float32 rows the kernel refuses: both
    programs are XLA's gathers, and the counter says so).  The routing is
    uneven: two experts take most pairs, one none, and two chunks are
    walked."""
    cfg = dataclasses.replace(CFG, d_model=256, d_ff=128, dtype=dtype)
    lp = _params(cfg, seed=7)
    lp["router"] = lp["router"].at[0, 3].add(0.5).at[0, 4].add(0.3).at[
        0, 2].add(-2.0)
    x = jax.random.normal(jax.random.key(8), (2, 512, cfg.d_model),
                          dtype).at[..., 0].set(6.0)
    t = jax.random.normal(jax.random.key(9), x.shape)

    def run():
        def loss(x, lp):
            y, stats = moe.dropless_moe_layer(x, lp, cfg, PAR)
            return (y * t).sum(), (y, stats)

        (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True))(x, lp)
        return y, stats, grads

    def gathers():
        fam = metrics.registry().to_dict().get("hvd_moe_gmm_kernel_total", {})
        return {s["labels"]["path"]: s["value"] for s in fam.get("series", [])
                if s["labels"]["kernel"] == "gather"}

    grew = lambda a, b: {k: b[k] - a.get(k, 0) for k in b if b[k] != a.get(k, 0)}
    before = gathers()
    y, stats, grads = run()
    mid = gathers()
    # the same program with the dispatch refused: XLA's gathers
    monkeypatch.setattr(grouped_matmul, "_dispatch_refusal",
                        lambda *a: "refused for the comparison")
    y2, stats2, grads2 = run()
    if metrics.ACTIVE:           # tokens forward; tokens and dout backward
        assert grew(before, mid) == {
            "pallas" if dtype == jnp.bfloat16 else "xla": 3}
        assert grew(mid, gathers()) == {"xla": 3}
    sizes = np.asarray(stats)
    assert sizes[0] > moe._chunk_rows(1024, 2, 4, 8) and sizes[2] > 800
    np.testing.assert_array_equal(stats, stats2)
    np.testing.assert_array_equal(y, y2)
    assert len(jax.tree_util.tree_leaves(grads)) == 5
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads2)):
        assert np.asarray(a, np.float32).any()
        np.testing.assert_array_equal(a, b)


def test_chunk_rows_follow_even_routing_not_the_worst_case():
    # the cell: 16,384 positions, 8 of 128 a token, 16 held: 16,384 pairs
    # even, half as much again a chunk; the worst case is eight times that
    assert moe._chunk_rows(16384, 8, 16, 128) == 24576
    assert moe._chunk_rows(16, 8, 16, 128) == 16 * 8        # never past it
    assert moe._chunk_rows(1024, 2, 8, 8) == 2048           # all held


def test_record_routing_adds_to_the_counter():
    def routed():
        fam = metrics.registry().to_dict()["hvd_moe_routed_total"]
        return {s["labels"]["what"]: s["value"] for s in fam["series"]}

    before = routed()
    moe.record_routing(np.array([10.0, 10.0, 4.0, 2.0], np.float32))
    after = routed()
    assert {k: after[k] - before.get(k, 0) for k in after} == {
        "pairs": 10, "rows": 10, "fullest": 4, "layers": 2}


def test_dropless_is_refused_over_tp_or_ep_axes():
    lp = _params(CFG)
    x = jnp.zeros((1, 8, CFG.d_model))
    with pytest.raises(NotImplementedError):
        moe.dropless_moe_layer(x, lp, CFG, llama.ParallelSpec(tp_axis="tp"))


def test_config_head_dim_and_dispatch_fields():
    assert llama.tiny().head_dim == 16                     # d_model / n_heads
    cfg = dataclasses.replace(llama.tiny(), head_dim=32, qk_norm=True,
                              tie_embeddings=False)
    params = llama.init_params(cfg, jax.random.key(0))
    assert params["layers"]["wq"].shape == (2, 64, 4 * 32)
    assert params["layers"]["q_norm"].shape == (2, 32)
    assert params["head"].shape == params["embed"].shape
    assert llama.count_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) \
        - 2 * 2 * 32                                       # q/k norms apart
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, moe_dispatch="sometimes")


# ------------------------- the router's scores, its bias, a shared expert

SIGMOID = dataclasses.replace(CFG, router_score="sigmoid")


def test_route_scores_by_softmax_or_sigmoid_and_weighs_over_the_chosen():
    tokens = jax.random.normal(jax.random.key(0), (40, 32))
    router = jax.random.normal(jax.random.key(1), (32, 8))
    logits = np.asarray(tokens @ router, np.float64)
    for score, s in (("softmax", np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)),
                     ("sigmoid", 1 / (1 + np.exp(-logits)))):
        top_i, top_w = moe.route(tokens, router, 3, score)
        want_i = np.argsort(-s, axis=-1)[:, :3]
        np.testing.assert_array_equal(np.sort(top_i, -1), np.sort(want_i, -1))
        chosen = np.take_along_axis(s, np.asarray(top_i), -1)
        np.testing.assert_allclose(top_w, chosen / chosen.sum(-1, keepdims=True),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(top_w).sum(-1), 1.0, rtol=1e-6)
    # the default is what the softmax-top-k trunk always computed
    for a, b in zip(moe.route(tokens, router, 3), moe.route(tokens, router, 3, "softmax")):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="score must be one of"):
        moe.route(tokens, router, 3, "tanh")
    with pytest.raises(ValueError, match="router_score"):
        dataclasses.replace(CFG, router_score="tanh")


@pytest.mark.parametrize("score", moe.SCORES)
def test_a_selection_bias_moves_the_choice_and_not_the_weights(score):
    """Chosen by ``s + b``, weighed by ``s``: a large bias on one expert
    puts it among every token's choices at its own small score."""
    tokens = jax.random.normal(jax.random.key(0), (64, 32))
    router = jax.random.normal(jax.random.key(1), (32, 8))
    plain_i, _ = moe.route(tokens, router, 2, score)
    bias = jnp.zeros((8,)).at[5].set(10.0).at[0].set(-10.0)
    top_i, top_w = moe.route(tokens, router, 2, score, bias)
    assert (np.asarray(top_i) == 5).any(-1).all() and not (np.asarray(top_i) == 0).any()
    assert not (np.asarray(plain_i) == 5).any(-1).all()
    logits = np.asarray(tokens @ router, np.float64)
    s = (np.exp(logits) / np.exp(logits).sum(-1, keepdims=True) if score == "softmax"
         else 1 / (1 + np.exp(-logits)))
    want_i = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(top_i, -1), np.sort(want_i, -1))
    chosen = np.take_along_axis(s, np.asarray(top_i), -1)     # without the bias
    np.testing.assert_allclose(top_w, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    # a zero bias is no bias; and the bias takes no gradient
    for a, b in zip(moe.route(tokens, router, 2, score, jnp.zeros((8,))),
                    moe.route(tokens, router, 2, score)):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    g = jax.grad(lambda b: moe.route(tokens, router, 2, score, b)[1].sum())(bias)
    assert not np.asarray(g).any()


def _dense_sigmoid(x, lp, cfg):
    tokens = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(tokens @ lp["router"])
    _, top_i = jax.lax.top_k(s + lp["router_bias"], cfg.expert_top_k)
    top_s = jnp.take_along_axis(s, top_i, -1)
    top_w = top_s / top_s.sum(-1, keepdims=True)
    y = jnp.zeros_like(tokens)
    for e in range(lp["we_gate"].shape[0]):
        w_e = jnp.where(top_i == cfg.experts_first + e, top_w, 0.0).sum(-1)
        h = jax.nn.silu(tokens @ lp["we_gate"][e]) * (tokens @ lp["we_up"][e])
        y = y + w_e[:, None] * (h @ lp["we_down"][e])
    return y.reshape(x.shape)


def test_dropless_layer_with_a_sigmoid_router_and_a_bias_and_its_gradients():
    lp = {**_params(SIGMOID),
          "router_bias": 0.5 * jax.random.normal(jax.random.key(3), (8,))}
    x = jax.random.normal(jax.random.key(1), (2, 48, CFG.d_model))
    y, stats = moe.dropless_moe_layer(x, lp, SIGMOID, PAR)
    np.testing.assert_allclose(y, _dense_sigmoid(x, lp, SIGMOID), atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(y - moe.dropless_moe_layer(
        x, {k: v for k, v in lp.items() if k != "router_bias"}, SIGMOID, PAR)[0]).max()) > 1e-3
    w = jax.random.normal(jax.random.key(2), x.shape)
    got = jax.grad(lambda x_, lp_: (moe.dropless_moe_layer(x_, lp_, SIGMOID, PAR)[0] * w).sum(),
                   (0, 1))(x, lp)
    want = jax.grad(lambda x_, lp_: (_dense_sigmoid(x_, lp_, SIGMOID) * w).sum(), (0, 1))(x, lp)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    assert not np.asarray(got[1]["router_bias"]).any()


def test_shared_expert_is_a_fused_gate_up_swiglu():
    x = jax.random.normal(jax.random.key(0), (2, 24, 32)).astype(jnp.bfloat16)
    w1 = jax.random.normal(jax.random.key(1), (32, 2 * 16)) * 0.2
    w2 = jax.random.normal(jax.random.key(2), (16, 32)) * 0.2
    y = moe.shared_expert(x, w1, w2)
    assert y.dtype == x.dtype and y.shape == x.shape
    x32 = x.astype(jnp.float32)
    want = (jax.nn.silu(x32 @ w1[:, :16]) * (x32 @ w1[:, 16:])) @ w2
    np.testing.assert_allclose(y.astype(jnp.float32), want, atol=0.05, rtol=0.05)
    assert moe.SCOPE_SHARED == "hvd_moe_shared"


def test_refusal_over_an_expert_axis_speaks_of_the_shared_expert_too():
    with pytest.raises(NotImplementedError, match="shared expert beside them whole"):
        moe.dropless_moe_layer(jnp.zeros((1, 8, 32)), _params(CFG), CFG,
                               llama.ParallelSpec(ep_axis="ep"))


def test_shared_experts_and_the_gate_belong_to_the_trunk_of_several_kinds():
    for field in ({"n_shared_experts": 1}, {"attn_gate": True}):
        with pytest.raises(ValueError, match="trunk of several kinds"):
            dataclasses.replace(CFG, **field)
