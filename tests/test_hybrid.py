"""models/hybrid.py: differential attention in its three forms against
its dense formula (through the masked flash kernels in interpret mode and
through the XLA path) and a trunk of several kinds with runs of equal
layers.  (The llama and SDAR programs' lowered text, which the new kinds
leave as it was: tests/benchmark/test_bench_phi4flash.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import hybrid, llama
from horovod_tpu.ops import flash_attention as fa

H, HKV, DH = 8, 4, 64          # groups of two, head_dim 64, values of 128


def _cfg(**kw):
    base = dict(vocab_size=256, d_model=H * DH, n_layers=1, n_heads=H,
                n_kv_heads=HKV, d_ff=128, norm_eps=1e-5, dtype=jnp.float32,
                layer_kinds=("full",), sliding_window=100, ssm_inner=128,
                ssm_dt_rank=8, remat=False)
    return llama.LlamaConfig(**{**base, **kw})


def _dense(q, k, v, lp, lam0, live):
    """The formula, written out: two dense softmaxes a head pair."""
    B, T, _, _ = q.shape
    pairs = lambda x: (x[:, :, 0::2], x[:, :, 1::2])
    (q1, q2), (k1, k2), (va, vb) = pairs(q), pairs(k), pairs(v)
    vv = jnp.concatenate([va, vb], -1)                  # [B, Tk, Hkv/2, 2Dh]
    rep = lambda x: jnp.repeat(x, 2, axis=2)            # two query pairs a kv pair

    def soft(q_, k_):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_, rep(k_)) / 8.0
        return jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)

    lam = (jnp.exp(lp["lambda_q1"] @ lp["lambda_k1"])
           - jnp.exp(lp["lambda_q2"] @ lp["lambda_k2"]) + lam0)
    o = jnp.einsum("bhqk,bkhd->bqhd", soft(q1, k1) - lam * soft(q2, k2), rep(vv))
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
    o = o * lp["subln"] * (1 - lam0)
    return o.reshape(B, T, H * DH) @ lp["wo"]


@pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "xla"])
@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_differential_attention_follows_its_dense_formula(kind, interpret,
                                                          monkeypatch,
                                                          pallas_interpret):
    """Window, causal and cross; forward and every gradient; through
    ``hvd_flash_fwd``/``dq``/``dkv`` with values twice as wide as queries
    and keys, and through the XLA path."""
    pallas_interpret(interpret)
    monkeypatch.setattr(fa, "_BLOCK", 128)
    cfg, T, B = _cfg(layer_kinds=(kind if kind != "cross" else "full",)), 256, 2
    ks = jax.random.split(jax.random.key(4), 10)
    q = jax.random.normal(ks[0], (B, T, H, DH))
    k, v = (jax.random.normal(ks[i], (B, T, HKV, DH)) for i in (1, 2))
    lp = {n: jax.random.normal(ks[3 + i], (DH,)) * 0.3 for i, n in enumerate(
        ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))}
    lp["subln"] = 1 + 0.1 * jax.random.normal(ks[7], (2 * DH,))
    lp["wo"] = jax.random.normal(ks[8], (H * DH, H * DH)) * (H * DH) ** -0.5
    w = jax.random.normal(ks[9], (B, T, H * DH))
    lam0 = hybrid.lambda_init(17)
    ranges = hybrid.key_ranges(kind, T, cfg)
    live = fa.dense_mask(ranges, T)
    before = metrics.registry().to_dict().get("hvd_flash_kernel_total", {})
    got = jax.value_and_grad(lambda q, k, v, lp: (hybrid._diff_attention(
        q, k, v, lp, lam0, ranges, cfg) * w).sum(), (0, 1, 2, 3))(q, k, v, lp)
    want = jax.value_and_grad(lambda q, k, v, lp: (_dense(
        q, k, v, lp, lam0, live) * w).sum(), (0, 1, 2, 3))(q, k, v, lp)
    scale = float(jnp.abs(want[0]))
    assert abs(float(got[0] - want[0])) < 1e-4 * max(scale, 1.0)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()),
                                   rtol=2e-3)
    if interpret and metrics.ACTIVE:       # two calls a layer, each of three
        after = metrics.registry().to_dict()["hvd_flash_kernel_total"]
        # head_dim 64 is half a lane tile: transposed around the kernels
        count = lambda fam: {s["labels"]["kernel"]: s["value"] for s in
                             fam.get("series", [])
                             if (s["labels"]["path"], s["labels"]["layout"])
                             == ("masked", "heads")}
        grew = {k: n - count(before).get(k, 0) for k, n in count(after).items()}
        assert grew == {"fwd": 2, "dq": 2, "dkv": 2}


def test_lowered_trunk_text_is_unchanged_by_the_kernels_second_layout(
        monkeypatch, pallas_interpret):
    """The lowered text (``lower().as_text()``) of a remat'd trunk of the
    three attention kinds at the phi widths (``head_dim`` 64, values of
    128, groups of two), kernels interpreted, forward and backward, as it
    was at the commit before the masked kernels learnt to read the
    caller's layout (PR 33's tree, jax 0.9.0): half a lane tile a head
    stays on the transposed route, built as it was.  A later change to
    this program changes the hash and states it here: PR 37, the masked
    backward's kernel bodies (``dq`` both heads of a pair a step with its
    accumulator, ``lse`` / ``delta`` columns and spread ranges in scratch;
    ``dkv``'s tile keys by queries on ranges handed over ``[Bm, 4, T]``);
    PR 44, the kernel bodies again (one ``visit`` serves a whole tile and
    a sub-tile; the forward's mask made once a visit and added by every
    head, as ``dq`` made it; at these tiles of 128 no tile is walked by
    sub-tiles); the block specs and everything around the kernels as they
    were."""
    import hashlib
    monkeypatch.setattr(fa, "_BLOCK", 128)
    cfg = _cfg(n_layers=3, layer_kinds=("window", "full", "cross"),
               layer_ids=(1, 17, 19), dtype=jnp.bfloat16, remat=True)
    layers = llama.init_params(cfg, jax.random.key(0))["layers"]
    h = jax.ShapeDtypeStruct((2, 256, H * DH), cfg.dtype)
    text = jax.jit(jax.value_and_grad(lambda h, ls: hybrid.layer_stack(
        h, ls, cfg, llama.remat_policy("full")).astype(jnp.float32).sum(),
        (0, 1))).lower(h, layers).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "85265b68d64b884c"


def test_mamba_trunk_is_untouched_by_the_mamba2_mixers_kernels(
        monkeypatch, pallas_interpret):
    """``_mamba`` keeps ``_conv_silu`` and its own gate: with the Mamba-2
    mixer's kernels switched on (interpret), tracing a remat'd trunk of
    ``mamba`` and ``gmu`` layers forward and backward counts nothing in
    ``hvd_mixer_kernel_total`` and calls neither ``hvd_conv_silu_*`` nor
    ``hvd_gated_norm_*``, in the jaxpr and in the lowered text."""
    from horovod_tpu.ops import mamba2_mixer as mm
    monkeypatch.setattr(mm, "_BLOCK", 32)
    cfg = _cfg(d_model=128, n_heads=2, n_kv_heads=2, n_layers=3,
               layer_kinds=("mamba", "mamba", "gmu"), layer_ids=(0, 2, 4),
               ssm_inner=256, dtype=jnp.bfloat16, remat=True)
    layers = llama.init_params(cfg, jax.random.key(0))["layers"]
    h = jax.ShapeDtypeStruct((2, 64, 128), cfg.dtype)
    step = jax.jit(jax.value_and_grad(lambda h, ls: hybrid.layer_stack(
        h, ls, cfg, llama.remat_policy("full")).astype(jnp.float32).sum(),
        (0, 1)))
    before = _mixer_counts()
    jaxpr = str(jax.make_jaxpr(step)(h, layers))
    text = step.lower(h, layers).as_text()
    assert _mixer_grew(before) == {}
    for name in ("hvd_conv_silu", "hvd_gated_norm"):
        assert name not in jaxpr and name not in text
    # the same widths through a mamba2 layer do reach them
    cfg2 = _cfg(d_model=128, n_heads=2, n_kv_heads=2, n_layers=1,
                layer_kinds=("mamba2",), ssm_inner=256, ssm_heads=4,
                ssm_groups=1, ssm_state=128, ssm_chunk=32, dtype=jnp.bfloat16,
                trunk_norm="rmsnorm")
    lp = jax.tree_util.tree_map(
        lambda w: w[0].astype(cfg2.dtype),
        llama.init_params(cfg2, jax.random.key(0))["layers"]["mamba2"])
    jaxpr2 = str(jax.make_jaxpr(lambda h, lp: hybrid._mamba2(h, lp, cfg2))(
        h, lp))
    assert "hvd_conv_silu_fwd" in jaxpr2 and "hvd_gated_norm_fwd" in jaxpr2
    if metrics.ACTIVE:
        assert _mixer_grew(before) == {("conv_fwd", "pallas"): 1,
                                       ("norm_fwd", "pallas"): 1}


def test_masked_kernels_refuse_what_they_cannot_hold(pallas_interpret):
    q = jnp.zeros((1, 128, 2, 64))
    k = jnp.zeros((1, 128, 1, 64))
    assert fa._refusal(q, k, jnp.zeros((1, 128, 1, 128))) is None
    assert "width" in fa._refusal(q, k, jnp.zeros((1, 128, 1, 96))) or \
        "multiple of 64" in fa._refusal(q, k, jnp.zeros((1, 128, 1, 96)))
    assert "v must match k" in fa._refusal(q, k, jnp.zeros((1, 256, 1, 64)))
    # values of a width of their own never take the packed path
    out = fa.flash_attention(q, k, jnp.ones((1, 128, 1, 128)), causal=True)
    assert out.shape == (1, 128, 2, 128)
    np.testing.assert_allclose(out, 1.0, rtol=1e-6)


def test_trunk_scans_runs_of_equal_layers_and_hands_memory_on():
    """A run of equal layers that is its kind's whole stack is one scan;
    a kind whose stack several runs share goes layer by layer (no slice
    of a stack is scanned); the emitting mamba and full layers are runs
    of their own; the stack equals the layers applied one by one."""
    kinds = ("mamba", "mamba", "window", "window", "mamba", "full", "gmu",
             "gmu", "cross", "cross")
    cfg = _cfg(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=10,
               layer_kinds=kinds, layer_ids=(0, 2, 3, 5, 16, 17, 18, 20, 21, 23),
               sliding_window=16)
    assert [(r[0], r[1], len(r[2]), r[3]) for r in hybrid._runs(cfg)] == [
        ("mamba", 0, 1, False), ("mamba", 1, 1, False), ("window", 0, 2, False),
        ("mamba", 2, 1, True), ("full", 0, 1, True), ("gmu", 0, 2, False),
        ("cross", 0, 2, False)]
    # (each whole trunk as one compiled program: op by op the interpreter's
    # dispatch is most of this test's clock)
    params = jax.jit(lambda key: llama.init_params(cfg, key))(jax.random.key(0))
    assert llama.count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    h = jax.random.normal(jax.random.key(1), (2, 32, 64))
    before = metrics.registry().to_dict().get("hvd_layer_kind_total", {})
    got = jax.jit(lambda h_, ls: hybrid.layer_stack(h_, ls, cfg))(
        h, params["layers"])
    if metrics.ACTIVE:
        count = lambda fam: {s["labels"]["kind"]: s["value"]
                             for s in fam.get("series", [])}
        after = count(metrics.registry().to_dict()["hvd_layer_kind_total"])
        # (kinds another file's tests counted in this process stand still)
        grew = {k: n - count(before).get(k, 0) for k, n in after.items()}
        assert {k: n for k, n in grew.items() if n} == {
            "mamba": 3, "window": 2, "full": 1, "gmu": 2, "cross": 2}
    want, m, kv, seen = h, None, None, {}
    for i, kind in enumerate(kinds):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        lp = jax.tree_util.tree_map(lambda w_: w_[at], params["layers"][kind])
        memory = m if kind == "gmu" else kv if kind == "cross" else None
        want, out, _ = hybrid._layer(kind, i in (4, 5), cfg)(
            want, lp, hybrid.lambda_init(cfg.layer_ids[i]), memory)
        m, kv = (out, kv) if i == 4 else (m, out) if i == 5 else (m, kv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # remat changes no number
    remat = jax.jit(lambda h_, ls: hybrid.layer_stack(
        h_, ls, _cfg(**{**vars(cfg), "remat": True}),
        llama.remat_policy("full")))(h, params["layers"])
    np.testing.assert_allclose(remat, got, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kinds,error", [
    (("gmu", "mamba"), "needs a mamba"), (("cross", "full"), "needs a full"),
    (("mamba", "linear"), "layer_kinds must name"), (("mamba",), "n_layers")])
def test_kinds_that_make_no_trunk_are_refused(kinds, error):
    with pytest.raises(ValueError, match=error):
        hybrid.check(_cfg(n_layers=2, layer_kinds=kinds))


def test_trunk_of_kinds_takes_no_positions_mask_or_model_parallel_axis():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jnp.zeros((1, 128), jnp.int32)
    for kw in (dict(positions=tokens), dict(mask=fa.causal_ranges(128))):
        with pytest.raises(NotImplementedError, match="no positions"):
            llama.hidden(params, tokens, cfg, llama.ParallelSpec(), **kw)
    with pytest.raises(NotImplementedError, match="data parallelism"):
        llama.hidden(params, tokens, cfg, llama.ParallelSpec(tp_axis="tp"))


# (The Mamba-2 hybrids, granite-4.0-h-micro's kinds against its plain
# reference, are tests/test_hybrid_granite.py; the two helpers below count
# the mixer's kernels for that file and this one.)

def _mixer_counts():
    family = metrics.registry().to_dict().get("hvd_mixer_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"]): s["value"]
            for s in family.get("series", [])}


def _mixer_grew(before):
    after = _mixer_counts()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


# --------------------------- the delta-rule hybrids (solar-open2-250b)
# The kind ``kda``, the gate on ``attention`` and routed experts beside a
# shared one as the config's feed-forward.  The plain reference is the
# benchmark's, benchmark/configs/solar-open2-250b/reference.py, at its toy
# sizes (tests/benchmark/test_bench_solar.py runs the train step against it).

def _solar_cfg(**kw):
    base = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=3,
                d_ff=32, layer_kinds=("attention", "kda", "kda"), ssm_heads=4,
                ssm_state=16, ssm_inner=64, ssm_conv=4, ssm_chunk=16,
                trunk_norm="rmsnorm", attn_gate=True, tie_embeddings=False,
                n_experts=16, expert_top_k=4, experts_held=4, experts_first=4,
                moe_dispatch="dropless", router_score="sigmoid",
                n_shared_experts=1)
    return _cfg(**{**base, **kw})


def test_kda_gate_and_routed_feed_forward_are_the_configs_leaves():
    cfg = _solar_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    routed = {"norm1_w", "norm2_w", "router", "router_bias", "we_gate", "we_up",
              "we_down", "w1", "w2"}
    assert set(params["layers"]["attention"]) == routed | {"wqkv", "wgate", "wo"}
    assert set(params["layers"]["kda"]) == routed | {
        "wqkv", "conv_w", "f_a", "f_b", "dt_bias", "A_log", "b_proj", "g_a",
        "g_b", "o_norm", "wo"}
    kda, att = params["layers"]["kda"], params["layers"]["attention"]
    assert kda["wqkv"].shape == (2, 64, 3 * 64) and kda["conv_w"].shape == (2, 4, 192)
    assert kda["f_a"].shape == (2, 64, 16) and kda["f_b"].shape == (2, 16, 64)
    assert kda["dt_bias"].shape == (2, 64) and kda["A_log"].shape == (2, 4)
    assert kda["b_proj"].shape == (2, 64, 4) and kda["o_norm"].shape == (2, 16)
    assert att["wgate"].shape == (1, 64, 64) and att["w1"].shape == (1, 64, 64)
    assert att["router"].shape == (1, 64, 16) and att["we_down"].shape == (1, 4, 32, 64)
    assert not np.asarray(kda["router_bias"]).any() and (np.asarray(kda["o_norm"]) == 1).all()
    A = np.exp(np.asarray(kda["A_log"]))
    assert (1 <= A).all() and (A <= 16).all()
    step = np.asarray(jax.nn.softplus(kda["dt_bias"]))
    assert 0.999e-3 <= step.min() and step.max() <= 0.1001
    assert abs(float(att["we_gate"].std()) - 64 ** -0.5) < 0.01     # an expert's rows
    assert params["head"].shape == params["embed"].shape == (256, 64)
    assert llama.count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    specs = llama.param_specs(llama.ParallelSpec(), cfg)
    assert jax.tree_util.tree_structure(specs) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, params)) or set(specs) == set(params)
    # without the flags the kinds' leaves are Granite's, to the name
    plain = llama.init_params(_solar_cfg(attn_gate=False, n_experts=0,
                                         n_shared_experts=0), jax.random.key(0))
    assert set(plain["layers"]["attention"]) == {
        "norm1_w", "norm2_w", "w1", "w2", "wqkv", "wo"}
    assert plain["layers"]["attention"]["w1"].shape == (1, 64, 64)
    # every routed layer is a run of its own (no scan's stacked gradient)
    assert [(k, at, ids) for k, at, ids, _ in hybrid._runs(cfg)] == [
        ("attention", 0, [0]), ("kda", 0, [1]), ("kda", 1, [2])]
    assert [(k, ids) for k, _, ids, _ in hybrid._runs(_solar_cfg(
        n_experts=0, n_shared_experts=0))] == [("attention", [0]), ("kda", [1, 2])]


@pytest.mark.parametrize("kw,error", [
    (dict(ssm_heads=3), "kda layer needs"), (dict(ssm_state=0), "kda layer needs"),
    (dict(moe_dispatch="capacity"), "dropless")])
def test_kda_and_routed_trunks_that_cannot_be_are_refused(kw, error):
    with pytest.raises(ValueError, match=error):
        hybrid.check(_solar_cfg(**kw))


def test_routed_trunk_hands_on_its_statistics_and_a_dense_one_none():
    cfg = _solar_cfg()
    # (each whole trunk as one compiled program: op by op the interpreter's
    # dispatch is most of this test's clock)
    stack = lambda c, **kw: jax.jit(
        lambda h_, ls: hybrid.layer_stack(h_, ls, c, **kw))
    params = jax.jit(lambda key: llama.init_params(cfg, key))(jax.random.key(0))
    h = jax.random.normal(jax.random.key(1), (2, 32, 64))
    out, stats = stack(cfg, with_stats=True)(h, params["layers"])
    pairs, rows, fullest, layers = np.asarray(stats)
    assert layers == 3 and pairs == rows and 0 < fullest <= pairs
    assert 0 < pairs < 3 * 2 * 32 * 4
    np.testing.assert_array_equal(out, stack(cfg)(h, params["layers"]))
    tokens = jnp.zeros((2, 32), jnp.int32)
    _, got = jax.jit(lambda p: llama.loss_fn(
        p, tokens, tokens, cfg, llama.ParallelSpec(), with_stats=True))(params)
    assert got.shape == (4,) and got[3] == 3
    dense = _solar_cfg(n_experts=0, n_shared_experts=0)
    dparams = jax.jit(lambda key: llama.init_params(dense, key))(
        jax.random.key(0))
    _, none = stack(dense, with_stats=True)(h, dparams["layers"])
    assert none is None
    # a layer's own statistics count one layer
    f = hybrid._layer("kda", False, cfg)
    one = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["kda"])
    _, _, s = f(h, one, 0.0, None)
    assert s.shape == (4,) and s[3] == 1


def test_the_attention_gate_is_a_sigmoid_of_the_input_before_wo():
    cfg = _solar_cfg(layer_kinds=("attention",), n_layers=1, n_experts=0,
                     n_shared_experts=0)
    lp = jax.tree_util.tree_map(
        lambda w: w[0], llama.init_params(cfg, jax.random.key(0))["layers"]["attention"])
    h = jax.random.normal(jax.random.key(1), (1, 32, 64))
    got, _, _ = hybrid._layer("attention", False, cfg)(h, lp, 0.0, None)
    u = hybrid.norm(h, lp["norm1_w"], None, cfg)
    q, k, v = jnp.split(u @ lp["wqkv"], (64, 96), axis=-1)
    q, k, v = q.reshape(1, 32, 4, 16), k.reshape(1, 32, 2, 16), v.reshape(1, 32, 2, 16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, 2)) / 4.0
    s = jnp.where(jnp.tri(32, dtype=bool), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), jnp.repeat(v, 2, 2))
    a = h + (o.reshape(1, 32, 64) * jax.nn.sigmoid(u @ lp["wgate"])) @ lp["wo"]
    want = a + hybrid._mlp(hybrid.norm(a, lp["norm2_w"], None, cfg), lp)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    ungated, _, _ = hybrid._layer("attention", False, _solar_cfg(
        **{**vars(cfg), "attn_gate": False}))(h, lp, 0.0, None)
    assert float(jnp.abs(ungated - got).max()) > 1e-3


# ------------------------------ plain GQA under a window, a table a kind
# (Mellum 2's ``sliding_attention`` and ``full_attention``: the ``swa`` and
# ``attention`` kinds with ``cfg.rope_tables``; the tables themselves are
# held to the equations in tests/test_llama.py)

YARN = llama.RopeTable(theta=10000.0, rope_type="yarn", factor=4,
                       original_max_position_embeddings=64, beta_fast=2,
                       beta_slow=0.125)
PLAIN = llama.RopeTable(theta=10000.0)


def _mellum_cfg(**kw):
    base = dict(layer_kinds=("swa",), trunk_norm="rmsnorm", sliding_window=100,
                rope_tables=(("attention", YARN), ("swa", PLAIN)))
    return _cfg(**{**base, **kw})


@pytest.mark.parametrize("kind", ["swa", "attention"])
def test_the_two_plain_kinds_masks_are_the_dense_ones(kind):
    """``swa``: Hugging Face's ``sliding_window`` semantics, ``i - j <
    window`` and ``j <= i``; ``attention``: causal."""
    T, cfg = 384, _mellum_cfg()
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    want = (j <= i) & ((i - j < cfg.sliding_window) | (kind == "attention"))
    np.testing.assert_array_equal(
        fa.dense_mask(hybrid.key_ranges(kind, T, cfg), T), want)
    assert want[300].sum() == (100 if kind == "swa" else 301)


@pytest.mark.parametrize("interpret", [True, False], ids=["kernels", "xla"])
@pytest.mark.parametrize("kind", ["swa", "attention"])
def test_plain_gqa_with_a_rotary_table_follows_its_dense_formula(
        kind, interpret, monkeypatch, pallas_interpret):
    """One layer of either kind, forward and every gradient, through the
    masked flash kernels and through the XLA path: ``q`` and ``k`` rotated
    by the kind's own table (float64 here), the window or the causal mask,
    no q/k norm, ``wo``; then the frame's feed-forward."""
    pallas_interpret(interpret)
    monkeypatch.setattr(fa, "_BLOCK", 128)
    cfg, T, B = _mellum_cfg(layer_kinds=(kind,)), 256, 2
    table = dict(cfg.rope_tables)[kind]
    lp = jax.tree_util.tree_map(
        lambda w: w[0], llama.init_params(cfg, jax.random.key(0))["layers"][kind])
    assert set(lp) == {"norm1_w", "norm2_w", "w1", "w2", "wqkv", "wo"}
    h = jax.random.normal(jax.random.key(1), (B, T, H * DH))
    w = jax.random.normal(jax.random.key(2), (B, T, H * DH))
    inv, factor = llama.rope_inv_freq(table, DH)
    assert (factor > 1.1) == (kind == "attention")
    angles = np.arange(T)[:, None] * inv.astype(np.float64)
    cos, sin = (jnp.asarray(f(angles) * factor, jnp.float32)[None, :, None]
                for f in (np.cos, np.sin))
    live = fa.dense_mask(hybrid.key_ranges(kind, T, cfg), T)

    def rot(x):
        x1, x2 = x[..., :DH // 2], x[..., DH // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def dense(h, lp):
        u = hybrid.norm(h, lp["norm1_w"], None, cfg)
        q, k, v = jnp.split(u @ lp["wqkv"], (H * DH, (H + HKV) * DH), axis=-1)
        q, k = rot(q.reshape(B, T, H, DH)), rot(k.reshape(B, T, HKV, DH))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, 2)) / 8.0
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v.reshape(B, T, HKV, DH), 2, 2))
        a = h + o.reshape(B, T, H * DH) @ lp["wo"]
        return a + hybrid._mlp(hybrid.norm(a, lp["norm2_w"], None, cfg), lp)

    layer = hybrid._layer(kind, False, cfg)
    rope = llama.rope_table(table, DH, T)
    got = jax.value_and_grad(lambda h, lp: (layer(h, lp, 0.0, None, rope)[0]
                                            * w).sum(), (0, 1))(h, lp)
    want = jax.value_and_grad(lambda h, lp: (dense(h, lp) * w).sum(), (0, 1))(h, lp)
    assert abs(float(got[0] - want[0])) < 1e-4 * max(float(jnp.abs(want[0])), 1.0)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()),
                                   rtol=2e-3)
    # without its table the layer is another one; the other kind's too
    # (on XLA's path: what is looked at is the table, not the kernels)
    pallas_interpret(False)
    bare = layer(h, lp, 0.0, None)[0]
    other = layer(h, lp, 0.0, None, llama.rope_table(
        YARN if kind == "swa" else PLAIN, DH, T))[0]
    here = layer(h, lp, 0.0, None, rope)[0]
    assert float(jnp.abs(bare - here).max()) > 1e-3
    assert float(jnp.abs(other - here).max()) > 1e-3


def _rope_counts():
    family = metrics.registry().to_dict().get("hvd_rope_tables_total", {})
    return {(s["labels"]["kind"], s["labels"]["type"]): s["value"]
            for s in family.get("series", [])}


def test_a_trunks_rotary_tables_are_made_once_a_kind_and_counted(monkeypatch):
    """Three ``swa`` layers and one ``attention``: two tables (two ``cos``)
    whatever the layers, ``hvd_rope_tables_total`` grows by one a kind,
    both scopes and ``hvd_rope`` are in the lowered names; a trunk without
    tables makes none and is position-free (Granite's and Solar's)."""
    monkeypatch.setattr(metrics, "ACTIVE", True)
    kinds = ("swa", "swa", "swa", "attention")
    cfg = _mellum_cfg(n_layers=4, layer_kinds=kinds, remat=True)
    params = llama.init_params(cfg, jax.random.key(0))
    assert {k: v["wqkv"].shape[0] for k, v in params["layers"].items()} == {
        "swa": 3, "attention": 1}
    h = jax.random.normal(jax.random.key(1), (1, 128, H * DH))
    run = lambda c: (lambda h, ls: hybrid.layer_stack(
        h, ls, c, llama.remat_policy("full")))
    before = _rope_counts()
    jaxpr = jax.make_jaxpr(run(cfg))(h, params["layers"])
    grew = {k: v - before.get(k, 0) for k, v in _rope_counts().items()}
    assert {k: v for k, v in grew.items() if v} == {
        ("swa", "default"): 1, ("attention", "yarn"): 1}
    outer = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert outer.count("cos") == outer.count("sin") == 2
    text = jax.jit(run(cfg)).lower(h, params["layers"]).as_text(debug_info=True)
    for scope in ("hvd_window_attention/hvd_rope", "hvd_attention/hvd_rope"):
        assert scope in text, scope
    bare = _mellum_cfg(n_layers=4, layer_kinds=kinds, rope_tables=())
    before = _rope_counts()
    plain = jax.make_jaxpr(run(bare))(h, params["layers"])
    assert _rope_counts() == before and " cos " not in str(plain)
    # a shuffled row moves a position-free trunk's outputs with it, and not
    # one that counts positions
    perm = jax.random.permutation(jax.random.key(2), 128)
    full = _mellum_cfg(n_layers=1, layer_kinds=("attention",), rope_tables=())
    lp = llama.init_params(full, jax.random.key(3))["layers"]
    last = lambda c, x: hybrid.layer_stack(x, lp, c)[:, -1]
    moved = h[:, jnp.concatenate([perm[perm != 127], jnp.array([127])])]
    np.testing.assert_allclose(last(full, moved), last(full, h), atol=1e-5)
    tabled = _mellum_cfg(n_layers=1, layer_kinds=("attention",))
    assert float(jnp.abs(last(tabled, moved) - last(tabled, h)).max()) > 1e-3


@pytest.mark.parametrize("kw,error", [
    (dict(sliding_window=0), "sliding_window"),
    (dict(rope_tables=(("full", PLAIN),)), "one table at most"),
    (dict(rope_tables=(("swa", PLAIN), ("swa", YARN))), "one table at most")])
def test_plain_kinds_that_cannot_be_are_refused(kw, error):
    with pytest.raises(ValueError, match=error):
        hybrid.check(_mellum_cfg(**kw))


def test_a_softmax_routers_layer_carries_no_selection_bias():
    """The bias moves a sigmoid router's choice; a softmax router has none
    (Mellum 2's, SDAR's), so its layer has no such leaf."""
    cfg = _solar_cfg(layer_kinds=("swa",), n_layers=1, router_score="softmax",
                     sliding_window=8, attn_gate=False, n_shared_experts=0)
    assert "router_bias" not in hybrid.layer_shapes(cfg, "swa")
    assert "router_bias" in hybrid.layer_shapes(
        _solar_cfg(layer_kinds=("kda",), n_layers=1), "kda")
    assert set(hybrid.layer_shapes(cfg, "swa")) == {
        "norm1_w", "norm2_w", "wqkv", "wo", "router", "we_gate", "we_up", "we_down"}


# ------------- latent attention ("mla") and a dense layer before routed ones
# The layer's equations are the benchmark's,
# benchmark/configs/kanana-2-30b-a3b/reference.py, at its toy sizes
# (tests/benchmark/test_bench_kanana.py runs the train step against it).

def _latent_cfg(**kw):
    base = dict(d_model=64, n_heads=4, n_kv_heads=4, head_dim=24, n_layers=3,
                d_ff=32, dense_d_ff=96, first_dense_layers=1,
                layer_kinds=("mla",) * 3, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, trunk_norm="rmsnorm",
                tie_embeddings=False, n_experts=8, expert_top_k=2,
                experts_held=4, moe_dispatch="dropless",
                router_score="sigmoid", n_shared_experts=2,
                routed_scaling_factor=2.448,
                rope_tables=(("mla", llama.RopeTable(theta=1e6)),))
    return _cfg(**{**base, **kw})


def test_a_dense_layer_before_routed_ones_is_a_stack_of_its_own():
    """The feed-forward is a layer's: ``first_dense_layers`` leading layers
    keep the dense one, ``dense_d_ff`` wide, in a stack ``dense_<kind>``
    beside the kind's routed layers; the statistics handed on count the
    routed layers alone; at 0 the tree, the runs and the count are what
    they were."""
    cfg = _latent_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    assert list(params["layers"]) == ["mla", "dense_mla"]
    dense, routed = params["layers"]["dense_mla"], params["layers"]["mla"]
    latent = {"norm1_w", "norm2_w", "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert set(dense) == latent | {"w1", "w2"}
    assert set(routed) == latent | {"w1", "w2", "router", "router_bias",
                                    "we_gate", "we_up", "we_down"}
    assert dense["w1"].shape == (1, 64, 2 * 96) and dense["w2"].shape == (1, 96, 64)
    assert routed["w1"].shape == (2, 64, 2 * 2 * 32)
    assert routed["wq"].shape == (2, 64, 4 * 24) and routed["wkv_a"].shape == (2, 64, 40)
    assert routed["wkv_b"].shape == (2, 32, 4 * 32) and routed["wo"].shape == (2, 64, 64)
    assert (np.asarray(routed["kv_norm"]) == 1).all()
    assert llama.count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    specs = llama.param_specs(llama.ParallelSpec(), cfg)
    assert {k: set(v) for k, v in specs["layers"].items()} == {
        k: set(v) for k, v in params["layers"].items()}
    assert [(s, at, ids) for s, at, ids, _ in hybrid._runs(cfg)] == [
        ("dense_mla", 0, [0]), ("mla", 0, [1]), ("mla", 1, [2])]
    h = jax.random.normal(jax.random.key(1), (2, 32, 64))
    out, stats = jax.jit(lambda h, ls: hybrid.layer_stack(
        h, ls, cfg, with_stats=True))(h, params["layers"])
    pairs, rows, fullest, layers = np.asarray(stats)
    assert layers == 2 and pairs == rows and 0 < fullest <= pairs
    assert out.shape == h.shape and np.isfinite(np.asarray(out)).all()
    # the dense layer's own function hands on no statistics
    one = jax.tree_util.tree_map(lambda w: w[0], dense)
    rope = llama.rope_table(llama.RopeTable(theta=1e6), 8, 32)
    none = jax.eval_shape(hybrid._layer("mla", False, cfg, dense=True), h, one,
                          0.0, None, rope)[2]
    assert none is None
    # no leading dense layer: the kind's stack alone, as before the field
    plain = _latent_cfg(first_dense_layers=0)
    made = lambda c: jax.eval_shape(lambda k: llama.init_params(c, k),
                                    jax.random.key(0))["layers"]
    assert list(made(plain)) == ["mla"]
    assert [s for s, *_ in hybrid._runs(plain)] == ["mla"] * 3
    # without experts every layer is dense and none is set apart
    dense_only = _latent_cfg(n_experts=0, n_shared_experts=0,
                             routed_scaling_factor=1.0)
    assert list(made(dense_only)) == ["mla"]
    assert [(s, ids) for s, _, ids, _ in hybrid._runs(dense_only)] == [
        ("mla", [0, 1, 2])]


def test_latent_attention_follows_its_dense_formula():
    """``latent_attention`` against the equations written out: one product
    over the joined 24-wide query and key, the rotary key shared by all
    four heads, the latent normed, the scale over both widths."""
    cfg = _latent_cfg()
    lp = jax.tree_util.tree_map(
        lambda w: w[0], llama.init_params(cfg, jax.random.key(2))["layers"]["mla"])
    B, T, Hh, R, Dn, Dr, Dv = 2, 32, 4, 32, 16, 8, 16
    u = jax.random.normal(jax.random.key(3), (B, T, 64))
    rope = llama.rope_table(llama.RopeTable(theta=1e6), Dr, T)
    got = hybrid.latent_attention(u, lp, rope, cfg)
    q = u @ lp["wq"]
    q_n, q_r = q[..., :Hh * Dn].reshape(B, T, Hh, Dn), q[..., Hh * Dn:].reshape(B, T, Hh, Dr)
    a = u @ lp["wkv_a"]
    c, k_r = a[..., :R], a[..., R:].reshape(B, T, 1, Dr)
    c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.norm_eps) * lp["kv_norm"]
    kv = c @ lp["wkv_b"]
    k_n, v = kv[..., :Hh * Dn].reshape(B, T, Hh, Dn), kv[..., Hh * Dn:].reshape(B, T, Hh, Dv)
    q_r, k_r = llama.rotate(q_r, *rope), llama.rotate(k_r, *rope)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n)
         + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])) * 24 ** -0.5
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf), -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, Hh * Dv) @ lp["wo"]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("kw,error", [
    (dict(kv_lora_rank=0), "an mla layer needs"),
    (dict(qk_rope_head_dim=7), "an mla layer needs"),
    (dict(first_dense_layers=4), "first_dense_layers counts"),
    (dict(rope_tables=(("kda", llama.RopeTable()),)), "one table at most")])
def test_latent_trunks_that_cannot_be_are_refused(kw, error):
    with pytest.raises(ValueError, match=error):
        hybrid.check(_latent_cfg(**kw))


def test_config_fields_of_the_latent_trunk_are_refused_elsewhere():
    with pytest.raises(ValueError, match="first_dense_layers"):
        llama.LlamaConfig(first_dense_layers=1)
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        llama.LlamaConfig(routed_scaling_factor=2.5)


def test_routed_scaling_factor_multiplies_the_chosen_weights():
    from horovod_tpu.models import moe
    tokens = jax.random.normal(jax.random.key(0), (16, 64))
    router = jax.random.normal(jax.random.key(1), (64, 8))
    i1, w1 = moe.route(tokens, router, 2, "sigmoid", jnp.zeros((8,)))
    i2, w2 = moe.route(tokens, router, 2, "sigmoid", jnp.zeros((8,)), 2.448)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(w2, w1 * 2.448, rtol=1e-6)
    np.testing.assert_allclose(w1.sum(-1), 1.0, rtol=1e-6)


# ------------------------------------- the gated short convolution (PR 53)

def _conv_cfg(**kw):
    base = dict(d_model=64, n_heads=4, n_kv_heads=2, n_layers=5, d_ff=32,
                dense_d_ff=96, first_dense_layers=1, ssm_conv=3,
                layer_kinds=("conv", "attention", "conv", "conv", "conv"),
                layer_ids=(0, 2, 3, 4, 5), trunk_norm="rmsnorm", qk_norm=True,
                n_experts=8, expert_top_k=2, experts_held=4,
                moe_dispatch="dropless", router_score="sigmoid",
                router_eps=1e-6,
                rope_tables=(("attention", llama.RopeTable(theta=1e6)),))
    return _cfg(**{**base, **kw})


def test_the_gated_convolutions_taps_follow_a_hand_written_loop():
    """``c_t = sum_j w[j] (B x)_(t - 2 + j)`` a channel, zero before the
    row's first position, times ``C``: a loop in float32, in the order the
    chain is written, to the bit where each operation is its own program;
    jitted, XLA contracts a multiply and an add, within two units."""
    B, T, D, Kc = 2, 16, 8, 3
    r = jax.random.split(jax.random.key(0), 4)
    b, c, x = (np.asarray(jax.random.normal(k, (B, T, D))) for k in r[:3])
    w = np.asarray(jax.random.normal(r[3], (Kc, D)))
    f32, want = np.float32, np.zeros((B, T, D), np.float32)
    for n in range(B):
        for t in range(T):
            acc = np.zeros((D,), f32)
            for j in range(Kc):
                s = t - (Kc - 1) + j
                g = f32(b[n, s] * x[n, s]) if s >= 0 else np.zeros((D,), f32)
                acc = f32(acc + f32(g * w[j]))
            want[n, t] = f32(c[n, t] * acc)
    np.testing.assert_array_equal(hybrid.gated_conv(b, c, x, w), want)
    np.testing.assert_allclose(jax.jit(hybrid.gated_conv)(b, c, x, w), want,
                               rtol=3e-7, atol=1e-6)
    # in bfloat16 the chain is float32 inside and rounds once
    low = hybrid.gated_conv(*(jnp.asarray(a, jnp.bfloat16) for a in (b, c, x)), w)
    assert low.dtype == jnp.bfloat16
    r16 = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(
        low, hybrid.gated_conv(r16(b), r16(c), r16(x), w).astype(jnp.bfloat16))


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "routed"])
def test_a_convolution_layer_leaks_neither_across_rows_nor_from_later(dense):
    """Row 1 perturbed, row 0 does not move; positions past ``t``
    perturbed, positions up to ``t`` do not move: to the bit, through the
    whole layer (norm, ``in_proj``, the chain, ``out_proj``, the
    feed-forward); and the layer does look two positions back, no
    further."""
    cfg, T, t = _conv_cfg(), 32, 11
    stack = ("dense_" if dense else "") + "conv"
    lp = jax.tree_util.tree_map(
        lambda w: w[0], llama.init_params(cfg, jax.random.key(0))["layers"][stack])
    assert set(lp) >= {"in_proj", "conv_w", "out_proj"} and lp["conv_w"].shape == (3, 64)
    assert lp["in_proj"].shape == (64, 192) and "conv_b" not in lp
    layer = jax.jit(lambda h: hybrid._layer("conv", False, cfg, dense)(
        h, lp, 0.0, None)[0])
    h = jax.random.normal(jax.random.key(1), (2, T, 64))
    noise = jax.random.normal(jax.random.key(2), (2, T, 64))
    base = np.asarray(layer(h))
    other_row = np.asarray(layer(h.at[1].add(noise[1])))
    np.testing.assert_array_equal(other_row[0], base[0])
    assert np.abs(other_row[1] - base[1]).max() > 0.1
    later = np.asarray(layer(h.at[:, t + 1:].add(noise[:, t + 1:])))
    np.testing.assert_array_equal(later[:, :t + 1], base[:, :t + 1])
    assert np.abs(later[:, t + 1] - base[:, t + 1]).max() > 0.1
    # one position moved: it, the next and the one after see it, no other
    one = np.asarray(layer(h.at[:, t].add(noise[:, t])))
    moved = np.abs(one - base).max(axis=(0, 2)) > 0
    assert moved.nonzero()[0].tolist() == [t, t + 1, t + 2]


def test_a_trunk_of_convolutions_and_one_normed_attention_layer(monkeypatch):
    """The tree (``dense_conv`` by ``first_dense_layers``, q/k norm's two
    leaves in the ``attention`` kind alone), the runs (a routed layer a run
    of its own), the count, the kinds counted, the statistics of the four
    routed layers, XLA's normed rotation counted once."""
    cfg = _conv_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    assert list(params["layers"]) == ["attention", "conv", "dense_conv"]
    conv, attn = params["layers"]["conv"], params["layers"]["attention"]
    routed = {"router", "router_bias", "we_gate", "we_up", "we_down"}
    frame = {"norm1_w", "norm2_w"}
    assert set(conv) == frame | routed | {"in_proj", "conv_w", "out_proj"}
    assert set(attn) == frame | routed | {"wqkv", "wo", "q_norm", "k_norm"}
    assert set(params["layers"]["dense_conv"]) == frame | {
        "in_proj", "conv_w", "out_proj", "w1", "w2"}
    assert conv["conv_w"].shape == (3, 3, 64) and attn["q_norm"].shape == (1, 16)
    assert (np.asarray(attn["k_norm"]) == 1).all()
    assert llama.count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert [(s, at, ids) for s, at, ids, _ in hybrid._runs(cfg)] == [
        ("dense_conv", 0, [0]), ("attention", 0, [2]), ("conv", 0, [3]),
        ("conv", 1, [4]), ("conv", 2, [5])]
    monkeypatch.setattr(metrics, "ACTIVE", True)
    series = lambda family: metrics.registry().to_dict().get(
        family, {}).get("series", [])
    kinds = lambda: {s["labels"]["kind"]: s["value"]
                     for s in series("hvd_layer_kind_total")}
    normed = lambda: sum(
        s["value"] for s in series("hvd_rope_kernel_total")
        if s["labels"] == {"kernel": "norm_fwd", "path": "xla"})
    before, xla = kinds(), normed()
    h = jax.random.normal(jax.random.key(1), (2, 32, 64))
    step = jax.jit(lambda h, ls: hybrid.layer_stack(h, ls, cfg, with_stats=True))
    out, stats = step(h, params["layers"])
    assert kinds()["conv"] - before.get("conv", 0) == 4
    assert kinds()["attention"] - before.get("attention", 0) == 1
    assert normed() - xla == 1
    pairs, rows, fullest, layers = np.asarray(stats)
    assert layers == 4 and pairs == rows and 0 < fullest <= pairs
    assert np.isfinite(np.asarray(out)).all()
    text = step.lower(h, params["layers"]).compile().as_text()
    for scope in ("hvd_conv_mixer", "hvd_conv_mixer/hvd_gated_conv",
                  "hvd_attention/hvd_rope", "hvd_mlp/hvd_moe_route"):
        assert scope in text, scope
    # q/k norm is these two kinds' and no leaf of another's
    assert "q_norm" not in hybrid.layer_shapes(cfg, "conv")
    assert "q_norm" not in hybrid.layer_shapes(_latent_cfg(qk_norm=True), "mla")


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_qk_norm_in_the_attention_kind_norms_a_head_before_the_rotation(
        path, pallas_interpret):
    """One ``attention`` layer under ``cfg.qk_norm`` against the formula
    written out, forward and every gradient: ``q`` and ``k`` RMS-normed a
    head by ``q_norm`` / ``k_norm`` (not at 1 here), then rotated.  On XLA's
    path at heads of 64; where ``ops/rope.norm_rotate`` takes the rows
    (heads of 128, interpreted here) through its one pass."""
    from horovod_tpu.ops import rope as rotary
    pallas_interpret(path == "kernel")
    heads, kv, dh = (2, 1, 128) if path == "kernel" else (4, 2, 64)
    T, B, table = 64, 2, llama.RopeTable(theta=1e6)
    cfg = _cfg(layer_kinds=("attention",), trunk_norm="rmsnorm", qk_norm=True,
               d_model=heads * dh, n_heads=heads, n_kv_heads=kv,
               rope_tables=(("attention", table),))
    lp = jax.tree_util.tree_map(
        lambda w: w[0], llama.init_params(cfg, jax.random.key(0))["layers"]["attention"])
    lp["q_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.key(3), (dh,))
    lp["k_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.key(4), (dh,))
    h = jax.random.normal(jax.random.key(1), (B, T, heads * dh))
    w = jax.random.normal(jax.random.key(2), (B, T, heads * dh))
    rope = llama.rope_table(table, dh, T)
    assert rotary.norm_supported(
        jax.ShapeDtypeStruct((B, T, heads * dh), h.dtype), lp["q_norm"],
        *rope) == (path == "kernel")
    cos, sin = (t[None, :, None] for t in rope)
    live = fa.dense_mask(fa.causal_ranges(T), T)

    def normed_rot(x, weight):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * weight
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def dense(h, lp):
        u = hybrid.norm(h, lp["norm1_w"], None, cfg)
        q, k, v = jnp.split(u @ lp["wqkv"], (heads * dh, (heads + kv) * dh), -1)
        q = normed_rot(q.reshape(B, T, heads, dh), lp["q_norm"])
        k = normed_rot(k.reshape(B, T, kv, dh), lp["k_norm"])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, heads // kv, 2)) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p,
                       jnp.repeat(v.reshape(B, T, kv, dh), heads // kv, 2))
        a = h + o.reshape(B, T, heads * dh) @ lp["wo"]
        return a + hybrid._mlp(hybrid.norm(a, lp["norm2_w"], None, cfg), lp)

    layer = hybrid._layer("attention", False, cfg)
    got = jax.jit(jax.value_and_grad(
        lambda h, lp: (layer(h, lp, 0.0, None, rope)[0] * w).sum(), (0, 1)))(h, lp)
    want = jax.jit(jax.value_and_grad(
        lambda h, lp: (dense(h, lp) * w).sum(), (0, 1)))(h, lp)
    assert abs(float(got[0] - want[0])) < 1e-4 * max(float(jnp.abs(want[0])), 1.0)
    assert set(got[1][1]) == set(lp) and float(jnp.abs(got[1][1]["q_norm"]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()),
                                   rtol=2e-3)


def test_qk_norm_off_lowers_the_plain_kinds_to_the_parents_text():
    """A ``swa`` and an ``attention`` layer with routed experts, q/k norm
    off and ``router_eps`` 0.0: the trunk's lowered text is the parent
    commit's (a596a6b, jax 0.9.0, on the CPU)."""
    import hashlib
    cfg = _mellum_cfg(layer_kinds=("swa", "attention"), n_layers=2, n_experts=8,
                      expert_top_k=2, experts_held=4, moe_dispatch="dropless",
                      router_score="sigmoid")
    assert not cfg.qk_norm and cfg.router_eps == 0.0
    layers = jax.eval_shape(lambda k: llama.init_params(cfg, k)["layers"],
                            jax.random.key(0))
    assert not {"q_norm", "k_norm"} & set(layers["attention"])
    text = jax.jit(lambda h, ls: hybrid.layer_stack(
        h, ls, cfg, with_stats=True)).lower(
            jax.ShapeDtypeStruct((2, 128, H * DH), jnp.float32), layers).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "2f751bd468bcac0f"


def test_router_eps_is_added_to_the_chosen_sum_and_at_zero_to_nothing():
    """``route(eps=0.0)`` is today's to the bit and to the jaxpr; at 1e-6
    the chosen scores are divided by their sum plus it, which float32
    holds at four sigmoid scores; the field is the dropless experts'."""
    from horovod_tpu.models import moe
    tokens = jax.random.normal(jax.random.key(0), (64, 64))
    router = jax.random.normal(jax.random.key(1), (64, 32)) * 0.1
    bias = jnp.zeros((32,))
    i0, w0 = moe.route(tokens, router, 4, "sigmoid", bias)
    i1, w1 = moe.route(tokens, router, 4, "sigmoid", bias, eps=0.0)
    np.testing.assert_array_equal(w0, w1)
    scores = jax.nn.sigmoid(jnp.dot(tokens, router, precision="highest"))
    top = jnp.take_along_axis(scores, i0, -1)
    np.testing.assert_array_equal(w0, top / top.sum(-1, keepdims=True))
    jaxpr = lambda **kw: str(jax.make_jaxpr(lambda t, r, b: moe.route(
        t, r, 4, "sigmoid", b, **kw))(tokens, router, bias))
    assert jaxpr() == jaxpr(eps=0.0) != jaxpr(eps=1e-6)
    i2, w2 = moe.route(tokens, router, 4, "sigmoid", bias, eps=1e-6)
    np.testing.assert_array_equal(i2, i0)
    np.testing.assert_array_equal(w2, top / (top.sum(-1, keepdims=True) + 1e-6))
    assert float(jnp.abs(w2 - w0).max()) > 0 and float(w2.sum(-1).max()) < 1.0
    with pytest.raises(ValueError, match="router_eps"):
        llama.LlamaConfig(router_eps=1e-6)
