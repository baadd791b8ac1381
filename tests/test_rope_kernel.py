"""ops/rope.py: the rotate-half rotation as one Pallas kernel (interpret mode
on the CPU) against the forms that stand, ``llama.rotate`` and
``llama._rope``: values to the bit and the gradient within an ulp of bf16,
in bf16 and float32, a table every row's and a table a row, YaRN's factor
on the tables, a joined product cut into ``q``, ``k`` and ``v``; each
refusal (the trunk's own form, counted); what remat keeps and reruns; a toy
step whose gradient loses and gains no leaf; the lowering for the chip.
Then the pass that takes q/k norm with the rotation (``norm_rotate``, the
scanned llama trunk's where it norms ``q`` and ``k``) against
``llama._rmsnorm`` then ``llama._rope``, case for case the same."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import described_chip as _described_chip, pallas_eqns
from horovod_tpu import metrics
from horovod_tpu.models import hybrid, llama
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import rope

D = 128
BF, F32 = jnp.bfloat16, jnp.float32
YARN = llama.RopeTable(theta=10000.0, rope_type="yarn", factor=4,
                       original_max_position_embeddings=64, beta_fast=2,
                       beta_slow=0.125)
PLAIN = llama.RopeTable(theta=10000.0)


def _unfused(fn, *args):
    """``fn(*args)`` under ``jit`` with LLVM's optimiser off: left on, the
    CPU contracts a product and a sum into one fused multiply-add, which
    rounds once where the chip's vector unit (and numpy) round twice, and
    two ways of writing one sum then differ in the last bit."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args)


def _small_blocks(monkeypatch):
    """Blocks of 32 positions walked 16 at a time: two grid steps a row of
    64 and two tiles a block."""
    monkeypatch.setattr(rope, "_BLOCK", 32)
    monkeypatch.setattr(rope, "_ROWS", 16)


def _counts():
    family = metrics.registry().to_dict().get("hvd_rope_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"]): s["value"]
            for s in family.get("series", [])}


def _grew(before):
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}


def _within_an_ulp(got, want):
    """``got`` within one unit of the last place of ``want``, a bf16 or
    float32 array."""
    mantissa = 8 if want.dtype == BF else 24
    w = np.asarray(want.astype(F32), np.float64)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(w), 1e-30)))
                  - (mantissa - 1))
    return bool((np.abs(np.asarray(got.astype(F32), np.float64) - w)
                 <= ulp).all())


def _turn(x, cos, sin):
    """``x [B, T, H, 128]`` through the kernel, a call of one part."""
    B, T, H, _ = x.shape
    (out,) = rope.split_rotate(x.reshape(B, T, H * D), cos, sin, (H * D,),
                               (True,))
    return out.reshape(x.shape)


def _both(fn, ws):
    """``x -> (sum of fn(x)'s results times ws, its gradient)``, a function
    made anew at every call: what jax has traced under one setting of the
    interpreter's flag it would hand back under the other."""
    def loss(x):
        outs = fn(x)
        outs, weights = ((outs, ws) if isinstance(outs, tuple)
                         else ((outs,), (ws,)))
        return sum((o.astype(F32) * w).sum() for o, w in zip(outs, weights))
    return lambda x: jax.value_and_grad(loss)(x)


@pytest.mark.parametrize("table", ["plain", "yarn"])
@pytest.mark.parametrize("dtype", [BF, F32], ids=["bf16", "float32"])
def test_kernel_is_llama_rotate_to_the_bit(dtype, table, monkeypatch,
                                           pallas_interpret):
    """A table every row's (``[T, 64]``): values equal to the bit, the
    gradient within an ulp (here equal too: the transpose is the rotation
    by the negated angle, the sums autodiff's in another order)."""
    _small_blocks(monkeypatch)
    B, T, H = 2, 64, 3
    cos, sin = llama.rope_table(YARN if table == "yarn" else PLAIN, D, T)
    assert (float(cos[0, 0]) > 1.1) == (table == "yarn")     # YaRN's factor
    x = jax.random.normal(jax.random.key(0), (B, T, H, D)).astype(dtype)
    w = jax.random.normal(jax.random.key(1), (B, T, H, D))
    standing = lambda x: llama.rotate(x, cos, sin)
    kernel = lambda x: _turn(x, cos, sin)
    pallas_interpret(True)
    want, (_, dwant) = _unfused(standing, x), _unfused(_both(standing, w), x)
    got, (_, dgot) = _unfused(kernel, x), _unfused(_both(kernel, w), x)
    assert not pallas_eqns(_both(standing, w), x)
    assert [e.params["name"] for e in pallas_eqns(_both(kernel, w), x)] == [
        "hvd_rope_fwd", "hvd_rope_bwd"]
    assert got.dtype == dgot.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got.astype(F32)),
                                  np.asarray(want.astype(F32)))
    assert _within_an_ulp(dgot, dwant)


@pytest.mark.parametrize("dtype", [BF, F32], ids=["bf16", "float32"])
def test_kernel_is_llama_rope_to_the_bit(dtype, monkeypatch,
                                         pallas_interpret):
    """A table a row (``[B, T, 64]`` from ``positions [B, T]``, the rows
    starting elsewhere): the scanned llama trunk's rotation, which stays
    XLA's (PERF.md, PR 47), in the kernel's values."""
    _small_blocks(monkeypatch)
    B, T, H = 2, 64, 2
    positions = jnp.arange(T)[None] + jnp.asarray([[0], [37]])
    x = jax.random.normal(jax.random.key(0), (B, T, H, D)).astype(dtype)
    w = jax.random.normal(jax.random.key(1), (B, T, H, D))
    standing = lambda x: llama._rope(x, positions, 1e4)

    def kernel(x):      # ``_rope``'s tables, made where it makes them
        freqs = 1e4 ** (-jnp.arange(0, D // 2, dtype=F32) / (D // 2))
        angles = positions[..., None].astype(F32) * freqs
        return _turn(x, jnp.cos(angles), jnp.sin(angles))

    pallas_interpret(True)
    want, (_, dwant) = _unfused(standing, x), _unfused(_both(standing, w), x)
    got, (_, dgot) = _unfused(kernel, x), _unfused(_both(kernel, w), x)
    assert not pallas_eqns(_both(standing, w), x)
    assert len(pallas_eqns(_both(kernel, w), x)) == 2
    np.testing.assert_array_equal(np.asarray(got.astype(F32)),
                                  np.asarray(want.astype(F32)))
    assert float(jnp.abs(got[0].astype(F32) - got[1].astype(F32)).max()) > .1
    assert _within_an_ulp(dgot, dwant)


def test_joined_product_is_cut_and_rotated_in_one_call(monkeypatch,
                                                       pallas_interpret):
    """``q`` and ``k`` rotated and ``v`` as it is out of the ``wqkv``
    product's columns, each an array of its own; backward one call writes
    the three cotangents into one joined array."""
    _small_blocks(monkeypatch)
    B, T, H, Hkv = 2, 64, 4, 2
    widths = (H * D, Hkv * D, Hkv * D)
    cos, sin = llama.rope_table(PLAIN, D, T)
    a = jax.random.normal(jax.random.key(0), (B, T, sum(widths))).astype(BF)
    ws = [jax.random.normal(jax.random.key(1 + i), (B, T, w))
          for i, w in enumerate(widths)]

    def standing(a):
        q, k, v = jnp.split(a, (H * D, (H + Hkv) * D), axis=-1)
        q = llama.rotate(q.reshape(B, T, H, D), cos, sin)
        k = llama.rotate(k.reshape(B, T, Hkv, D), cos, sin)
        return q.reshape(B, T, -1), k.reshape(B, T, -1), v

    kernel = lambda a: rope.split_rotate(a, cos, sin, widths,
                                         (True, True, False))
    both = lambda f: _both(f, ws)
    pallas_interpret(True)
    want, (_, dwant) = _unfused(standing, a), _unfused(both(standing), a)
    assert not pallas_eqns(both(standing), a)
    assert rope.supported(a, cos, sin, widths)
    got, (_, dgot) = _unfused(kernel, a), _unfused(both(kernel), a)
    assert [e.params["name"] for e in pallas_eqns(both(kernel), a)] == [
        "hvd_rope_fwd", "hvd_rope_bwd"]
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g.astype(F32)),
                                      np.asarray(w_.astype(F32)))
    assert _within_an_ulp(dgot, dwant)


def _toy(**changes):
    """A trunk of a windowed and a full GQA layer at ``head_dim`` 128, a
    table each (Mellum's, small)."""
    return dataclasses.replace(llama.LlamaConfig(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, norm_eps=1e-5, dtype=jnp.float32, remat=True,
        remat_policy="full", layer_kinds=("swa", "attention"),
        trunk_norm="rmsnorm", sliding_window=24, ssm_inner=128,
        ssm_dt_rank=8, rope_tables=(("attention", YARN), ("swa", PLAIN))),
        **changes)


REFUSED = {
    # name: (positions, what the toy trunk changes, the tables' dtype,
    # interpreted); a trunk makes float32 tables only, so the tables' case
    # is asked of ``supported`` alone
    "head-of-64": (64, dict(n_heads=4, n_kv_heads=2, head_dim=0), F32,
                   True),
    "ragged-T": (72, {}, F32, True),
    "row-of-one-position": (1, {}, F32, True),
    "float16": (64, dict(dtype=jnp.float16), F32, True),
    "bf16-tables": (64, {}, BF, True),
    "off-the-chip": (64, {}, F32, False),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_refusals_take_the_trunks_form_and_are_counted(
        case, monkeypatch, pallas_interpret):
    """Each refusal: ``supported`` says no, and a layer of the trunk then
    rotates in ``llama.rotate``'s form and counts it, once a layer."""
    monkeypatch.setattr(metrics, "ACTIVE", True)
    monkeypatch.setattr(fa, "supported", lambda *a, **k: False)
    _small_blocks(monkeypatch)
    T, changes, tdtype, interpret = REFUSED[case]
    pallas_interpret(interpret)
    cfg = _toy(remat=False, **changes)
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    widths = (H * Dh, Hkv * Dh, Hkv * Dh)
    cos, sin = (t.astype(tdtype) for t in llama.rope_table(PLAIN, Dh, T))
    assert not rope.supported(
        jax.ShapeDtypeStruct((2, T, sum(widths)), cfg.dtype), cos, sin,
        widths)
    if tdtype != F32:
        return
    params = llama.init_params(cfg, jax.random.key(0))
    h = jnp.zeros((2, T, cfg.d_model), cfg.dtype)
    before = _counts()
    stack = lambda h, ls: hybrid.layer_stack(h, ls, cfg)
    assert not pallas_eqns(stack, h, params["layers"])
    assert _grew(before) == {("fwd", "xla"): 2}     # a layer a kind


def test_taken_calls_are_counted_once_a_call_site(monkeypatch,
                                                  pallas_interpret):
    monkeypatch.setattr(metrics, "ACTIVE", True)
    _small_blocks(monkeypatch)
    cos, sin = llama.rope_table(PLAIN, D, 64)
    x = jnp.zeros((1, 64, 2, D), BF)
    before = _counts()
    jax.make_jaxpr(jax.grad(lambda x: (
        _turn(_turn(x, cos, sin), cos, sin).astype(F32).sum())))(x)
    assert _grew(before) == {("fwd", "pallas"): 2, ("bwd", "pallas"): 2}


def test_remat_reruns_the_forward_kernel_and_keeps_nothing_of_x(
        monkeypatch, pallas_interpret):
    """Under ``jax.checkpoint`` with the ``full`` policy: the forward
    kernel twice (the pass and its rerun) and the backward once; what the
    forward hands the backward beside the layer's own input is the tables'
    size at most, nothing ``x``-sized."""
    _small_blocks(monkeypatch)
    B, T, H = 2, 64, 4
    cos, sin = llama.rope_table(PLAIN, D, T)
    x = jax.random.normal(jax.random.key(0), (B, T, H, D)).astype(BF)

    def layer(x):
        return jnp.tanh(_turn(x * 2, cos, sin).astype(F32)).sum()

    kept = jax.checkpoint(layer, policy=llama.remat_policy("full"))
    names = [e.params["name"] for e in pallas_eqns(jax.grad(kept), x)]
    assert sorted(names) == ["hvd_rope_bwd", "hvd_rope_fwd", "hvd_rope_fwd"]
    # without remat: the residuals of the rotation itself
    _, vjp = jax.vjp(lambda x: _turn(x, cos, sin), x)
    held = [a.shape for a in jax.tree_util.tree_leaves(vjp)
            if hasattr(a, "shape")]
    assert held and all(int(np.prod(s)) <= T * D // 2 for s in held)


def test_a_toy_step_loses_and_gains_no_gradient_leaf(monkeypatch,
                                                     pallas_interpret):
    """The trunk of several kinds, heads of 128: loss and every leaf of the
    gradient through the joined call beside the standing form's."""
    monkeypatch.setattr(metrics, "ACTIVE", True)
    _small_blocks(monkeypatch)
    # attention itself in XLA's form on both sides: the rotation alone moves
    monkeypatch.setattr(fa, "supported", lambda *a, **k: False)
    cfg = _toy()
    assert cfg.head_dim == D
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 32), 0, 64)
    # (made anew for each setting of the flag)
    step = lambda: jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(
        p, tokens, jnp.roll(tokens, -1, 1), cfg, llama.ParallelSpec())))
    pallas_interpret(False)
    before = _counts()
    want = step()(params)
    assert _grew(before) == {("fwd", "xla"): 2}     # a layer a kind
    pallas_interpret(True)
    before = _counts()
    got = step()(params)
    # the kernel's forward is built once more for remat's rerun
    assert _grew(before) == {("fwd", "pallas"): 4, ("bwd", "pallas"): 2}
    assert abs(float(got[0] - want[0])) < 1e-5
    flat = lambda g: dict(jax.tree_util.tree_flatten_with_path(
        jax.device_get(g))[0])
    got, want = flat(got[1]), flat(want[1])
    assert set(got) == set(want)
    for path, leaf in want.items():
        assert np.abs(leaf).max() > 0, path
        np.testing.assert_allclose(
            got[path], leaf, atol=1e-5 * np.abs(leaf).max(), rtol=1e-4,
            err_msg=str(path))


def test_a_trunk_without_tables_builds_no_rotation(monkeypatch,
                                                   pallas_interpret):
    """Granite's, Solar's and Phi's attention layers carry no positions: no
    call, no count."""
    monkeypatch.setattr(metrics, "ACTIVE", True)
    cfg = _toy(rope_tables=())
    params = llama.init_params(cfg, jax.random.key(0))
    h = jnp.zeros((1, 64, 256))
    scopes = lambda cfg: jax.make_jaxpr(
        lambda h, ls: hybrid.layer_stack(h, ls, cfg))(
            h, params["layers"]).pretty_print(name_stack=True)
    before = _counts()
    assert "hvd_rope" not in scopes(cfg)
    assert not _grew(before)
    assert "hvd_rope" in scopes(_toy())


CHIP = {
    # the Mellum cell's joined product of a layer; a table a row
    "mellum-qkv": ((1, 16384, 5120), (4096, 512, 512), (True, True, False),
                   (16384, 64)),
    "table-a-row": ((2, 8192, 4096), (4096,), (True,), (2, 8192, 64)),
}


@pytest.mark.parametrize("case", sorted(CHIP))
def test_rope_kernels_lower_for_the_chip(case, monkeypatch):
    """Mosaic takes both kernels at the benchmark's shapes (the 64-lane
    halves of the tables joined in VMEM, the roll on a head's lanes), and
    the compiled call reads the product and writes the parts with no slice,
    copy or concatenate of them beside it."""
    one_chip = _described_chip(monkeypatch)
    shape, widths, turned, table = CHIP[case]
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    a, cos = sds(shape, BF), sds(table, F32)
    assert rope.supported(a, cos, cos, widths)

    def loss(a, cos, sin, *ws):
        parts = rope.split_rotate(a, cos, sin, widths, turned)
        return sum((p.astype(F32) * w).sum() for p, w in zip(parts, ws))

    text = jax.jit(jax.value_and_grad(loss)).lower(
        a, cos, cos, *(sds(shape[:2] + (w,), BF) for w in widths)
    ).compile().as_text()
    assert "hvd_rope_fwd" in text and "hvd_rope_bwd" in text
    B, T, W = shape
    for line in text.splitlines():
        if f"bf16[{B},{T},{W}]" in line.split("=")[0]:
            assert " copy(" not in line and " concatenate(" not in line, line


# ------------------------------------------------- q/k norm and rotation

EPS = 1e-6
THETA = 1e6
ROWS = {"a-table-a-row": ((0, 37), 2), "one-table": ((5,), 1)}


def _tables(starts, T):
    """``(positions [len(starts), T], cos, sin)``, the tables by ``_rope``'s
    own expressions."""
    positions = jnp.arange(T)[None] + jnp.asarray(starts)[:, None]
    freqs = THETA ** (-jnp.arange(0, D // 2, dtype=F32) / (D // 2))
    angles = positions[..., None].astype(F32) * freqs
    return positions, jnp.cos(angles), jnp.sin(angles)


def _standing(a, w, positions):
    """``llama._attention``'s standing form on a product's rows."""
    B, T, W = a.shape
    x = llama._rmsnorm(a.reshape(B, T, W // D, D), w, EPS)
    return llama._rope(x, positions, THETA).reshape(B, T, W)


def _exact_rows(key, shape, dtype):
    """Rows whose heads' sums of squares are exact in float32 in any order:
    every entry a signed power of two between 1/4 and 4."""
    k1, k2 = jax.random.split(key)
    e = jax.random.randint(k1, shape, -2, 3).astype(F32)
    sign = jnp.where(jax.random.bernoulli(k2, 0.5, shape), 1.0, -1.0)
    return (sign * 2.0 ** e).astype(dtype)


def _within_ulps_of_the_head(got, want, n):
    """``got`` within ``n`` units of the last place of the largest number
    of its head (128 columns) in ``want``: a rotation's difference of two
    products may come out small, and is then no nearer than its terms."""
    mantissa = 8 if want.dtype == BF else 24
    w = np.asarray(want.astype(F32), np.float64)
    top = np.abs(w.reshape(w.shape[:-1] + (-1, D))).max(-1, keepdims=True)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(top, 1e-30))) - (mantissa - 1))
    off = np.abs(np.asarray(got.astype(F32), np.float64) - w)
    return bool((off.reshape(top.shape[:-1] + (D,)) <= n * ulp).all())


@pytest.mark.parametrize("heads", [4, 1], ids=["q-heads", "k-heads"])
@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("dtype", [BF, F32], ids=["bf16", "float32"])
def test_normed_pass_is_rmsnorm_then_rope(dtype, rows, heads, monkeypatch,
                                          pallas_interpret):
    """Tables ``[B, T, 64]`` and ``[1, T, 64]``.  The pass is float32 inside
    and rounds once (the chip's own compile of the standing form: ops/
    rope.py), so it is held to ``_rmsnorm`` then ``_rope`` on float32
    copies of the same rows and weight, rounded once: within one unit in
    the last place on any rows (four in float32, which shows an inverse
    root off by one through three products: the lane sums come in another
    order), to the bit where a head's sum of squares is exact whatever its
    order.
    Beside the standing form as the CPU compiles it, which rounds the
    scaled row to the rows' dtype on the way: within two units."""
    _small_blocks(monkeypatch)
    starts, _ = ROWS[rows]
    B, T = 2, 64
    positions, cos, sin = _tables(starts, T)
    assert cos.shape == (len(starts), T, D // 2)
    w = (1 + .3 * jax.random.normal(jax.random.key(1), (D,))).astype(dtype)
    standing = lambda a, w: _standing(
        a, w, jnp.broadcast_to(positions, (B, T)))
    once = lambda a, w: standing(a.astype(F32), w.astype(F32)).astype(dtype)
    kernel = lambda a, w: rope.norm_rotate(a, w, cos, sin, EPS)
    pallas_interpret(True)
    for a in ((3 * jax.random.normal(jax.random.key(0), (B, T, heads * D))
               ).astype(dtype),
              _exact_rows(jax.random.key(2), (B, T, heads * D), dtype)):
        assert rope.norm_supported(a, w, cos, sin)
        want, got = _unfused(once, a, w), _unfused(kernel, a, w)
        assert not pallas_eqns(standing, a, w)
        assert [e.params["name"] for e in pallas_eqns(kernel, a, w)] == [
            "hvd_rope_norm_fwd"]
        assert got.dtype == dtype and got.shape == a.shape
        assert _within_ulps_of_the_head(got, want, 1 if dtype == BF else 4)
        assert _within_ulps_of_the_head(got, _unfused(standing, a, w),
                                        2 if dtype == BF else 4)
    # (the second rows: exact sums)
    np.testing.assert_array_equal(np.asarray(got.astype(F32)),
                                  np.asarray(want.astype(F32)))
    if len(starts) > 1:
        assert float(jnp.abs(got[0].astype(F32)
                             - got[1].astype(F32)).max()) > .1


@pytest.mark.parametrize("heads", [4, 1], ids=["q-heads", "k-heads"])
@pytest.mark.parametrize("dtype", [BF, F32], ids=["bf16", "float32"])
def test_normed_pass_gradients_are_autodiffs(dtype, heads, monkeypatch,
                                             pallas_interpret):
    """The cotangents of the rows and of ``w`` against autodiff of the
    standing form.  In float32 they are its values to a few roundings; in
    bfloat16 autodiff rounds between its fusions where the kernel keeps
    float32 in VMEM (its second lane sum sixteen bits a term), so each is
    held to be no further than autodiff's own from the gradient computed in
    float32 on the same (bfloat16) values."""
    _small_blocks(monkeypatch)
    B, T = 2, 64
    positions, cos, sin = _tables((0, 37), T)
    a = (3 * jax.random.normal(jax.random.key(0), (B, T, heads * D))
         ).astype(dtype)
    w = (1 + .3 * jax.random.normal(jax.random.key(1), (D,))).astype(dtype)
    g = jax.random.normal(jax.random.key(2), (B, T, heads * D))
    both = lambda fn: jax.grad(
        lambda a, w: (fn(a, w).astype(F32) * g).sum(), (0, 1))
    standing = lambda a, w: _standing(a, w, positions)
    kernel = lambda a, w: rope.norm_rotate(a, w, cos, sin, EPS)
    pallas_interpret(True)
    want = _unfused(both(standing), a, w)
    got = _unfused(both(kernel), a, w)
    assert [e.params["name"] for e in pallas_eqns(both(kernel), a, w)] == [
        "hvd_rope_norm_fwd", "hvd_rope_norm_bwd"]
    true = _unfused(both(standing), a.astype(F32), w.astype(F32))
    for got_, want_, true_ in zip(got, want, true):
        assert got_.dtype == want_.dtype == dtype
        assert got_.shape == want_.shape
        got_, want_, true_ = (np.asarray(x.astype(F32), np.float64)
                              for x in (got_, want_, true_))
        scale = np.abs(true_).max()
        if dtype == F32:
            assert np.abs(got_ - want_).max() <= 2e-6 * scale
        else:
            # one rounding of the result at the most beside autodiff's own
            assert (np.abs(got_ - true_).max()
                    <= np.abs(want_ - true_).max() + 2.0 ** -8 * scale)
            assert np.abs(got_ - true_).max() <= 2.0 ** -7 * scale


def test_normed_remat_reruns_the_forward_kernel_and_keeps_the_rows(
        monkeypatch, pallas_interpret):
    """Under ``jax.checkpoint`` with the ``full`` policy: the forward
    kernel twice and the backward once; without remat what is held of the
    product is its rows alone (no inverse root ``[B, T, H]``, nothing in
    float32 of the rows' size), beside ``w`` and the tables."""
    _small_blocks(monkeypatch)
    B, T, H = 2, 64, 4
    _, cos, sin = _tables((0, 37), T)
    a = jax.random.normal(jax.random.key(0), (B, T, H * D)).astype(BF)
    w = jnp.ones((D,), BF)

    def layer(a, w):
        return jnp.tanh(rope.norm_rotate(a * 2, w, cos, sin, EPS
                                         ).astype(F32)).sum()

    kept = jax.checkpoint(layer, policy=llama.remat_policy("full"))
    names = [e.params["name"]
             for e in pallas_eqns(jax.grad(kept, (0, 1)), a, w)]
    assert sorted(names) == ["hvd_rope_norm_bwd", "hvd_rope_norm_fwd",
                             "hvd_rope_norm_fwd"]
    _, vjp = jax.vjp(lambda a, w: rope.norm_rotate(a, w, cos, sin, EPS),
                     a, w)
    held = sorted(((a_.shape, str(a_.dtype))
                   for a_ in jax.tree_util.tree_leaves(vjp)
                   if hasattr(a_, "shape")), key=str)
    assert held == sorted([((B, T, H * D), "bfloat16"), ((1, D), "float32"),
                           ((2, T, D // 2), "float32"),
                           ((2, T, D // 2), "float32")], key=str)


def _normed_toy(**changes):
    """SDAR's trunk, small: the scanned llama layers with q/k norm at
    ``head_dim`` 128."""
    return dataclasses.replace(llama.LlamaConfig(
        vocab_size=64, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=64, norm_eps=1e-6, rope_theta=1e6, dtype=jnp.float32,
        remat=True, remat_policy="full", qk_norm=True), **changes)


def _stack_step(cfg, T):
    """``(step, params)``: loss and gradient of the toy trunk on ``T``
    tokens a row, rows whose positions start elsewhere; the step made
    anew."""
    params = llama.init_params(cfg, jax.random.key(0))
    # (no norm weight at its initial one: its gradient is then a number)
    params["layers"] = dict(params["layers"], **{
        n: params["layers"][n] * (1 + .2 * jax.random.normal(
            jax.random.key(i), params["layers"][n].shape))
        for i, n in enumerate(("q_norm", "k_norm")) if n in params["layers"]})
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, 64)
    positions = jnp.arange(T)[None] + jnp.asarray([[0], [11]])

    def loss(p):
        h, _ = llama.hidden(p, tokens, cfg, llama.ParallelSpec(),
                            positions=positions)
        return (h.astype(F32) * jax.random.normal(
            jax.random.key(2), h.shape)).mean()

    return jax.jit(jax.value_and_grad(loss)), params


def test_a_normed_toy_trunk_loses_and_gains_no_gradient_leaf(
        monkeypatch, pallas_interpret):
    """The scanned trunk with q/k norm, heads of 128: loss and every leaf
    of the gradient through the normed pass beside the standing form's,
    the tables made once outside the layer scan."""
    monkeypatch.setattr(metrics, "ACTIVE", True)
    _small_blocks(monkeypatch)
    monkeypatch.setattr(fa, "supported", lambda *a, **k: False)
    cfg = _normed_toy()
    assert cfg.head_dim == D
    pallas_interpret(False)
    before = _counts()
    step, params = _stack_step(cfg, 32)
    want = step(params)
    assert _grew(before) == {("norm_fwd", "xla"): 1}    # the scan's body
    pallas_interpret(True)
    before = _counts()
    step, params = _stack_step(cfg, 32)
    got = step(params)
    # q and k; the forward is built once more for remat's rerun
    assert _grew(before) == {("norm_fwd", "pallas"): 4,
                             ("norm_bwd", "pallas"): 2}
    assert abs(float(got[0] - want[0])) < 1e-5 * abs(float(want[0]))
    flat = lambda g: dict(jax.tree_util.tree_flatten_with_path(
        jax.device_get(g))[0])
    got, want = flat(got[1]), flat(want[1])
    assert set(got) == set(want)
    for path, leaf in want.items():
        assert np.abs(leaf).max() > 0, path
        np.testing.assert_allclose(
            got[path], leaf, atol=1e-5 * np.abs(leaf).max(), rtol=1e-4,
            err_msg=str(path))
    # the tables: once a stack, none inside the scan
    text = jax.make_jaxpr(lambda p: llama.hidden(
        p, jnp.zeros((2, 32), jnp.int32), cfg, llama.ParallelSpec()))(
            params).pretty_print(name_stack=True)
    top, scanned = text.split(" scan[", 1)
    assert "hvd_rope" in top and " cos " in top and " sin " in top
    assert " cos " not in scanned and " sin " not in scanned
    assert "hvd_rope_norm_fwd" in scanned


NORMED_REFUSED = {
    # name: (positions a row, what the toy trunk changes, interpreted, the
    # series counted once a traced layer body)
    "off-the-chip": (32, {}, False, ("norm_fwd", "xla")),
    "heads-of-32": (32, dict(n_heads=8, n_kv_heads=4, head_dim=0), True,
                    ("norm_fwd", "xla")),
    "row-of-one-position": (1, {}, True, ("norm_fwd", "xla")),
    "ragged-T": (40, {}, True, ("norm_fwd", "xla")),
    "float16": (32, dict(dtype=jnp.float16), True, ("norm_fwd", "xla")),
    "no-qk-norm": (32, dict(qk_norm=False), True, ("fwd", "xla")),
}


@pytest.mark.parametrize("case", sorted(NORMED_REFUSED))
def test_normed_refusals_trace_the_standing_form_and_are_counted(
        case, monkeypatch, pallas_interpret):
    """Each refusal traces what the parent traces, equation for equation
    (no kernel, no table outside the scan), and counts the standing form
    once a traced layer body; a trunk without q/k norm keeps XLA's
    ``_rope`` whatever its shapes."""
    monkeypatch.setattr(metrics, "ACTIVE", True)
    monkeypatch.setattr(fa, "supported", lambda *a, **k: False)
    _small_blocks(monkeypatch)
    T, changes, interpret, series = NORMED_REFUSED[case]
    cfg = _normed_toy(remat=False, **changes)
    params = llama.init_params(cfg, jax.random.key(0))
    h = jnp.zeros((2, T, cfg.d_model), cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(T), (2, T))
    stack = lambda h, ls: llama._layer_stack(
        h, ls, cfg, llama.ParallelSpec(), positions)
    pallas_interpret(interpret)
    before = _counts()
    assert not pallas_eqns(stack, h, params["layers"])
    assert _grew(before) == {series: 1}
    text = jax.make_jaxpr(stack)(h, params["layers"]).pretty_print(
        name_stack=True)
    assert " cos " not in text.split(" scan[", 1)[0]
    # what the kernel's own test says of the same shapes
    Dh = cfg.head_dim
    table = jax.ShapeDtypeStruct((2, T, Dh // 2), F32)
    assert rope.norm_supported(
        jax.ShapeDtypeStruct((2, T, cfg.n_heads * Dh), cfg.dtype),
        jax.ShapeDtypeStruct((Dh,), cfg.dtype), table, table) == (
            case == "no-qk-norm")


def test_normed_weight_of_another_width_is_refused(pallas_interpret):
    pallas_interpret(True)
    table = jax.ShapeDtypeStruct((1, 256, D // 2), F32)
    rows = jax.ShapeDtypeStruct((1, 256, 2 * D), BF)
    assert rope.norm_supported(rows, jax.ShapeDtypeStruct((D,), BF), table,
                               table)
    assert not rope.norm_supported(
        rows, jax.ShapeDtypeStruct((2 * D,), BF), table, table)


def test_normed_calls_are_counted_once_a_call_site(monkeypatch,
                                                   pallas_interpret):
    monkeypatch.setattr(metrics, "ACTIVE", True)
    _small_blocks(monkeypatch)
    _, cos, sin = _tables((0,), 64)
    a, w = jnp.zeros((1, 64, 2 * D), BF), jnp.ones((D,), BF)
    turn = lambda a: rope.norm_rotate(a, w, cos, sin, EPS)
    before = _counts()
    jax.make_jaxpr(jax.grad(lambda a: turn(turn(a)).astype(F32).sum()))(a)
    assert _grew(before) == {("norm_fwd", "pallas"): 2,
                             ("norm_bwd", "pallas"): 2}


NORMED_CHIP = {
    # the SDAR cell's products of a layer, a table a row: (rows, table)
    "qknorm-rope-sdar-q": ((2, 8192, 4096), (2, 8192, 64)),
    "qknorm-rope-sdar-k": ((2, 8192, 512), (2, 8192, 64)),
}


@pytest.mark.parametrize("case", sorted(NORMED_CHIP))
def test_normed_kernels_lower_for_the_chip(case, monkeypatch):
    """Mosaic takes both normed kernels at SDAR's shapes (the lane sums of
    a head, the weight's partial sums a grid step), and the compiled calls
    read the product's rows and the cotangent's and write their results
    with no copy, transpose or convert of them beside the calls."""
    one_chip = _described_chip(monkeypatch)
    shape, table = NORMED_CHIP[case]
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    a, w, cos = sds(shape, BF), sds((D,), BF), sds(table, F32)
    assert rope.norm_supported(a, w, cos, cos)

    def loss(a, w, cos, sin, g):
        return (rope.norm_rotate(a, w, cos, sin, EPS).astype(F32) * g).sum()

    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        a, w, cos, cos, sds(shape, BF)).compile().as_text()
    assert "hvd_rope_norm_fwd" in text and "hvd_rope_norm_bwd" in text
    B, T, W = shape
    for line in text.splitlines():
        made = line.split("=")[0]
        if f"bf16[{B},{T},{W}]" in made or f"f32[{B},{T},{W}]" in made:
            assert not any(f" {op}(" in line for op in (
                "copy", "transpose", "convert", "reshape")), line
