"""ops/mamba2_mixer.py: the Mamba-2 mixer's two elementwise chains as Pallas
kernels (interpret mode on the CPU) against their plain ``jax.numpy`` forms
and against a convolution written as a loop: results and every gradient in
float32 and in bf16, several blocks of positions so that the halo crosses a
block's edge in both directions, splits of one, two and three parts, the
first part and the gate's ``y`` turned (positions on the lanes) or not, the
refusals (each to the bit the plain form), the counter that says which
path ran, and their lowering for the chip."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import described_chip as _described_chip
from horovod_tpu import metrics
from horovod_tpu.ops import mamba2_mixer as mm

KC, BLOCK, ROWS, LANES = 4, 32, 16, 128


def _sizes(monkeypatch):
    """Blocks of 32 positions walked 16 at a time in tiles of 128 channels:
    two tiles of positions a block and two of channels at 160 or 256."""
    monkeypatch.setattr(mm, "_BLOCK", BLOCK)
    monkeypatch.setattr(mm, "_ROWS", ROWS)
    monkeypatch.setattr(mm, "_CHANNELS", LANES)
    monkeypatch.setattr(mm, "_TURN", (ROWS, LANES))


def _kernels(monkeypatch, pallas_interpret, on=True):
    pallas_interpret(on)
    _sizes(monkeypatch)


def _conv_operands(dtype, Bt, T, sizes, seed=0):
    k = jax.random.split(jax.random.key(seed), 4 + len(sizes))
    C = sum(sizes)
    xBC = jax.random.normal(k[0], (Bt, T, C)).astype(dtype)
    w = jax.random.normal(k[1], (KC, C)) * 0.5
    b = jax.random.normal(k[2], (C,)) * 0.1
    cts = [jax.random.normal(k[4 + i], (Bt, T, s))
           for i, s in enumerate(sizes)]
    return (xBC, w, b), cts


def _norm_operands(dtype, Bt, T, C, seed=0, unit=False):
    k = jax.random.split(jax.random.key(seed), 4)
    y = jax.random.normal(k[0], (Bt, T, C)).astype(dtype)
    z = (jax.random.normal(k[1], (Bt, T, C)) * 2).astype(dtype)
    w = jnp.ones((C,)) if unit else 1 + 0.3 * jax.random.normal(k[2], (C,))
    return (y, z, w), jax.random.normal(k[3], (Bt, T, C))


def _value_and_grads(fn, operands, cts):
    def loss(*a):
        outs = fn(*a)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return sum((o.astype(jnp.float32) * c).sum()
                   for o, c in zip(outs, cts))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(*operands)


def _close(got, want, tols, names):
    """Each gradient within its ``tol`` of the plain form's, measured
    against its largest entry."""
    for name, a, b, tol in zip(names, got, want, tols):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all()), name
        err = float(jnp.abs(a - b).max())
        assert err <= tol * float(jnp.abs(b).max()), (name, err)


def _counts():
    family = metrics.registry().to_dict().get("hvd_mixer_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"]): s["value"]
            for s in family.get("series", [])}


def _grew(before):
    after = _counts()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _conv_loop(xBC, w, b):
    """``silu(conv(xBC) + b)`` position by position and tap by tap, in
    float64 on the host: nothing before position 0."""
    x, w, b = (np.asarray(a, np.float64) for a in (xBC, w, b))
    pre = np.zeros_like(x)
    for t in range(x.shape[1]):
        pre[:, t] = b
        for j in range(KC):
            if t - (KC - 1) + j >= 0:
                pre[:, t] += w[j] * x[:, t - (KC - 1) + j]
    return pre / (1 + np.exp(-pre))


# the float32 bounds are a few roundings of sums made in another order; in
# bf16 both sides compute in float32 from the same bf16 operands and round
# once, so results agree to a rounding of the result (2**-8) and the
# parameters' float32 gradients as in float32
_TOL = {jnp.float32: (1e-5, 1e-5, 1e-5), jnp.bfloat16: (2 ** -8, 1e-5, 1e-5)}


@pytest.mark.parametrize("turned", [False, True], ids=["rows", "turned"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sizes", [(160,), (128, 32), (128, 16, 16)],
                         ids=["no-split", "two-parts", "three-parts"])
def test_convolution_and_its_gradients_follow_the_plain_form(dtype, sizes,
                                                             turned,
                                                             monkeypatch,
                                                             pallas_interpret):
    """A batch of two rows of three blocks of positions, each walked in two
    tiles of positions: the forward's halo is the block before, the
    backward's the block after; the first part's channels are a tile, or a
    tile and a narrower one.  ``turned``: the first part comes ``[Bt, size,
    T]`` and its cotangent goes in so, two tiles of positions a block."""
    _kernels(monkeypatch, pallas_interpret)
    operands, cts = _conv_operands(dtype, 2, 3 * BLOCK, sizes)
    if turned:
        cts[0] = jnp.swapaxes(cts[0], 1, 2)
    assert mm.supported(operands[0], sizes, turned)
    got = mm.conv_silu_split(*operands, sizes, turned)
    want = mm._conv_silu_split_xla(*operands, sizes)
    assert [o.shape[2] for o in got[1:]] == list(sizes[1:])
    assert got[0].shape == ((2, sizes[0], 3 * BLOCK) if turned
                            else (2, 3 * BLOCK, sizes[0]))
    want = ((jnp.swapaxes(want[0], 1, 2),) + want[1:]) if turned else want
    _close(got, want, (_TOL[dtype][0],) * len(sizes), sizes)
    value, grads = _value_and_grads(
        lambda *a: mm.conv_silu_split(*a, sizes, turned), operands, cts)
    want_value, want = _value_and_grads(
        lambda *a: mm._conv_silu_split_xla(*a, sizes, turned), operands, cts)
    assert abs(float(value - want_value)) <= _TOL[dtype][0] * sum(
        float(jnp.abs(c).sum()) for c in cts)
    _close(grads, want, _TOL[dtype], ("xBC", "conv_w", "conv_b"))


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_convolution_is_causal_from_position_zero(path, monkeypatch,
                                                  pallas_interpret):
    """Against the convolution written as a loop: positions 0..2 see zeros
    and not the row before (a batch of two: the second row's first
    positions must not read the first row's last), and every block's first
    positions see the block before."""
    _kernels(monkeypatch, pallas_interpret, path == "pallas")
    (xBC, w, b), _ = _conv_operands(jnp.float32, 2, 3 * BLOCK, (128,), seed=3)
    (got,) = mm.conv_silu_split(xBC, w, b, (128,))
    want = _conv_loop(xBC, w, b)
    np.testing.assert_allclose(got[:, :KC - 1], want[:, :KC - 1], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    # the cotangent of the last positions has nothing after it: dxBC of a
    # row's last position is d pre there times the tap of the position itself
    _, vjp = jax.vjp(lambda x: mm.conv_silu_split(x, w, b, (128,))[0], xBC)
    ct = jnp.zeros_like(xBC).at[:, -1].set(1.0)
    (dx,) = vjp(ct)
    assert not np.asarray(dx[:, :-KC]).any()
    pre = np.asarray(b + sum(w[j] * xBC[:, -KC + j] for j in range(KC)))
    s = 1 / (1 + np.exp(-pre))
    np.testing.assert_allclose(dx[:, -1], s * (1 + pre * (1 - s)) * w[KC - 1],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("turned", [False, True], ids=["rows", "turned"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("eps,unit", [(1e-5, False), (0.5, False),
                                      (1e-5, True)],
                         ids=["eps-1e-5", "eps-0.5", "unit-w"])
def test_gate_and_norm_and_their_gradients_follow_the_plain_form(
        dtype, eps, unit, turned, monkeypatch, pallas_interpret):
    """``eps`` 0.5 is as large as the mean square itself, so a kernel that
    dropped it or put it outside the root would read far off; ``w`` away
    from 1 tells ``dw`` and the scaling of ``dy``, ``dz`` apart.
    ``turned``: ``y`` comes ``[Bt, C, T]`` and ``dy`` goes out so."""
    _kernels(monkeypatch, pallas_interpret)
    (y, z, w), ct = _norm_operands(dtype, 2, 3 * BLOCK, 256, unit=unit)
    operands = (jnp.swapaxes(y, 1, 2) if turned else y, z, w)
    assert mm.supported(z, (256,), turned)
    got = mm.gated_rmsnorm(*operands, eps, turned)
    want = mm._gated_rmsnorm_xla(y, z, w, eps)
    _close((got,), (want,), _TOL[dtype][:1], ("o",))
    value, grads = _value_and_grads(
        lambda *a: mm.gated_rmsnorm(*a, eps, turned), operands, (ct,))
    want_value, want = _value_and_grads(
        lambda *a: mm._gated_rmsnorm_xla(*a, eps, turned), operands, (ct,))
    assert grads[0].shape == operands[0].shape
    assert abs(float(value - want_value)) <= _TOL[dtype][0] * float(
        jnp.abs(ct).sum())
    tol = _TOL[dtype][0]
    _close(grads, want, (tol, tol, 1e-5), ("y", "z", "gate_norm"))
    if not unit:
        # eps is read: another one gives another result
        assert float(jnp.abs(
            got.astype(jnp.float32)
            - mm.gated_rmsnorm(*operands, 2 * eps, turned).astype(jnp.float32)
        ).max()) > (1e-6 if eps < 0.1 else 1e-2)


def _refused(monkeypatch, pallas_interpret, why):
    """Operands' shapes ``(T, sizes)`` under the refusal ``why``."""
    pallas_interpret(why == "positions")
    if why == "backend":                  # the CPU, nothing flipped
        return 2 * BLOCK, (128, 16, 16)
    _sizes(monkeypatch)
    if why == "lanes":                    # a TPU, channels off the lanes
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        return 2 * BLOCK, (128, 16, 16)
    return BLOCK + ROWS, (128, 16, 16)    # a T the block does not divide


@pytest.mark.parametrize("chain", ["conv", "norm"])
@pytest.mark.parametrize("why,reason", [("backend", "backend is cpu"),
                                        ("lanes", "128 lanes"),
                                        ("positions", "positions")])
def test_refused_shapes_take_the_plain_form_to_the_bit(chain, why, reason,
                                                       monkeypatch,
                                                       pallas_interpret):
    """The branch lies outside the ``custom_vjp``: result and gradients are
    the plain form's own, bit for bit, and the counter says ``xla``."""
    T, sizes = _refused(monkeypatch, pallas_interpret, why)
    if chain == "conv":
        operands, cts = _conv_operands(jnp.bfloat16, 1, T, sizes, seed=1)
        assert reason in mm._refusal(operands[0], sizes)
        fn = lambda *a: mm.conv_silu_split(*a, sizes)
        plain = lambda *a: mm._conv_silu_split_xla(*a, sizes)
    else:
        operands, ct = _norm_operands(jnp.bfloat16, 1, T, sizes[0] + 16,
                                      seed=1)
        cts = (ct,)
        assert reason in mm._refusal(operands[0], operands[0].shape[2:])
        fn = lambda *a: mm.gated_rmsnorm(*a, 1e-5)
        plain = lambda *a: mm._gated_rmsnorm_xla(*a, 1e-5)
    assert not mm.supported(operands[0], sizes if chain == "conv"
                            else operands[0].shape[2:])
    before = _counts()
    got = _value_and_grads(fn, operands, cts)
    if metrics.ACTIVE:
        assert _grew(before) == {(f"{chain}_fwd", "xla"): 1}
    want = _value_and_grads(plain, operands, cts)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))
    text = jax.jit(fn).lower(*operands).as_text()
    assert "hvd_conv_silu" not in text and "hvd_gated_norm" not in text


def test_each_call_site_is_counted_once_with_its_kernel_and_path(
        monkeypatch, pallas_interpret):
    _kernels(monkeypatch, pallas_interpret)
    sizes = (128, 16, 16)
    operands, cts = _conv_operands(jnp.float32, 1, 2 * BLOCK, sizes)
    noperands, ct = _norm_operands(jnp.float32, 1, 2 * BLOCK, 128)
    before = _counts()
    mm.conv_silu_split(*operands, sizes)
    if metrics.ACTIVE:
        assert _grew(before) == {("conv_fwd", "pallas"): 1}
    # traced once, run three times: one call site
    step = jax.jit(lambda a, b: (
        _value_and_grads(lambda *o: mm.conv_silu_split(*o, sizes), a, cts),
        _value_and_grads(lambda *o: mm.gated_rmsnorm(*o, 1e-5), b, (ct,))))
    before = _counts()
    for _ in range(3):
        step(operands, noperands)
    if metrics.ACTIVE:
        assert _grew(before) == {(k, "pallas"): 1 for k in (
            "conv_fwd", "conv_bwd", "norm_fwd", "norm_bwd")}
    text = str(jax.make_jaxpr(step)(operands, noperands))
    for name in ("hvd_conv_silu_fwd", "hvd_conv_silu_bwd",
                 "hvd_gated_norm_fwd", "hvd_gated_norm_bwd"):
        assert name in text


def test_on_the_chip_the_kernels_want_whole_tiles(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    cell = s(1, 8192, 4352)
    assert mm._refusal(cell, (4096, 128, 128)) is None
    assert mm._refusal(cell, (4352,)) is None
    assert mm._refusal(s(1, 8192, 4096), (4096,)) is None
    assert "128 lanes" in mm._refusal(cell, (4096, 192, 64))
    assert "channels" in mm._refusal(cell, (4096, 128))
    assert "positions" in mm._refusal(s(1, 8192 + 128, 4352), (4352,))
    assert "positions" in mm._refusal(s(1, 8, 4352), (4352,))
    assert mm._refusal(cell, (4096, 128, 128), turned=True) is None
    assert "whole lanes" in mm._refusal(s(1, 64, 4352), (4352,), turned=True)
    assert mm._refusal(s(1, 64, 4352), (4352,)) is None
    assert "dtype" in mm._refusal(
        jax.ShapeDtypeStruct((1, 8192, 4352), jnp.float16), (4352,))
    # conv_w wider than a sublane tile of taps, or of other channels
    (xBC, w, b), _ = _conv_operands(jnp.bfloat16, 1, 256, (128,))
    monkeypatch.setattr(mm, "_count", lambda kernel, path: seen.append(
        (kernel, path)))
    seen = []
    mm.conv_silu_split(xBC, jnp.zeros((9, 128)), b, (128,))
    mm.gated_rmsnorm(xBC, xBC.astype(jnp.float32), b, 1e-5)
    assert seen == [("conv_fwd", "xla"), ("norm_fwd", "xla")]


@pytest.mark.parametrize("turned", [False, True], ids=["rows", "turned"])
def test_mamba2_mixer_kernels_lower_for_the_chip(turned, monkeypatch):
    """Mosaic takes the Mamba-2 mixer's four elementwise kernels at the
    benchmark's granite-4.0-h-micro cell: 8,192 positions, 4,352 convolved
    channels cut 4,096 / 128 / 128 under four taps, 4,096 gated ones, bf16
    operands beside float32 parameters; ``turned``, as the cell runs them,
    ``x`` written and ``y`` read ``[1, 4096, 8192]`` with the chunked scan
    on that layout between them and no transpose in the compiled chain."""
    from horovod_tpu.ops import ssd_scan as sd
    one_chip = _described_chip(monkeypatch)
    T, sizes, H, N = 8192, (4096, 128, 128), 64, 128
    C, Di = sum(sizes), sizes[0]
    bf, f32 = jnp.bfloat16, jnp.float32
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    assert mm.supported(sds((1, T, C), bf), sizes, turned)

    def chain(xBC, conv_w, conv_b, z, gate_w, delta, A, D):
        x, B, Cm = mm.conv_silu_split(xBC, conv_w, conv_b, sizes, turned)
        scan, heads = ((sd.ssd_scan_turned, (1, H, Di // H, T)) if turned
                       else (sd.ssd_scan, (1, T, H, Di // H)))
        y = scan(x.reshape(heads), delta, A, B.reshape(1, T, 1, N),
                 Cm.reshape(1, T, 1, N), D, 256)
        return mm.gated_rmsnorm(y.reshape(x.shape), z, gate_w, 1e-5,
                                turned).astype(f32).sum()

    text = jax.jit(jax.value_and_grad(chain, argnums=tuple(range(8)))).lower(
        sds((1, T, C), bf), sds((4, C), f32), sds((C,), f32),
        sds((1, T, Di), bf), sds((Di,), f32), sds((1, T, H), f32),
        sds((H,), f32), sds((H,), f32)).compile().as_text()
    for name in ("hvd_conv_silu_fwd", "hvd_conv_silu_bwd",
                 "hvd_gated_norm_fwd", "hvd_gated_norm_bwd",
                 "hvd_ssd_chunk_fwd", "hvd_ssd_chunk_bwd"):
        assert name in text
    if turned:
        # x, y and their cotangents never change layout in HBM
        assert f"bf16[1,{T},{H},{Di // H}]" not in text
        assert not re.search(
            rf"bf16\[1,{T},{Di}\]\S* (copy|transpose)\(", text)
