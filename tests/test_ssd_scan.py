"""ops/ssd_scan.py: the chunked form in jax.numpy and the Pallas kernels
(interpret mode on the CPU) against the recurrence walked position by
position, written here in float32: the output and all six gradients over
one, two and eight chunks and over two groups, a strong decay, the states
kept at chunk boundaries, the refusals and the counter that says which path
ran.  (Their lowering for the chip is in tests/test_flash_attention.py, the
one file that describes the chip.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.ops import ssd_scan as sd

H, P, N, Q = 8, 16, 16, 16


def _states(x, delta, A, B, C, D):
    """(y [Bt, T, H, P], every step's state [T, Bt, H, P, N]), float32."""
    R = x.shape[2] // B.shape[2]

    def step(S, at):
        xt, d, b, c = at            # [Bt, H, P], [Bt, H], [Bt, G, N] twice
        b, c = (jnp.repeat(a, R, axis=1) for a in (b, c))
        S = (jnp.exp(d * A)[..., None, None] * S
             + (d[..., None] * xt)[..., None] * b[:, :, None, :])
        return S, ((S * c[:, :, None, :]).sum(-1) + D[:, None] * xt, S)

    tm = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    S0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:])
    _, (y, S) = jax.lax.scan(step, S0, tuple(map(tm, (x, delta, B, C))))
    return jnp.moveaxis(y, 0, 1), S


def _plain(*operands):
    return _states(*operands)[0]


def _operands(dtype, Bt, T, G=1, seed=0, decay=1.0, heads=H):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (Bt, T, heads, P)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(k[1], (Bt, T, heads)) - 1)
    A = -jnp.exp(jax.random.normal(k[2], (heads,)) * 0.5) * decay
    B = jax.random.normal(k[3], (Bt, T, G, N)).astype(dtype)
    C = jax.random.normal(k[4], (Bt, T, G, N)).astype(dtype)
    D = jax.random.normal(k[5], (heads,))
    return (x, delta, A, B, C, D), jax.random.normal(k[6], (Bt, T, heads, P))


def _counts():
    family = metrics.registry().to_dict().get("hvd_ssd_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"]): s["value"]
            for s in family.get("series", [])}


def _value_and_grads(fn, operands, w):
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
        argnums=tuple(range(6)))(*operands)


def _close(got, want, tol):
    """Each gradient within ``tol`` of the recurrence's, measured against
    its largest entry."""
    for name, a, b in zip(("x", "delta", "A", "B", "C", "D"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.isfinite(a).all()), name
        err = float(jnp.abs(a - b).max())
        assert err <= tol * float(jnp.abs(b).max()), (name, err)


@pytest.mark.parametrize("path", ["pallas", "xla"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunks,G", [(1, 1), (2, 1), (8, 1), (2, 2)],
                         ids=["one-chunk", "two-chunks", "eight-chunks",
                              "two-chunks-two-groups"])
def test_scan_and_its_six_gradients_follow_the_recurrence(path, dtype, chunks,
                                                          G, monkeypatch):
    """A batch of two; one chunk, two and eight; one group of ``B`` and
    ``C`` for all heads and two groups of four."""
    monkeypatch.setattr(sd, "_INTERPRET", path == "pallas")
    operands, w = _operands(dtype, 2, chunks * Q, G)
    assert sd.supported(*operands, Q) == (path == "pallas")
    before = _counts()
    value, grads = _value_and_grads(lambda *a: sd.ssd_scan(*a, Q), operands, w)
    if metrics.ACTIVE:
        after = _counts()
        assert {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)} == {("fwd", path): 1,
                                                     ("bwd", path): 1}
    # the recurrence on the operands as the scan gets them, in float32
    exact = tuple(a.astype(jnp.float32) for a in operands)
    want_value, want = _value_and_grads(_plain, exact, w)
    want = tuple(g.astype(a.dtype) for g, a in zip(want, operands))
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert abs(float(value - want_value)) <= tol * float(
        jnp.abs(_plain(*exact) * w).sum())
    _close(grads, want, 2e-5 if dtype == jnp.float32 else 2.5e-2)


def test_blocks_of_heads_share_a_groups_products(monkeypatch):
    """Two groups of eight heads, four heads a grid step: ``B C^T`` made at
    a group's first block, ``dB`` and ``dC`` added up over its two."""
    monkeypatch.setattr(sd, "_INTERPRET", True)
    monkeypatch.setattr(sd, "_HEADS", (4,))
    operands, w = _operands(jnp.float32, 1, 2 * Q, G=2, heads=16, seed=5)
    assert sd._head_block(16, 2) == 4
    value, grads = _value_and_grads(lambda *a: sd.ssd_scan(*a, Q), operands, w)
    want_value, want = _value_and_grads(_plain, operands, w)
    assert abs(float(value - want_value)) <= 1e-5 * abs(float(want_value))
    _close(grads, want, 2e-5)


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_a_strong_decay_neither_overflows_nor_loses_the_state(path,
                                                              monkeypatch):
    """``delta A`` near -20 a position: a chunk's running sum passes -300,
    whose exponential is 0 in float32 and whose inverse would be inf;
    every exponent is a difference that is never positive, so nothing
    overflows and nothing is NaN, forward or backward."""
    monkeypatch.setattr(sd, "_INTERPRET", path == "pallas")
    operands, w = _operands(jnp.float32, 1, 4 * Q, seed=2, decay=40.0)
    x, delta, A = operands[:3]
    assert float((delta * A).min()) < -20
    assert float(jnp.cumsum((delta * A)[0, :Q], 0).min()) < -100
    value, grads = _value_and_grads(lambda *a: sd.ssd_scan(*a, Q), operands, w)
    want_value, want = _value_and_grads(_plain, operands, w)
    assert np.isfinite(float(value))
    assert abs(float(value - want_value)) <= 1e-5 * abs(float(want_value))
    _close(grads, want, 1e-3)


def test_state_kept_at_a_chunk_boundary_is_the_sequential_state(monkeypatch):
    monkeypatch.setattr(sd, "_INTERPRET", True)
    operands, _ = _operands(jnp.float32, 2, 4 * Q, seed=3)
    y, bounds = sd._scan_fwd_pallas(*operands[:5], Q)
    want_y, states = _states(*operands[:5], jnp.zeros((H,)))
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    assert bounds.shape == (2, 4, H, P, N) and not np.asarray(bounds[:, 0]).any()
    _, plain_bounds = sd._scan_fwd_xla(*operands[:5], Q)
    for k in range(1, 4):               # chunk k starts from step Q k - 1's state
        np.testing.assert_allclose(bounds[:, k], states[Q * k - 1],
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(plain_bounds[:, k], states[Q * k - 1],
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("change,reason", [
    (dict(T=3 * Q // 2), "positions"), (dict(dtype=jnp.float16), "dtype")])
def test_refused_shapes_take_the_plain_path(change, reason, monkeypatch):
    """The plain path takes what the kernels refuse: positions that are no
    multiple of the chunk in chunks of their common divisor."""
    monkeypatch.setattr(sd, "_INTERPRET", True)
    kw = {"dtype": jnp.float32, "Bt": 1, "T": Q, **change}
    operands, w = _operands(**kw)
    assert reason in sd._refusal(*operands, Q)
    before = _counts()
    got = sd.ssd_scan(*operands, Q)
    if metrics.ACTIVE:
        assert _counts().get(("fwd", "xla"), 0) == before.get(("fwd", "xla"), 0) + 1
    np.testing.assert_allclose(
        got.astype(jnp.float32),
        _plain(*(a.astype(jnp.float32) for a in operands)), rtol=2e-3, atol=2e-3)


def test_on_the_chip_the_kernels_want_whole_tiles(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    f = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    ok = (s(1, 512, 64, 64), f(1, 512, 64), f(64), s(1, 512, 1, 128),
          s(1, 512, 1, 128), f(64))
    assert sd._refusal(*ok, 256) is None
    assert "multiples of 128" in sd._refusal(*ok, 64)
    small = (s(1, 512, 8, 16), f(1, 512, 8), f(8), s(1, 512, 1, 16),
             s(1, 512, 1, 16), f(8))
    assert "multiples of 128" in sd._refusal(*small, 256)
    assert "disagree" in sd._refusal(*ok[:3], s(1, 512, 3, 128), ok[4], ok[5],
                                     256)
    twelve = (s(1, 512, 12, 64), f(1, 512, 12), f(12)) + ok[3:5] + (f(12),)
    assert "heads a group" in sd._refusal(*twelve, 256)


def test_off_the_chip_the_plain_path_runs_without_being_asked():
    operands, _ = _operands(jnp.float32, 1, Q)
    assert "backend" in sd._refusal(*operands, Q)
    before = _counts()
    jax.jit(lambda *a: sd.ssd_scan(*a, Q))(*operands)
    if metrics.ACTIVE:
        assert _counts().get(("fwd", "xla"), 0) == before.get(("fwd", "xla"), 0) + 1



@pytest.mark.parametrize("path", ["pallas", "xla"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_layout_taken_and_returned_is_the_same_scan(path, dtype,
                                                                monkeypatch):
    """``ssd_scan_turned`` on ``x^T [Bt, H, P, T]`` is ``ssd_scan`` between
    two transposes: ``y^T`` to the bit (the same kernels, ``D x`` added
    element by element in the other layout) and the six gradients, ``dD``'s
    sum in another order; off the kernels it is that function itself."""
    monkeypatch.setattr(sd, "_INTERPRET", path == "pallas")
    operands, w = _operands(dtype, 2, 4 * Q, G=2)
    turn = lambda a: jnp.transpose(a, (0, 2, 3, 1))
    turned = (turn(operands[0]),) + operands[1:]
    before = _counts()
    value, grads = _value_and_grads(
        lambda *a: sd.ssd_scan_turned(*a, Q), turned, turn(w))
    if metrics.ACTIVE:
        after = _counts()
        assert {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)} == {("fwd", path): 1,
                                                     ("bwd", path): 1}
    want_value, want = _value_and_grads(lambda *a: sd.ssd_scan(*a, Q),
                                        operands, w)
    y = sd.ssd_scan(*operands, Q).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sd.ssd_scan_turned(*turned, Q).astype(jnp.float32)),
        np.asarray(turn(y)))
    # the same terms added up in another order
    assert abs(float(value - want_value)) <= 1e-6 * float(jnp.abs(y * w).sum())
    _close((jnp.transpose(grads[0], (0, 3, 1, 2)),) + grads[1:], want, 1e-5)
