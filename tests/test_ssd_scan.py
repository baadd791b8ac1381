"""ops/ssd_scan.py: the chunked form in jax.numpy and the Pallas kernels
(interpret mode on the CPU) against the recurrence walked position by
position, written here in float32: the output and all six gradients over
one, two and eight chunks and over two groups, the refusals, the counter
that says which path ran, and their lowering for the chip.  (A strong
decay, the states kept at chunk boundaries and the kernels' own layout:
tests/test_ssd_states.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import described_chip as _described_chip
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
                                                          G, pallas_interpret):
    """A batch of two; one chunk, two and eight; one group of ``B`` and
    ``C`` for all heads and two groups of four."""
    pallas_interpret(path == "pallas")
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


@pytest.mark.parametrize("change,reason", [
    (dict(T=3 * Q // 2), "positions"), (dict(dtype=jnp.float16), "dtype")])
def test_refused_shapes_take_the_plain_path(change, reason, pallas_interpret):
    """The plain path takes what the kernels refuse: positions that are no
    multiple of the chunk in chunks of their common divisor."""
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


def test_ssd_scan_kernels_lower_for_the_chip(monkeypatch):
    """Mosaic takes the chunked state-space scan forward and backward at
    the benchmark's granite-4.0-h-micro cell: 8,192 positions of 64 heads
    of 64 channels over one group of 128 states in chunks of 256, bf16
    ``x``, ``B`` and ``C`` beside a float32 step; no ``[T, H, P, N]``
    array and no ``[Q, Q]`` tile a head in the compiled program."""
    one_chip = _described_chip(monkeypatch)
    T, H, P, N = 8192, 64, 64, 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    operands = (sds((1, T, H, P), jnp.bfloat16), sds((1, T, H), jnp.float32),
                sds((H,), jnp.float32), sds((1, T, 1, N), jnp.bfloat16),
                sds((1, T, 1, N), jnp.bfloat16), sds((H,), jnp.float32))
    assert sd.supported(*operands, 256)
    text = jax.jit(jax.grad(
        lambda *a: sd.ssd_scan(*a, 256).astype(jnp.float32).sum(),
        argnums=tuple(range(6)))).lower(*operands).compile().as_text()
    assert "hvd_ssd_chunk_fwd" in text and "hvd_ssd_chunk_bwd" in text
    assert f"{T},{H},{P},{N}]" not in text and "64,256,256]" not in text
