"""ops/selective_scan.py: the Pallas kernels (interpret mode on the CPU)
and the ``lax.scan`` fallback against a float32 recurrence written here:
the output and all six gradients, the states kept at chunk boundaries, the
counter that says which path ran, and their lowering for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import described_chip as _described_chip
from horovod_tpu import metrics
from horovod_tpu.ops import selective_scan as ss

N = 16


def _states(xs, delta, A, B, C, D):
    """(s [Bt, T, Ch], every step's state [T, Bt, Ch, N]), float32."""
    def step(S, at):
        x, d, b, c = at
        S = jnp.exp(d[..., None] * A) * S + (d * x)[..., None] * b[:, None]
        return S, ((S * c[:, None]).sum(-1) + D * x, S)

    tm = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    _, (s, S) = jax.lax.scan(step, jnp.zeros((xs.shape[0],) + A.shape),
                             tuple(map(tm, (xs, delta, B, C))))
    return jnp.moveaxis(s, 0, 1), S


def _plain(*operands):
    return _states(*operands)[0]


def _operands(dtype, Bt, T, Ch, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    xs = jax.random.normal(k[0], (Bt, T, Ch)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(k[1], (Bt, T, Ch)) - 2)
    A = -jnp.exp(jax.random.normal(k[2], (Ch, N)) * 0.5)
    B = jax.random.normal(k[3], (Bt, T, N)).astype(dtype)
    C = jax.random.normal(k[4], (Bt, T, N)).astype(dtype)
    D = jax.random.normal(k[5], (Ch,))
    return (xs, delta, A, B, C, D), jax.random.normal(k[6], (Bt, T, Ch))


def _counts():
    family = metrics.registry().to_dict().get("hvd_ssm_scan_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"]): s["value"]
            for s in family.get("series", [])}


def _value_and_grads(fn, operands, w):
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
        argnums=tuple(range(6)))(*operands)


def _close(got, want, dtype):
    """Each gradient within a few roundings of the operands' dtype of the
    float32 recurrence's, measured against its largest entry."""
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("xs", "delta", "A", "B", "C", "D"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        err = float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        assert err <= tol * float(jnp.abs(b.astype(jnp.float32)).max()), (name, err)


@pytest.mark.parametrize("path", ["pallas", "xla"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("T,Ch", [(48, 384), (16, 384), (32, 1024)],
                         ids=["three-chunks-three-blocks", "one-chunk",
                              "two-chunks-two-blocks"])
def test_scan_and_its_six_gradients_follow_the_recurrence(path, dtype, T, Ch,
                                                          monkeypatch,
                                                          pallas_interpret):
    """A batch of two; a sequence of several chunks and of one; channel
    blocks of 128 and of 512."""
    pallas_interpret(path == "pallas")
    monkeypatch.setattr(ss, "_CHUNK", 16)
    operands, w = _operands(dtype, 2, T, Ch)
    assert ss.supported(*operands) == (path == "pallas")
    before = _counts()
    value, grads = _value_and_grads(ss.selective_scan, operands, w)
    if metrics.ACTIVE:
        after = _counts()
        assert {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)} == {("fwd", path): 1,
                                                     ("bwd", path): 1}
    # the recurrence on the operands as the kernel gets them, in float32
    exact = tuple(a.astype(jnp.float32) for a in operands)
    want_value, want = _value_and_grads(_plain, exact, w)
    want = tuple(g.astype(a.dtype) for g, a in zip(want, operands))
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert abs(float(value - want_value)) <= tol * float(
        jnp.abs(_plain(*exact) * w).sum())
    _close(grads, want, dtype)


def test_state_kept_at_a_chunk_boundary_is_the_sequential_state(monkeypatch,
        pallas_interpret):
    monkeypatch.setattr(ss, "_CHUNK", 8)
    operands, _ = _operands(jnp.float32, 2, 32, 256, seed=3)
    s, bounds = ss._scan_fwd_pallas(*operands)
    want_s, states = _states(*operands)
    np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)
    assert bounds.shape == (2, 4, N, 256) and not np.asarray(bounds[:, 0]).any()
    for k in range(1, 4):               # chunk k starts from step 8k - 1's state
        np.testing.assert_allclose(
            bounds[:, k], jnp.swapaxes(states[8 * k - 1], 1, 2),
            rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("change,reason", [
    (dict(Ch=200), "channels"), (dict(T=20), "positions"),
    (dict(dtype=jnp.float16), "dtype")])
def test_refused_shapes_take_the_plain_path(change, reason, pallas_interpret):
    kw = {"dtype": jnp.float32, "Bt": 1, "T": 16, "Ch": 128, **change}
    operands, w = _operands(**kw)
    assert reason in ss._refusal(*operands)
    got = ss.selective_scan(*operands)
    np.testing.assert_allclose(
        got.astype(jnp.float32),
        _plain(*(a.astype(jnp.float32) for a in operands)), rtol=2e-3, atol=2e-3)


def test_off_the_chip_the_plain_path_runs_without_being_asked():
    operands, _ = _operands(jnp.float32, 1, 16, 128)
    assert "backend" in ss._refusal(*operands)
    before = _counts()
    jax.jit(ss.selective_scan)(*operands)
    if metrics.ACTIVE:
        assert _counts().get(("fwd", "xla"), 0) == before.get(("fwd", "xla"), 0) + 1


def test_selective_scan_kernels_lower_for_the_chip(monkeypatch):
    """Mosaic takes the selective scan forward and backward at the
    benchmark's phi4-mini-flash cell: 8,192 positions of 5,120 channels
    of 16 states, bf16 ``xs``, ``B`` and ``C`` beside a float32 step."""
    one_chip = _described_chip(monkeypatch)
    T, Ch, N = 8192, 5120, 16
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    operands = (sds((1, T, Ch), jnp.bfloat16), sds((1, T, Ch), jnp.float32),
                sds((Ch, N), jnp.float32), sds((1, T, N), jnp.bfloat16),
                sds((1, T, N), jnp.bfloat16), sds((Ch,), jnp.float32))
    assert ss.supported(*operands)
    text = jax.jit(jax.grad(
        lambda *a: ss.selective_scan(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(6)))).lower(*operands).compile().as_text()
    assert "hvd_ssm_scan_fwd" in text and "hvd_ssm_scan_bwd" in text
