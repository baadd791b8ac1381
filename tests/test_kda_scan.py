"""ops/kda_scan.py: the chunked form in jax.numpy and the Pallas kernels
(interpret mode on the CPU) against the recurrence walked position by
position in float64: the output and all five gradients over chunk sizes,
lengths that are and are not a multiple of the chunk, ``beta`` near 0 and
near 2, a decay whose running sums pass -1,000 (finite, and the walk's),
the states kept at chunk boundaries, the triangular inverse, the refusals
and the counter that says which path ran, and the four kernels' lowering
for the chip.  (The tile kernels alone are tests/test_kda_tiles.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import described_chip as _described_chip
from horovod_tpu import metrics
from horovod_tpu.ops import kda_scan as kd

H, K, V = 2, 16, 16


def walk(q, k, v, g, beta):
    """(o [Bt, T, H, V], every step's state [T, Bt, H, K, V]), in the
    operands' precision."""
    def step(S, at):
        qt, kt, vt, gt, bt = at     # [Bt, H, K] x2, [Bt, H, V], [Bt, H, K], [Bt, H]
        S = jnp.exp(gt)[..., None] * S
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, S))
        S = S + kt[..., None] * u[..., None, :]
        return S, (jnp.einsum("bhk,bhkv->bhv", qt, S) * qt.shape[-1] ** -0.5, S)

    tm = lambda a: jnp.moveaxis(a, 1, 0)
    S0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], q.dtype)
    _, (o, S) = jax.lax.scan(step, S0, tuple(map(tm, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), S


def _operands(T, Bt=2, seed=0, decay=1.0, beta=None, heads=H, dtype=jnp.float32):
    r = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(r[0], (Bt, T, heads, K)))
    k = unit(jax.random.normal(r[1], (Bt, T, heads, K)))
    v = jax.random.normal(r[2], (Bt, T, heads, V))
    g = -decay * jax.nn.softplus(jax.random.normal(r[3], (Bt, T, heads, K)))
    b = (2 * jax.nn.sigmoid(jax.random.normal(r[4], (Bt, T, heads)))
         if beta is None else jnp.full((Bt, T, heads), beta))
    w = jax.random.normal(r[5], (Bt, T, heads, V))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, b), w


def _want(operands, w):
    """The walk's value and gradients, in float64."""
    with jax.enable_x64(True):
        ops64 = tuple(jnp.asarray(np.asarray(a, np.float64)) for a in operands)
        w64 = jnp.asarray(np.asarray(w, np.float64))
        value, grads = jax.value_and_grad(
            lambda *a: (walk(*a)[0] * w64).sum(), argnums=tuple(range(5)))(*ops64)
        return float(value), [np.asarray(x) for x in grads], np.asarray(walk(*ops64)[0])


def _got(operands, w, chunk):
    value, grads = jax.value_and_grad(
        lambda *a: (kd.kda_scan(*a, chunk).astype(jnp.float32) * w).sum(),
        argnums=tuple(range(5)))(*operands)
    return float(value), grads


def _close(got, want, tol, names=("q", "k", "v", "g", "beta")):
    for name, a, b in zip(names, got, want):
        a = np.asarray(a.astype(jnp.float32), np.float64)
        b = np.asarray(b, np.float64)
        assert a.shape == b.shape and np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), (
            name, np.abs(a - b).max(), np.abs(b).max())


def _counts(family="hvd_kda_scan_total"):
    family = metrics.registry().to_dict().get(family, {})
    return {(s["labels"]["kernel"], s["labels"]["path"]): s["value"]
            for s in family.get("series", [])}


CASES = [(16, 64), (32, 64), (64, 64), (64, 128), (16, 40), (64, 100), (8, 24), (64, 16)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk,T,heads", [(16, 64, 2), (64, 128, 8), (32, 32, 3)],
                         ids=["chunk16", "chunk64-8heads", "one-chunk-3heads"])
def test_kernels_and_their_gradients_follow_the_walk(pallas_interpret, chunk, T, heads,
                                                     dtype, tol):
    operands, w = _operands(T, Bt=1, seed=T + heads, heads=heads, dtype=dtype)
    assert kd.supported(*operands, chunk)
    before = _counts()
    value, grads = _got(operands, w, chunk)
    want_value, want_grads, _ = _want(operands, w)
    assert abs(value - want_value) <= tol * max(1.0, abs(want_value))
    _close(grads, want_grads, tol)
    if metrics.ACTIVE:
        after = _counts()
        assert after[("fwd", "pallas")] == before.get(("fwd", "pallas"), 0) + 1
        assert after[("bwd", "pallas")] == before.get(("bwd", "pallas"), 0) + 1
        assert after.get(("fwd", "xla"), 0) == before.get(("fwd", "xla"), 0)


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("beta", [1e-3, 1.999], ids=["beta-near-0", "beta-near-2"])
def test_beta_at_both_ends(pallas_interpret, path, beta):
    pallas_interpret(path == "pallas")
    operands, w = _operands(64, Bt=1, seed=5, beta=beta)
    value, grads = _got(operands, w, 32)
    want_value, want_grads, _ = _want(operands, w)
    assert abs(value - want_value) <= 5e-5 * max(1.0, abs(want_value))
    _close(grads, want_grads, 1e-4)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_decay_whose_sums_pass_minus_1000_is_finite_and_the_walks(
        pallas_interpret, path):
    """``A`` at 16 and a softplus near 1: a chunk of 64 sums to under
    -1,000 in every channel, where ``exp(-G)`` is no float32."""
    pallas_interpret(path == "pallas")
    operands, w = _operands(128, Bt=1, seed=9, decay=32.0)
    g = operands[3]
    sums = jnp.cumsum(g.reshape(1, 2, 64, H, K), axis=2)
    assert float(sums[:, :, -1].max()) < -1000 and float(g.max()) < 0
    # a second set whose decay is strong in some channels and weak in others
    mixed = g * jnp.where(jnp.arange(K) % 2 == 0, 1.0, 1e-3)
    for ops in (operands, operands[:3] + (mixed,) + operands[4:]):
        value, grads = _got(ops, w, 64)
        want_value, want_grads, want_o = _want(ops, w)
        got_o = np.asarray(kd.kda_scan(*ops, 64), np.float64)
        assert np.isfinite(got_o).all()
        assert np.abs(got_o - want_o).max() <= 2e-5 * np.abs(want_o).max()
        assert abs(value - want_value) <= 2e-5 * max(1.0, abs(want_value))
        _close(grads, want_grads, 5e-5)


def test_the_states_kept_are_the_walks_at_chunk_boundaries(pallas_interpret):
    operands, _ = _operands(64, Bt=1, seed=2)
    _, S = walk(*operands)                              # [T, Bt, H, K, V]
    for interpret_ in (False, True):
        pallas_interpret(interpret_)
        _, res = kd._scan_fwd(*operands, 16)
        states = res[-1]                                # [Bt, nc, H, V, K]
        assert states.shape == (1, 4, H, V, K)
        assert float(jnp.abs(states[:, 0]).max()) == 0
        for c in (1, 2, 3):
            want = jnp.swapaxes(S[16 * c - 1], -1, -2)
            assert float(jnp.abs(states[:, c] - want).max()) < 1e-5


def test_refusals_say_why(pallas_interpret):
    (q, k, v, g, b), _ = _operands(64, Bt=1)
    assert kd._refusal(q, k, v, g, b, 64) is None
    assert "no multiple of the chunk" in kd._refusal(q, k, v, g, b, 48)
    assert "disagree" in kd._refusal(q, k[:, :32], v, g, b, 16)
    assert "dtype" in kd._refusal(q.astype(jnp.float16), k, v, g, b, 16)
    assert "[batch, T, heads, K]" in kd._refusal(q[0], k, v, g, b, 16)


def test_kda_scan_kernels_lower_for_the_chip(monkeypatch):
    """Mosaic takes the chunked gated delta rule forward and backward at
    the benchmark's solar-open2-250b cell: 8,192 positions of 8 heads whose
    keys and values are 128 wide in chunks of 64, bf16 ``q``, ``k``, ``v``
    beside float32 decays and ``beta``: the two chunk kernels and, since PR
    43, the two tile kernels (the triangular inverse's lane gather and
    float32 products among what only this compile sees).  The per-channel
    decay of the diagonal blocks (``[.., 16, 16, 128]``: 537 MB in float32,
    one array) is no array any more: forward and backward together take
    under 0.4 GB of temporaries (0.27; 0.65 when XLA made the tiles)."""
    one_chip = _described_chip(monkeypatch)
    T, H, K = 8192, 8, 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    wide = sds((1, T, H, K), jnp.bfloat16)
    operands = (wide, wide, wide, sds((1, T, H, K), jnp.float32),
                sds((1, T, H), jnp.float32))
    assert kd.supported(*operands, 64)
    compiled = jax.jit(jax.grad(
        lambda *a: kd.kda_scan(*a, 64).astype(jnp.float32).sum(),
        argnums=tuple(range(5)))).lower(*operands).compile()
    text = compiled.as_text()
    assert "hvd_kda_chunk_fwd" in text and "hvd_kda_chunk_bwd" in text
    assert "hvd_kda_tiles_fwd" in text and "hvd_kda_tiles_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9
