"""ops/kda_scan.py: the chunked form in jax.numpy and the Pallas kernels
(interpret mode on the CPU) against the recurrence walked position by
position in float64: the output and all five gradients over chunk sizes,
lengths that are and are not a multiple of the chunk, ``beta`` near 0 and
near 2, a decay whose running sums pass -1,000 (finite, and the walk's),
the states kept at chunk boundaries, the triangular inverse, the refusals
and the counter that says which path ran; and the tile kernels alone
against the plain path's tiles and autodiff's backward of them, their
exponents' signs, the blocked inverse against a float64 solve, their own
counter.  (The kernels' lowering for the chip is in
tests/test_flash_attention.py, the one file that describes the chip.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(kd, "_INTERPRET", True)


CASES = [(16, 64), (32, 64), (64, 64), (64, 128), (16, 40), (64, 100), (8, 24), (64, 16)]


@pytest.mark.parametrize("chunk,T", CASES, ids=[f"chunk{c}-T{t}" for c, t in CASES])
def test_chunked_form_and_its_gradients_follow_the_walk(chunk, T):
    operands, w = _operands(T, seed=chunk + T)
    before = _counts()
    value, grads = _got(operands, w, chunk)
    want_value, want_grads, _ = _want(operands, w)
    assert abs(value - want_value) <= 2e-5 * max(1.0, abs(want_value))
    _close(grads, want_grads, 2e-5)
    after = _counts()
    if metrics.ACTIVE:
        assert after.get(("fwd", "xla"), 0) > before.get(("fwd", "xla"), 0)
        assert after.get(("bwd", "xla"), 0) > before.get(("bwd", "xla"), 0)
        assert after.get(("fwd", "pallas"), 0) == before.get(("fwd", "pallas"), 0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk,T,heads", [(16, 64, 2), (64, 128, 8), (32, 32, 3)],
                         ids=["chunk16", "chunk64-8heads", "one-chunk-3heads"])
def test_kernels_and_their_gradients_follow_the_walk(interpret, chunk, T, heads,
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
def test_beta_at_both_ends(monkeypatch, path, beta):
    monkeypatch.setattr(kd, "_INTERPRET", path == "pallas")
    operands, w = _operands(64, Bt=1, seed=5, beta=beta)
    value, grads = _got(operands, w, 32)
    want_value, want_grads, _ = _want(operands, w)
    assert abs(value - want_value) <= 5e-5 * max(1.0, abs(want_value))
    _close(grads, want_grads, 1e-4)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_decay_whose_sums_pass_minus_1000_is_finite_and_the_walks(monkeypatch, path):
    """``A`` at 16 and a softplus near 1: a chunk of 64 sums to under
    -1,000 in every channel, where ``exp(-G)`` is no float32."""
    monkeypatch.setattr(kd, "_INTERPRET", path == "pallas")
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


def test_the_states_kept_are_the_walks_at_chunk_boundaries(monkeypatch):
    operands, _ = _operands(64, Bt=1, seed=2)
    _, S = walk(*operands)                              # [T, Bt, H, K, V]
    for interpret_ in (False, True):
        monkeypatch.setattr(kd, "_INTERPRET", interpret_)
        _, res = kd._scan_fwd(*operands, 16)
        states = res[-1]                                # [Bt, nc, H, V, K]
        assert states.shape == (1, 4, H, V, K)
        assert float(jnp.abs(states[:, 0]).max()) == 0
        for c in (1, 2, 3):
            want = jnp.swapaxes(S[16 * c - 1], -1, -2)
            assert float(jnp.abs(states[:, c] - want).max()) < 1e-5


def test_unit_lower_inverse_and_its_gradient():
    L = jnp.tril(jax.random.normal(jax.random.key(0), (3, 16, 16)) * 0.5, -1)
    N = kd._unit_lower_inverse(L)
    eye = jnp.eye(16)
    assert float(jnp.abs(N @ (eye + L) - eye).max()) < 1e-4
    assert float(jnp.abs(jnp.triu(N, 1)).max()) == 0
    w = jax.random.normal(jax.random.key(1), N.shape)
    got = jax.grad(lambda L_: (kd._unit_lower_inverse(L_) * w).sum())(L)
    want = jax.grad(lambda L_: (jnp.linalg.inv(eye + jnp.tril(L_, -1)) * jnp.tril(w)).sum())(L)
    assert float(jnp.abs(got - want).max()) <= 1e-3 * float(jnp.abs(want).max())


def test_refusals_say_why(interpret):
    (q, k, v, g, b), _ = _operands(64, Bt=1)
    assert kd._refusal(q, k, v, g, b, 64) is None
    assert "no multiple of the chunk" in kd._refusal(q, k, v, g, b, 48)
    assert "disagree" in kd._refusal(q, k[:, :32], v, g, b, 16)
    assert "dtype" in kd._refusal(q.astype(jnp.float16), k, v, g, b, 16)
    assert "[batch, T, heads, K]" in kd._refusal(q[0], k, v, g, b, 16)


def test_off_the_chip_the_kernels_are_refused_by_backend():
    (q, k, v, g, b), _ = _operands(64, Bt=1)
    assert "backend is cpu" in kd._refusal(q, k, v, g, b, 64)
    assert not kd.supported(q, k, v, g, b, 64)


# ------------------------------------------------------- the tile kernels
# hvd_kda_tiles_fwd / hvd_kda_tiles_bwd (interpret mode) against _tiles and
# autodiff's backward of it: the plain path is their reference.

def _tile_operands(chunk, T, heads, dtype, seed=0, decay=1.0, beta=None):
    """((q, k, G, beta), (dT, dAqk)) of one row, from numpy: nothing to
    compile."""
    r = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (unit(r.standard_normal((1, T, heads, K))) for _ in range(2))
    g = -decay * np.logaddexp(0, r.standard_normal((1, T, heads, K)))
    b = (2 / (1 + np.exp(-r.standard_normal((1, T, heads)))) if beta is None
         else np.full((1, T, heads), beta))
    G = np.cumsum(g.reshape(1, T // chunk, chunk, heads, K), 2).reshape(g.shape)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    shape = (1, heads, T // chunk, chunk, chunk)
    return ((f32(q).astype(dtype), f32(k).astype(dtype), f32(G), f32(b)),
            (f32(r.standard_normal(shape)), f32(r.standard_normal(shape))))


def _plain_tiles(operands, cts, chunk):
    """_tiles and autodiff's backward of it, in one program."""
    def both(operands, cts):
        out, vjp = jax.vjp(lambda *a: kd._tiles(*a, chunk), *operands)
        return out, vjp(cts)
    return jax.jit(both)(operands, cts)


def _kernel_tiles(operands, cts, chunk):
    return jax.jit(lambda operands, cts: (
        kd._tiles_fwd_pallas(*operands, chunk),
        kd._tiles_bwd_pallas(*operands, *cts, chunk)))(operands, cts)


# (chunk, T, heads, dtype): two, three (a block of one) and eight heads (a
# grid step's whole block: the loop over pairs and the batched inverse)
TILE_CASES = [(16, 32, 2, "float32"), (16, 32, 8, "bfloat16"),
              (32, 64, 3, "float32"), (32, 64, 3, "bfloat16"),
              (64, 128, 2, "float32"), (64, 128, 2, "bfloat16")]
TILE_TOLS = {"float32": (2e-5, 2e-5), "bfloat16": (1e-2, 4e-2)}


@pytest.mark.parametrize("chunk,T,heads,dtype", TILE_CASES,
                         ids=[f"chunk{c}-T{t}-{h}heads-{d}" for c, t, h, d in TILE_CASES])
def test_tile_kernels_follow_the_plain_tiles_and_autodiff(interpret, chunk, T,
                                                          heads, dtype):
    tol, btol = TILE_TOLS[dtype]
    operands, cts = _tile_operands(chunk, T, heads, jnp.dtype(dtype),
                                   seed=chunk + heads)
    want, want_grads = _plain_tiles(operands, cts, chunk)
    got, grads = _kernel_tiles(operands, cts, chunk)
    assert all(a.dtype == jnp.float32 for a in got + grads)
    _close(got, want, tol, ("T", "Aqk"))
    for a in got:       # nothing above the diagonal
        assert float(np.abs(np.triu(np.asarray(a), 1)).max()) == 0
    _close(grads, want_grads, btol, ("dq", "dk", "dG", "dbeta"))


@pytest.mark.parametrize("beta", [1e-3, 1.999], ids=["beta-near-0", "beta-near-2"])
def test_tile_kernels_at_both_ends_of_beta(interpret, beta):
    operands, cts = _tile_operands(64, 64, 2, jnp.float32, seed=5, beta=beta)
    want, want_grads = _plain_tiles(operands, cts, 64)
    got, grads = _kernel_tiles(operands, cts, 64)
    _close(got, want, 5e-5, ("T", "Aqk"))
    _close(grads, want_grads, 1e-4, ("dq", "dk", "dG", "dbeta"))


@pytest.mark.parametrize("mixed", [False, True], ids=["strong", "strong-and-weak"])
def test_tile_kernels_exponents_are_never_positive(monkeypatch, mixed):
    """A decay whose sums pass -1,000: every argument the chunk functions
    hand to ``exp``, forward and backward, is at most 0, and the tiles are
    the plain path's."""
    (q, k, G, b), (dT, dA) = _tile_operands(64, 64, 2, jnp.float32, seed=9,
                                            decay=32.0)
    if mixed:       # strong in some channels, weak in others
        G = G * jnp.where(jnp.arange(K) % 2 == 0, 1.0, 1e-3)
    assert float(G[:, -1].max()) < -1000 or mixed
    want, want_grads = _plain_tiles((q, k, G, b), (dT, dA), 64)
    seen, exp = [], jnp.exp

    def watched(x):
        seen.append(float(jnp.max(x)))
        return exp(x)

    monkeypatch.setattr(kd, "_roll", lambda a, d: jnp.roll(a, d, 0))
    monkeypatch.setattr(jnp, "exp", watched)
    for h in range(2):
        at, bcol, brow = (q[0, :, h], k[0, :, h], G[0, :, h]), b[0, :, h, None], b[0, None, :, h]
        A, Aqk = kd._scores(*at, 16)
        dA_, db_rows, db_cols = (a[0] for a in kd._inverse_bwd(
            A[None], bcol[None], brow[None], dT[0, h:h + 1, 0], 16))
        grads = kd._scores_bwd(*at, dA_, dA[0, h, 0], 16)
        monkeypatch.setattr(jnp, "exp", exp)
        Tm = kd._blocked_inverse(bcol * A, 16) * brow
        _close((Tm, Aqk), (want[0][0, h, 0], want[1][0, h, 0]), 2e-5,
                     ("T", "Aqk"))
        _close(grads, [w[0, :, h] for w in want_grads[:3]], 5e-5,
                     ("dq", "dk", "dG"))
        _close((db_rows[:, 0] + db_cols[0],), (want_grads[3][0, :, h],),
                     5e-5, ("dbeta",))
        monkeypatch.setattr(jnp, "exp", watched)
    monkeypatch.setattr(jnp, "exp", exp)
    assert len(seen) > 60 and max(seen) <= 0.0, max(seen)


@pytest.mark.parametrize("C,s", [(64, 16), (32, 16), (48, 16), (16, 16), (24, 8)],
                         ids=lambda v: str(v))
def test_blocked_inverse_is_the_substitutions_and_a_float64_solves(C, s):
    """``beta`` 1.999 and unit keys: the matrix the power series loses."""
    r = jax.random.split(jax.random.key(C), 2)
    kk = jax.random.normal(r[0], (C, 16))
    kk = kk / jnp.linalg.norm(kk, axis=-1, keepdims=True)
    L = 1.999 * jnp.tril(kk @ kk.T, -1)
    got = kd._blocked_inverse(L, s)
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0
    assert float(jnp.abs(jnp.diagonal(got) - 1).max()) == 0
    want = np.linalg.inv(np.eye(C) + np.asarray(L, np.float64))
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got, np.float64) - want).max() <= 2e-5 * scale
    rows = np.asarray(kd._unit_lower_inverse(L), np.float64)
    assert np.abs(np.asarray(got, np.float64) - rows).max() <= 2e-5 * scale


def test_tiles_counter_counts_a_call_site_once_a_kernel_and_path(monkeypatch):
    """A differentiated scan builds the tiles' forward twice (its backward
    makes them again) and their backward once, on either path;
    ``hvd_kda_scan_total`` counts as before."""
    if not metrics.ACTIVE:
        pytest.skip("metrics are off")
    operands, _ = _tile_operands(16, 32, 2, jnp.float32)
    operands = operands[:2] + (operands[0],) + operands[2:]      # q, k, v, g, beta
    for interpret_, path, other in ((False, "xla", "pallas"), (True, "pallas", "xla")):
        monkeypatch.setattr(kd, "_INTERPRET", interpret_)
        loss = lambda *a: kd.kda_scan(*a, 16).sum()     # a trace of its own
        tiles, scans = _counts("hvd_kda_tiles_total"), _counts()
        jax.make_jaxpr(loss)(*operands)                 # traced, never run
        after = _counts("hvd_kda_tiles_total")
        assert after.get(("fwd", path), 0) == tiles.get(("fwd", path), 0) + 1
        assert after.get(("bwd", path), 0) == tiles.get(("bwd", path), 0)
        jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(5))))(*operands)
        after, scans_after = _counts("hvd_kda_tiles_total"), _counts()
        assert after[("fwd", path)] == tiles.get(("fwd", path), 0) + 3
        assert after[("bwd", path)] == tiles.get(("bwd", path), 0) + 1
        for kernel in ("fwd", "bwd"):
            assert after.get((kernel, other), 0) == tiles.get((kernel, other), 0)
            assert scans_after.get((kernel, other), 0) == scans.get((kernel, other), 0)
        assert scans_after[("fwd", path)] == scans.get(("fwd", path), 0) + 2
        assert scans_after[("bwd", path)] == scans.get(("bwd", path), 0) + 1
