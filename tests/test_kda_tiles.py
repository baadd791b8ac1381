"""ops/kda_scan.py's tile kernels alone (``hvd_kda_tiles_fwd`` /
``hvd_kda_tiles_bwd``, interpret mode on the CPU) against the plain path's
tiles and autodiff's backward of them, their exponents' signs, the blocked
inverse against a float64 solve, their own counter.  (The scan they feed
is tests/test_kda_scan.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.ops import kda_scan as kd
from test_kda_scan import K, _close, _counts


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
def test_tile_kernels_follow_the_plain_tiles_and_autodiff(pallas_interpret, chunk, T,
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
def test_tile_kernels_at_both_ends_of_beta(pallas_interpret, beta):
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


def test_tiles_counter_counts_a_call_site_once_a_kernel_and_path(
        pallas_interpret):
    """A differentiated scan builds the tiles' forward twice (its backward
    makes them again) and their backward once, on either path;
    ``hvd_kda_scan_total`` counts as before."""
    if not metrics.ACTIVE:
        pytest.skip("metrics are off")
    operands, _ = _tile_operands(16, 32, 2, jnp.float32)
    operands = operands[:2] + (operands[0],) + operands[2:]      # q, k, v, g, beta
    for interpret_, path, other in ((False, "xla", "pallas"), (True, "pallas", "xla")):
        pallas_interpret(interpret_)
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
