"""Kimi Delta Attention's recurrence in its chunked form (the gated delta
rule with a decay a key channel, arXiv 2510.26692), as Pallas kernels for
TPU.

Per head a state ``S [K, V]`` that forgets channel by channel and is
corrected by the delta rule,

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(K)

with ``S_{-1} = 0``; ``q_t, k_t, g_t [K]`` (``g <= 0``), ``v_t [V]``,
``beta_t`` a scalar (in (0, 2) where negative eigenvalues are allowed).
With ``u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t)`` the update is
``S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T``, and a chunk of ``C``
positions that starts from ``S`` turns into matrix products.  With ``G``
the running sum of ``g`` within the chunk (float32, every entry
non-positive) and ``E[i, j, c] = exp(G_i[c] - G_j[c])``,

    A[i, j]   = sum_c k_i[c] k_j[c] E[i, j, c]      for j <  i, else 0
    Aqk[i, j] = sum_c q_i[c] k_j[c] E[i, j, c]      for j <= i, else 0
    T   = (I + Diag(beta) A)^-1 Diag(beta)          (unit lower triangular inverse)
    U   = T (V - (K * exp(G)) S)                    (the WY form's pseudo-values)
    O   = ((Q * exp(G)) S + Aqk U) / sqrt(K)
    S'  = Diag(exp(G_last)) S + (K * exp(G_last - G))^T U

**No exponential of a positive number.**  A chunk's ``G`` passes -1,000
at strong decays, so ``exp(-G)`` does not exist in float32 and the decay
cannot be pulled out of a ``[C, C]`` tile as a row factor times a column
factor.  The tiles are made by sub-chunks of 16 positions: a block below
the diagonal through a reference row, the first of the block's rows,
``exp(G_i - G_ref) <= 1`` on the row side and ``exp(G_ref - G_j) <= 1``
on the column side, a product on the MXU; a block on the diagonal
entry by entry, ``exp(G_i - G_j)`` for ``j <= i`` alone, in float32.  The
state's products see ``exp(G)``, ``exp(G_last - G)`` and ``exp(G_last)``
only.  Running sums, every exponential, the triangular inverse (forward
substitution, row by row) and the carried state are float32; the
products take the operands' dtype and accumulate in float32.

Two passes.  The tiles ``T`` and ``Aqk`` of every chunk at once are
elementwise work and small batched products and stay in XLA
(:func:`_tiles`; their backward is autodiff's, the inverse's its own
rule).  Only the ``T / C`` chunk states are walked one after another:

* ``hvd_kda_chunk_fwd`` takes a chunk's ``q, k, v, G, T, Aqk`` for a
  block of heads, keeps every head's state in VMEM scratch while the
  chunks go by, writes ``o`` and, as the backward's only residual beside
  the operands, the state each chunk starts from (``[Bt, T / C, H, V, K]``
  float32, the state transposed: its decay then runs along the lanes).
* ``hvd_kda_chunk_bwd`` walks the chunks in reverse with the state's
  cotangent as its carry, makes a chunk's ``U`` again and writes ``dq,
  dk, dv``, the running sum's cotangent and the two tiles' cotangents.

The grid is ``(batch, blocks of heads, chunks)``; the arrays are read as
``[Bt, T, H K]`` with a head's channels a block of lanes, so nothing is
transposed in HBM.  ``hvd_kda_scan_total{kernel, path}`` counts the calls
built, once per traced call site: ``kernel`` is ``fwd`` or ``bwd``,
``path`` is ``pallas`` or ``xla``.

Falls back cleanly: on another backend than a TPU and at shapes
:func:`supported` refuses, the same chunk functions (:func:`_chunk_fwd`,
:func:`_chunk_bwd`: the kernels call them on what they load) under a
``lax.scan`` over the chunks, every head at once, with the same
residuals; the choice is from shapes and backend, no knob.  A length that
is no multiple of the chunk is padded with positions that leave the state
as it is (``k = v = g = beta = 0``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import metrics as _metrics
from .flash_attention import _sds, _verdict

_INTERPRET = False  # flipped by tests to run kernels on CPU
_SUB = 16           # positions a sub-chunk: a diagonal block's side
# heads a grid step takes: their chains of small products are independent
# and fill one another's latencies
_HEADS = (8, 4, 2, 1)
# a backward grid step at 8 heads of 128 x 128 and a chunk of 64: fourteen
# blocks double-buffered (5 MB), every head's state (0.5 MB) and the
# unrolled heads' temporaries
_VMEM_LIMIT = 64 * 1024 * 1024

_m_kernels = _metrics.counter(
    "hvd_kda_scan_total",
    "Chunked gated-delta-rule (Kimi Delta Attention) scan calls built, one "
    "per traced call site; kernel is fwd or bwd, path is pallas "
    "(ops/kda_scan.py's kernels) or xla (the same chunked form in "
    "jax.numpy)",
    labels=("kernel", "path"))


def _count(kernel: str, path: str) -> None:
    if _metrics.ACTIVE:
        _m_kernels.inc(kernel=kernel, path=path)


def _acc(dtype):
    """What sums, exponentials and the state are kept in: float32 (float64
    for float64 operands, which only a test hands in)."""
    return jnp.promote_types(dtype, jnp.float32)


def _head_block(H: int) -> int:
    return next(hb for hb in _HEADS if H % hb == 0)


def _refusal(q, k, v, g, beta, chunk) -> Optional[str]:
    """Which test keeps the Pallas kernels off this call; None = they
    run."""
    if not _INTERPRET and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    if q.ndim != 4 or v.ndim != 4:
        return "q, k must be [batch, T, heads, K] and v [batch, T, heads, V]"
    Bt, T, H, K = q.shape
    V = v.shape[3]
    if (k.shape != q.shape or g.shape != q.shape or v.shape[:3] != (Bt, T, H)
            or beta.shape != (Bt, T, H)):
        return "operands disagree on batch, T, heads or K"
    if T % chunk:
        return f"{T} positions are no multiple of the chunk {chunk}"
    if not _INTERPRET and (chunk % 16 or K % 128 or V % 128):
        return (f"chunk {chunk} must be a multiple of 16, {K} key and {V} "
                "value channels a head of 128")
    if q.dtype not in (jnp.bfloat16, jnp.float32) or v.dtype != q.dtype:
        return f"dtype {q.dtype} is neither bfloat16 nor float32"
    return None


def supported(q, k, v, g, beta, chunk=64) -> bool:
    """True when the Pallas kernels can run these shapes on this
    backend."""
    return _verdict("kda_scan", _refusal(q, k, v, g, beta, chunk), q, v)


# ------------------------------------------------- the tiles of every chunk

@jax.custom_vjp
def _unit_lower_inverse(L):
    """``(I + L)^-1`` for ``L [..., C, C]`` strictly lower triangular, by
    forward substitution: row ``i`` is ``e_i - L[i, :] N`` over the rows
    made so far.  (The power series ``sum (-L)^n`` is the same matrix and
    cancels catastrophically at ``beta`` near 2.)"""
    C = L.shape[-1]

    def row(i, N):
        Li = lax.dynamic_slice_in_dim(L, i, 1, axis=-2)          # [.., 1, C]
        new = -jnp.matmul(Li, N, precision=lax.Precision.HIGHEST)
        return lax.dynamic_update_slice_in_dim(
            N, lax.dynamic_slice_in_dim(N, i, 1, axis=-2) + new, i, axis=-2)

    eye = jnp.broadcast_to(jnp.eye(C, dtype=L.dtype), L.shape) + 0 * L
    return lax.fori_loop(1, C, row, eye)


def _unit_lower_inverse_fwd(L):
    N = _unit_lower_inverse(L)
    return N, N


def _unit_lower_inverse_bwd(N, dN):
    NT = jnp.swapaxes(N, -1, -2)
    hi = lax.Precision.HIGHEST
    dL = -jnp.matmul(jnp.matmul(NT, jnp.tril(dN), precision=hi), NT,
                     precision=hi)
    return (jnp.tril(dL, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _tiles(q, k, G, beta, chunk):
    """-> (``T``, ``Aqk``) ``[Bt, H, T / chunk, chunk, chunk]`` in ``G``'s
    dtype, of every chunk at once.  ``q, k [Bt, T, H, K]``; ``G`` the
    running sums within each chunk; ``beta [Bt, T, H]``."""
    acc, dt = G.dtype, q.dtype
    Bt, T, H, K = q.shape
    nc, s = T // chunk, math.gcd(chunk, _SUB)
    ns = chunk // s
    sub = lambda a: a.reshape(Bt, nc, ns, s, H, K)
    qs, ks, Gs = sub(q), sub(k), sub(G)
    q32, k32 = qs.astype(acc), ks.astype(acc)
    # blocks below the diagonal: block row I through its first row
    ref = Gs[:, :, :, :1]                                     # [b n I 1 h c]
    rows = jnp.exp(Gs - ref)                                  # <= 1
    qd, kd = (q32 * rows).astype(dt), (k32 * rows).astype(dt)
    below = jnp.tri(ns, k=-1, dtype=bool)[:, :, None, None, None]   # [I J]
    cols = jnp.exp(jnp.minimum(ref[:, :, :, None, :] - Gs[:, :, None], 0.0))
    kr = jnp.where(below, k32[:, :, None] * cols, 0.0).astype(dt)   # [b n I J j h c]
    off = lambda a: jnp.einsum("bnIthc,bnIJjhc->bhnItJj", a, kr,
                               preferred_element_type=acc)
    # blocks on the diagonal: entry by entry
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i)[:, :, None, None]                         # [t j 1 1]
    E = jnp.exp(jnp.where(seen, Gs[:, :, :, :, None] - Gs[:, :, :, None],
                          -jnp.inf))                          # [b n I t j h c]
    P = k32[:, :, :, None] * E
    on = lambda a: jnp.moveaxis((a[:, :, :, :, None] * P).sum(-1), -1, 1)
    same = jnp.eye(ns, dtype=acc)[:, None, :, None]           # [I 1 J 1]
    whole = lambda below_, on_: (below_ + on_[..., None, :] * same).reshape(
        Bt, H, nc, chunk, chunk)
    Aqk = whole(off(qd), on(q32))
    A = whole(off(kd), on(k32) * (j < i))
    b = jnp.transpose(beta.astype(acc).reshape(Bt, nc, chunk, H),
                      (0, 3, 1, 2))                           # [b h n C]
    N = _unit_lower_inverse(b[..., None] * A)
    return N * b[..., None, :], Aqk


def _running_sums(g, chunk):
    Bt, T, H, K = g.shape
    return jnp.cumsum(g.reshape(Bt, T // chunk, chunk, H, K),
                      axis=2).reshape(g.shape)


def _sum_back(dG, chunk):
    """The running sums' cotangent back to ``g``'s."""
    Bt, T, H, K = dG.shape
    return lax.cumsum(dG.reshape(Bt, T // chunk, chunk, H, K), axis=2,
                      reverse=True).reshape(dG.shape)


# ------------------------------------------------ one chunk of one head
# Plain functions of two-dimensional arrays: the kernels call them on what
# they load, the plain path under vmap.  q, k, G [C, K]; v, o [C, V]; Tm,
# Aqk [C, C]; the state transposed, St [V, K].

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ())), acc=jnp.float32):
    return lax.dot_general(a, b, dims, preferred_element_type=acc)


def _decayed(q, k, G):
    """``exp(G)``, ``exp(G_last - G)``, ``exp(G_last) [1, K]`` and ``Q
    exp(G)``, ``K exp(G)``, ``K exp(G_last - G)``, in ``G``'s dtype: no
    exponent is positive."""
    acc = G.dtype
    last = G[-1:, :]
    e, w = jnp.exp(G), jnp.exp(last - G)
    k32 = k.astype(acc)
    return e, w, jnp.exp(last), q.astype(acc) * e, k32 * e, k32 * w


def _chunk_fwd(q, k, v, G, Tm, Aqk, St, scale):
    """-> (``o [C, V]``, the next chunk's ``St``), in ``G``'s dtype."""
    acc, dt = G.dtype, q.dtype
    dot = functools.partial(_dot, acc=acc)
    _, _, elast, qh, kh, kt = _decayed(q, k, G)
    Sd = St.astype(dt)
    R = v.astype(acc) - dot(kh.astype(dt), Sd, _NT)
    U = dot(Tm.astype(dt), R.astype(dt)).astype(dt)
    o = scale * (dot(qh.astype(dt), Sd, _NT) + dot(Aqk.astype(dt), U))
    return o, elast * St + dot(U, kt.astype(dt), _TN)


def _chunk_bwd(q, k, v, G, Tm, Aqk, St, do, dSt, scale):
    """-> (dq, dk [C, K], dv [C, V], dG [C, K], dT, dAqk [C, C], the
    earlier chunk's dSt), in ``G``'s dtype.  ``do`` the output's cotangent,
    ``dSt`` the next chunk's state's."""
    acc, dt = G.dtype, q.dtype
    dot = functools.partial(_dot, acc=acc)
    e, w, elast, qh, kh, kt = _decayed(q, k, G)
    Sd, dSd = St.astype(dt), dSt.astype(dt)
    Td, Ad = Tm.astype(dt), Aqk.astype(dt)
    khd, qhd, ktd = kh.astype(dt), qh.astype(dt), kt.astype(dt)
    R = (v.astype(acc) - dot(khd, Sd, _NT)).astype(dt)
    U = dot(Td, R).astype(dt)
    dos = (scale * do.astype(acc)).astype(dt)
    dU = (dot(Ad, dos, _TN) + dot(ktd, dSd, _NT)).astype(dt)
    dAqk = dot(dos, U, _NT)
    dqh = dot(dos, Sd)
    dkt = dot(U, dSd)
    dT = dot(dU, R, _NT)
    dR = dot(Td, dU, _TN)
    dRd = dR.astype(dt)
    dkh = -dot(dRd, Sd)
    dS0 = elast * dSt + dot(dos, qhd, _TN) - dot(dRd, khd, _TN)
    dktk = dkt * kt
    dG = dqh * qh + dkh * kh - dktk
    dlast = (dktk.sum(0, keepdims=True)
             + elast * (St * dSt).sum(0, keepdims=True))        # [1, K]
    at_last = lax.broadcasted_iota(jnp.int32, G.shape, 0) == G.shape[0] - 1
    dG = dG + jnp.where(at_last, dlast, 0.0)
    return dqh * e, dkh * e + dkt * w, dR, dG, dT, dAqk, dS0


# ------------------------------------------------------------ plain path

def _by_chunks(a, chunk):
    """``[Bt, T, ...]`` -> ``[T / chunk, Bt, chunk, ...]``."""
    Bt, T = a.shape[:2]
    return jnp.moveaxis(a.reshape(Bt, T // chunk, chunk, *a.shape[2:]), 1, 0)


def _tiles_by_chunks(a):
    """``[Bt, H, T / chunk, C, C]`` -> ``[T / chunk, Bt, H, C, C]``."""
    return jnp.moveaxis(a, 2, 0)


def _whole(a):
    """``[T / chunk, Bt, chunk, ...]`` -> ``[Bt, T, ...]``."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape(a.shape[0], a.shape[1] * a.shape[2], *a.shape[3:])


def _states_fwd_xla(q, k, v, G, Tm, Aqk, chunk, scale):
    """-> (``o [Bt, T, H, V]``, the chunks' first states ``[Bt, T / chunk,
    H, V, K]``), in ``G``'s dtype."""
    Bt, T, H, K = q.shape
    V = v.shape[3]
    # a chunk's heads lie on axis 1 of [C, H, K] and 0 of [H, C, C]; the
    # batch leads every operand
    fn = jax.vmap(jax.vmap(functools.partial(_chunk_fwd, scale=scale),
                           in_axes=(1, 1, 1, 1, 0, 0, 0), out_axes=(1, 0)))

    def step(St, at):
        o, new = fn(*at, St)
        return new, (o, St)

    S0 = jnp.zeros((Bt, H, V, K), G.dtype) + 0 * G[:, :1, :, None, :].sum(1)
    _, (o, states) = lax.scan(
        step, S0, (*(_by_chunks(a, chunk) for a in (q, k, v, G)),
                   _tiles_by_chunks(Tm), _tiles_by_chunks(Aqk)))
    return _whole(o), jnp.moveaxis(states, 0, 1)


def _states_bwd_xla(q, k, v, G, Tm, Aqk, states, do, chunk, scale):
    """-> (dq, dk, dv, dG as the operands lie, dT, dAqk ``[Bt, H, T /
    chunk, chunk, chunk]``), in ``G``'s dtype."""
    fn = jax.vmap(jax.vmap(functools.partial(_chunk_bwd, scale=scale),
                           in_axes=(1, 1, 1, 1, 0, 0, 0, 1, 0),
                           out_axes=(1, 1, 1, 1, 0, 0, 0)))

    def step(dSt, at):
        *grads, dS0 = fn(*at, dSt)
        return dS0, tuple(grads)

    S = jnp.moveaxis(states, 1, 0)                   # [nc, Bt, H, V, K]
    _, (dq, dk, dv, dG, dT, dAqk) = lax.scan(
        step, jnp.zeros_like(S[0]) + 0 * S[0],
        (*(_by_chunks(a, chunk) for a in (q, k, v, G)), _tiles_by_chunks(Tm),
         _tiles_by_chunks(Aqk), S, _by_chunks(do, chunk)), reverse=True)
    back = lambda a: jnp.moveaxis(a, 0, 2)
    return (_whole(dq), _whole(dk), _whole(dv), _whole(dG), back(dT),
            back(dAqk))


# --------------------------------------------------------------- kernels
# Blocks, a grid step (b, j, c): q, k, G and their cotangents (1, C, hb K)
# of [Bt, T, H K]; v, o and theirs (1, C, hb V) of [Bt, T, H V]; the tiles
# (1, hb, 1, C, C) of [Bt, H, T / C, C, C]; the saved states (1, 1, hb, V,
# K) of [Bt, T / C, H, V, K].

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, t_ref, a_ref, o_ref, bound_ref,
                state_ref, *, hb, K, V, scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    for h in range(hb):
        ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        St = state_ref[h]
        bound_ref[0, 0, h] = St
        o, state_ref[h] = _chunk_fwd(
            q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
            g_ref[0, :, ks], t_ref[0, h, 0], a_ref[0, h, 0], St, scale)
        o_ref[0, :, vs] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, t_ref, a_ref, bound_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dt_ref, da_ref, dstate_ref,
                *, hb, K, V, scale):
    """One chunk of one block of heads, the chunks coming last first;
    ``dstate_ref`` holds what the later chunk hands to this one's last
    state."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros(dstate_ref.shape, jnp.float32)

    for h in range(hb):
        ks, vs = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        dq, dk, dv, dG, dT, dA, dstate_ref[h] = _chunk_bwd(
            q_ref[0, :, ks], k_ref[0, :, ks], v_ref[0, :, vs],
            g_ref[0, :, ks], t_ref[0, h, 0], a_ref[0, h, 0],
            bound_ref[0, 0, h], do_ref[0, :, vs], dstate_ref[h], scale)
        dq_ref[0, :, ks] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, ks] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, vs] = dv.astype(dv_ref.dtype)
        dg_ref[0, :, ks] = dG
        dt_ref[0, h, 0] = dT
        da_ref[0, h, 0] = dA


def _specs(K, V, chunk, nk, hb, reverse):
    at = (lambda c: nk - 1 - c) if reverse else (lambda c: c)
    keys = pl.BlockSpec((1, chunk, hb * K), lambda b, j, c: (b, at(c), j))
    vals = pl.BlockSpec((1, chunk, hb * V), lambda b, j, c: (b, at(c), j))
    tile = pl.BlockSpec((1, hb, 1, chunk, chunk),
                        lambda b, j, c: (b, j, at(c), 0, 0))
    bound = pl.BlockSpec((1, 1, hb, V, K), lambda b, j, c: (b, at(c), j, 0, 0))
    return keys, vals, tile, bound


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _flat(a):
    return a.reshape(a.shape[0], a.shape[1], -1)


def _states_fwd_pallas(q, k, v, G, Tm, Aqk, chunk, scale):
    Bt, T, H, K = q.shape
    V = v.shape[3]
    nk, hb = T // chunk, _head_block(H)
    keys, vals, tile, bound = _specs(K, V, chunk, nk, hb, False)
    _count("fwd", "pallas")
    operands = (_flat(q), _flat(k), _flat(v), _flat(G), Tm, Aqk)
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, K=K, V=V, scale=scale),
        grid=(Bt, H // hb, nk),
        in_specs=[keys, keys, vals, keys, tile, tile],
        out_specs=[vals, bound],
        out_shape=[_sds((Bt, T, H * V), v.dtype, *operands),
                   _sds((Bt, nk, H, V, K), jnp.float32, *operands)],
        scratch_shapes=[pltpu.VMEM((hb, V, K), jnp.float32)],
        compiler_params=_params(),
        interpret=_INTERPRET,
        name="hvd_kda_chunk_fwd",
    )(*operands)
    return o.reshape(Bt, T, H, V), states


def _states_bwd_pallas(q, k, v, G, Tm, Aqk, states, do, chunk, scale):
    f32 = jnp.float32
    Bt, T, H, K = q.shape
    V = v.shape[3]
    nk, hb = T // chunk, _head_block(H)
    keys, vals, tile, bound = _specs(K, V, chunk, nk, hb, True)
    _count("bwd", "pallas")
    operands = (_flat(q), _flat(k), _flat(v), _flat(G), Tm, Aqk, states,
                _flat(do.astype(q.dtype)))
    dq, dk, dv, dG, dT, dA = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, K=K, V=V, scale=scale),
        grid=(Bt, H // hb, nk),
        in_specs=[keys, keys, vals, keys, tile, tile, bound, vals],
        out_specs=[keys, keys, vals, keys, tile, tile],
        out_shape=[_sds((Bt, T, H * K), f32, *operands),
                   _sds((Bt, T, H * K), f32, *operands),
                   _sds((Bt, T, H * V), v.dtype, *operands),
                   _sds((Bt, T, H * K), f32, *operands),
                   _sds(Tm.shape, f32, *operands),
                   _sds(Tm.shape, f32, *operands)],
        scratch_shapes=[pltpu.VMEM((hb, V, K), f32)],
        compiler_params=_params(),
        interpret=_INTERPRET,
        name="hvd_kda_chunk_bwd",
    )(*operands)
    return (dq.reshape(q.shape), dk.reshape(q.shape), dv.reshape(v.shape),
            dG.reshape(q.shape), dT, dA)


# ------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, chunk):
    return _scan_fwd(q, k, v, g, beta, chunk)[0]


def _scan_fwd(q, k, v, g, beta, chunk):
    scale = q.shape[3] ** -0.5
    G = _running_sums(g.astype(_acc(q.dtype)), chunk)
    Tm, Aqk = _tiles(q, k, G, beta, chunk)
    if supported(q, k, v, g, beta, chunk):
        o, states = _states_fwd_pallas(q, k, v, G, Tm, Aqk, chunk, scale)
    else:
        _count("fwd", "xla")
        o, states = _states_fwd_xla(q, k, v, G, Tm, Aqk, chunk, scale)
    return o.astype(v.dtype), (q, k, v, g, beta, states)


def _scan_bwd(chunk, res, do):
    q, k, v, g, beta, states = res
    scale = q.shape[3] ** -0.5
    acc = _acc(q.dtype)
    G = _running_sums(g.astype(acc), chunk)
    (Tm, Aqk), tiles_vjp = jax.vjp(
        lambda q_, k_, G_, b_: _tiles(q_, k_, G_, b_, chunk), q, k, G, beta)
    if supported(q, k, v, g, beta, chunk):        # as the forward found
        back = _states_bwd_pallas
    else:
        _count("bwd", "xla")
        back = _states_bwd_xla
    dq, dk, dv, dG, dT, dAqk = back(q, k, v, G, Tm, Aqk, states, do, chunk,
                                    scale)
    dq2, dk2, dG2, dbeta = tiles_vjp((dT.astype(acc), dAqk.astype(acc)))
    return ((dq + dq2.astype(acc)).astype(q.dtype),
            (dk + dk2.astype(acc)).astype(k.dtype), dv.astype(v.dtype),
            _sum_back(dG + dG2, chunk).astype(g.dtype),
            dbeta.astype(beta.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_scan(q, k, v, g, beta, chunk=64):
    """``o [Bt, T, H, V]`` of the recurrence in the module docstring, in
    ``v``'s dtype.  ``q, k [Bt, T, H, K]`` (the caller's: normed or not);
    ``v [Bt, T, H, V]``; ``g [Bt, T, H, K]``, the log of each key channel's
    decay, never positive; ``beta [Bt, T, H]``.  Differentiable in all
    five.  ``chunk``: positions a chunk; a length that is no multiple of it
    is padded."""
    T = q.shape[1]
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    return _scan(q, k, v, g, beta, chunk)[:, :T]
