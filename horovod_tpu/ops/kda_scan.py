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
only.  Running sums, every exponential, the triangular inverse and the
carried state are float32; the products take the operands' dtype and
accumulate in float32.

Two passes, two kernels each and a backward of their own; only the running
sums ``G`` and their transpose stay in XLA.

The tiles ``T`` and ``Aqk`` of every chunk have no carry, so every grid
step is its own:

* ``hvd_kda_tiles_fwd`` takes a chunk's ``q, k, G`` and ``beta`` for a
  block of heads and makes both tiles in VMEM.  A block row below the
  diagonal is one product, q's and k's row sides stacked against the
  columns before it; a pass over the diagonal blocks makes one
  sub-diagonal of all of them at once, row ``i`` against row ``i - d``
  brought beside it by a turn of the sublanes, ``exp(G_i - G_j)`` for ``j
  <= i`` alone (16 passes over ``[C, K]``, not a ``[16, 16, K]`` array a
  block).  The inverse (:func:`_blocked_inverse`): the diagonal blocks by
  forward substitution side by side along the lanes, 15 steps deep, then
  merged pair by pair by products at float32 precision.  (The power series
  ``sum (-L)^n`` is the same matrix and cancels catastrophically at
  ``beta`` near 2.)
* ``hvd_kda_tiles_bwd`` takes the two tiles' cotangents and the same
  operands, makes ``A``, its inverse and every decay again and writes
  ``dq, dk``, the running sum's cotangent (all float32, to be added to the
  chunk kernel's) and ``dbeta``: ``dN = dT Diag(beta)``, ``dL = -tril(N^T
  tril(dN) N^T, -1)``, ``dA = Diag(beta) dL``, then the tiles' transposes
  block by block as the forward made them.  ``G_i`` raises row ``i``'s
  entries and lowers column ``i``'s, so its cotangent needs no pass of its
  own: ``q_i dq_i + k_i (dk_i^row - dk_i^col)``.

The ``T / C`` chunk states are walked one after another:

* ``hvd_kda_chunk_fwd`` takes a chunk's ``q, k, v, G, T, Aqk`` for a
  block of heads, keeps every head's state in VMEM scratch while the
  chunks go by, writes ``o`` and, as the backward's only residual beside
  the operands, the state each chunk starts from (``[Bt, T / C, H, V, K]``
  float32, the state transposed: its decay then runs along the lanes).
* ``hvd_kda_chunk_bwd`` walks the chunks in reverse with the state's
  cotangent as its carry, makes a chunk's ``U`` again and writes ``dq,
  dk, dv``, the running sum's cotangent and the two tiles' cotangents.

The backward makes the tiles again with the forward kernel (what is kept
is the operands and the chunks' first states, nothing else).

The grid is ``(batch, blocks of heads, chunks)``; the arrays are read as
``[Bt, T, H K]`` with a head's channels a block of lanes, so nothing is
transposed in HBM.  ``hvd_kda_scan_total{kernel, path}`` counts the scans
built and ``hvd_kda_tiles_total{kernel, path}`` their first pass, once per
traced call site: ``kernel`` is ``fwd`` or ``bwd``, ``path`` is ``pallas``
or ``xla``.

Falls back cleanly, both passes together: on another backend than a TPU
and at shapes :func:`supported` refuses, the tiles of every chunk at once
in ``jax.numpy`` (:func:`_tiles`, unchanged since PR 42: its backward is
autodiff's, the row-by-row inverse's its own rule) and the same chunk
functions (:func:`_chunk_fwd`, :func:`_chunk_bwd`: the kernels call them
on what they load) under a ``lax.scan`` over the chunks, every head at
once, with the same residuals; the choice is from shapes and backend, no
knob.  A length that is no multiple of the chunk is padded with positions
that leave the state as it is (``k = v = g = beta = 0``).
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

from . import _pallas
from ._pallas import (NT as _NT, TN as _TN, sds as _sds,
                      verdict as _verdict)

_SUB = 16           # positions a sub-chunk: a diagonal block's side
# heads a grid step takes: their chains of small products are independent
# and fill one another's latencies
_HEADS = (8, 4, 2, 1)
_TOGETHER = 2      # heads a tile kernel's loop over a block's heads unrolls

_count = _pallas.kernel_counter(
    "hvd_kda_scan_total",
    "Chunked gated-delta-rule (Kimi Delta Attention) scan calls built, one "
    "per traced call site; kernel is fwd or bwd, path is pallas "
    "(ops/kda_scan.py's kernels) or xla (the same chunked form in "
    "jax.numpy)")
_count_tiles = _pallas.kernel_counter(
    "hvd_kda_tiles_total",
    "The chunked gated-delta-rule scan's first pass, every chunk's tiles T "
    "and Aqk with the triangular inverse, built, one per traced call site; "
    "kernel is fwd or bwd (a differentiated scan builds fwd twice: its "
    "backward makes the tiles again), path is pallas (ops/kda_scan.py's "
    "hvd_kda_tiles_fwd / hvd_kda_tiles_bwd) or xla (the same tiles in "
    "jax.numpy, their backward autodiff's)")


def _acc(dtype):
    """What sums, exponentials and the state are kept in: float32 (float64
    for float64 operands, which only a test hands in)."""
    return jnp.promote_types(dtype, jnp.float32)


def _head_block(H: int) -> int:
    return next(hb for hb in _HEADS if H % hb == 0)


def _refusal(q, k, v, g, beta, chunk) -> Optional[str]:
    """Which test keeps the Pallas kernels off this call; None = they
    run."""
    if (why := _pallas.off_chip()):
        return why
    if q.ndim != 4 or v.ndim != 4:
        return "q, k must be [batch, T, heads, K] and v [batch, T, heads, V]"
    Bt, T, H, K = q.shape
    V = v.shape[3]
    if (k.shape != q.shape or g.shape != q.shape or v.shape[:3] != (Bt, T, H)
            or beta.shape != (Bt, T, H)):
        return "operands disagree on batch, T, heads or K"
    if T % chunk:
        return f"{T} positions are no multiple of the chunk {chunk}"
    if not _pallas.INTERPRET and (chunk % 16 or K % 128 or V % 128):
        return (f"chunk {chunk} must be a multiple of 16, {K} key and {V} "
                "value channels a head of 128")
    return _pallas.dtype_refusal(q.dtype, v.dtype)


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

def _dot(a, b, dims=(((1,), (0,)), ((), ())), acc=jnp.float32, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=acc)


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


# ------------------------------------------ the tiles of one chunk of one head
# Plain functions of arrays that the tile kernels call on what they load: the
# scores and their transposes a head (q, k, G [C, K]; the tiles [C, C]), the
# inverse and its cotangent every head of a grid step at once ([n, C, C], beta
# down the rows [n, C, 1] and along the columns [n, 1, C]); s the side of a
# diagonal block.  The same mathematics as :func:`_tiles`, which stays the
# plain path's and the tests' reference.

_HI = lax.Precision.HIGHEST


def _roll(a, d):
    """Rows ``d`` later, around the end: ``out[i] = a[i - d]``."""
    return pltpu.roll(a, d % a.shape[0], 0)


def _at(n, m, axis):
    return lax.broadcasted_iota(jnp.int32, (n, m), axis)


def _block_row(q32, k32, G, s, I):
    """Block row ``I``'s factors through its first row: the row factor
    ``exp(G_i - G_ref) [s, K]``, q's and k's row sides stacked ``[2 s, K]``
    and the column side ``[C, K]`` in the operands' dtype, the column
    factor ``exp(min(G_ref - G_j, 0)) [C, K]``; only the columns before the
    block row are live."""
    lo = s * I
    ref = G[lo:lo + 1]
    rows = jnp.exp(G[lo:lo + s] - ref)
    cols = jnp.exp(jnp.minimum(ref - G, 0.0))
    side = jnp.concatenate([q32[lo:lo + s] * rows, k32[lo:lo + s] * rows], 0)
    return rows, cols, side, k32 * cols


def _diagonal(k32, G, s, d, local):
    """Pass ``d`` over the diagonal blocks: row ``i`` meets row ``i - d`` of
    its own block.  -> (``E [C, K]``: ``exp(G_i - G_{i-d})``, 0 where ``i -
    d`` lies in another block; ``k_{i-d} E``)."""
    if d == 0:
        return None, k32
    E = jnp.exp(jnp.where(local >= d, G - _roll(G, d), -jnp.inf))
    return E, _roll(k32, d) * E


def _scores(q, k, G, s, with_q=True):
    """-> (``A``, ``Aqk`` or None) ``[C, C]`` in ``G``'s dtype."""
    acc, dt = G.dtype, q.dtype
    C, K = G.shape
    q32, k32 = q.astype(acc), k.astype(acc)
    row, col = _at(C, C, 0), _at(C, C, 1)
    # blocks below the diagonal: one product a block row
    below = [jnp.zeros((2 * s, C), acc)]
    for I in range(1, C // s):
        _, _, side, kr = _block_row(q32, k32, G, s, I)
        both = _dot(side.astype(dt), kr.astype(dt), _NT, acc)
        below.append(jnp.where(_at(2 * s, C, 1) < s * I, both, 0.0))
    A = jnp.concatenate([b[s:] for b in below], 0)
    Aqk = jnp.concatenate([b[:s] for b in below], 0) if with_q else None
    # blocks on the diagonal: entry by entry, a sub-diagonal of all a pass
    local = _at(C, K, 0) % s
    for d in range(s):
        _, M = _diagonal(k32, G, s, d, local)
        on = col == row - d
        if with_q:
            Aqk = Aqk + jnp.where(on, (q32 * M).sum(1, keepdims=True), 0.0)
        if d:
            A = A + jnp.where(on, (k32 * M).sum(1, keepdims=True), 0.0)
    return A, Aqk


def _bdot(a, b):
    """``a @ b`` at float32 precision, a head at a time: ``[n, i, j] x [n,
    j, k]``."""
    return lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))), precision=_HI,
                           preferred_element_type=a.dtype)


def _blocked_inverse(L, s):
    """``(I + L)^-1`` for ``L [n, C, C]`` (or ``[C, C]``) strictly lower
    triangular, every matrix at once.  The diagonal blocks of side ``s`` by
    forward substitution, all of them side by side along the lanes of one
    ``[s, C]`` array a matrix (``s - 1`` steps deep, not ``C - 1``: step
    ``m`` takes row ``m``, final by then, off every later row); then merged
    pair by pair, ``[[P, 0], [B, Q]]^-1 = [[P^-1, 0], [-Q^-1 B P^-1,
    Q^-1]]``, by products at float32 precision of the rows that change.
    The same matrix as :func:`_unit_lower_inverse`'s and as well
    conditioned: no power of ``L`` is formed."""
    C = L.shape[-1]
    L3 = L.reshape(-1, C, C)
    n = L3.shape[0]
    # a lane's block's first lane, for one matrix's rows and for all (two
    # iotas: Mosaic aborts on a slice of one)
    first, firsts = (_at(r, C, 1) // s * s for r in (s, n * s))
    side = lambda a, lo: jnp.where(first == lo, a, 0.0)
    Ls = sum(side(L3[:, lo:lo + s], lo) for lo in range(0, C, s))
    Ls = Ls.reshape(n * s, C)
    N = jnp.broadcast_to(
        (_at(s, C, 1) - first == _at(s, C, 0)).astype(L.dtype), (n, s, C))
    for m in range(s - 1):      # every block's column m along its lanes
        col = jnp.take_along_axis(Ls, firsts + m, axis=1,
                                  mode="promise_in_bounds")
        N = N - col.reshape(n, s, C) * N[:, m:m + 1]
    N = jnp.concatenate([side(N, lo) for lo in range(0, C, s)], 1)
    row, col = _at(C, C, 0), _at(C, C, 1)
    w = s
    while w < C:
        pair = (row // (2 * w) == col // (2 * w)) & (row // w != col // w)
        later = [(lo, min(lo + w, C)) for lo in range(w, C, 2 * w)]
        X = _bdot(_bdot(jnp.concatenate([N[:, a:b] for a, b in later], 1),
                        jnp.where(pair, L3, 0.0)), N)
        rows, at = [], 0
        for a, b in later:
            rows += [N[:, a - w:a], N[:, a:b] - X[:, at:at + b - a]]
            at += b - a
        if later[-1][1] < C:                 # a last block without a pair
            rows.append(N[:, later[-1][1]:])
        N = jnp.concatenate(rows, 1)
        w *= 2
    return N.reshape(L.shape)


def _inverse_bwd(A, bcol, brow, dT, s):
    """``T = (I + Diag(beta) A)^-1 Diag(beta)``'s cotangent back to ``A``'s
    ``[n, C, C]`` and to beta's, in two parts: down the rows ``[n, C, 1]``
    and along the columns ``[n, 1, C]``; the inverse made again."""
    C = A.shape[-1]
    row, col = _at(C, C, 0), _at(C, C, 1)
    N = _blocked_inverse(bcol * A, s)
    Nt = jnp.swapaxes(N, 1, 2)
    dN = jnp.where(col <= row, dT * brow, 0.0)
    dL = jnp.where(col < row, -_bdot(_bdot(Nt, dN), Nt), 0.0)
    return (bcol * dL, (dL * A).sum(2, keepdims=True),
            (dT * N).sum(1, keepdims=True))


def _scores_bwd(q, k, G, dA, dAqk, s):
    """The two tiles' cotangents back to (dq, dk, dG) ``[C, K]`` in ``G``'s
    dtype, block by block as :func:`_scores` made them, every decay made
    again; ``dA`` strictly lower triangular."""
    acc, dt = G.dtype, q.dtype
    C, K = G.shape
    q32, k32 = q.astype(acc), k.astype(acc)
    row, col = _at(C, C, 0), _at(C, C, 1)
    dQK = jnp.where(col <= row, dAqk, 0.0)
    # blocks below the diagonal
    dq_rows, dk_rows = [jnp.zeros((s, K), acc)], [jnp.zeros((s, K), acc)]
    dk_cols = jnp.zeros((C, K), acc)
    for I in range(1, C // s):
        lo = s * I
        rows, cols, side, kr = _block_row(q32, k32, G, s, I)
        cot = jnp.where(
            _at(2 * s, C, 1) < lo,
            jnp.concatenate([dQK[lo:lo + s], dA[lo:lo + s]], 0), 0.0).astype(dt)
        dside = _dot(cot, kr.astype(dt), acc=acc)
        dq_rows.append(dside[:s] * rows)
        dk_rows.append(dside[s:] * rows)
        dk_cols = dk_cols + _dot(cot, side.astype(dt), _TN, acc) * cols
    dq, dk_row = jnp.concatenate(dq_rows, 0), jnp.concatenate(dk_rows, 0)
    # blocks on the diagonal: a pass reads its sub-diagonal of both
    # cotangents off the MXU, a row's entry along all its lanes
    local = _at(C, K, 0) % s
    ones = jnp.ones((C, K), dt)
    spread = functools.partial(
        _dot, acc=acc, precision=_HI if dt == jnp.float32 else None)
    for d in range(s):
        E, M = _diagonal(k32, G, s, d, local)
        on = col == row - d
        a = spread(jnp.where(on, dQK, 0.0).astype(dt), ones)
        dq = dq + a * M
        if d == 0:
            dk_cols = dk_cols + a * q32
            continue
        b = spread(jnp.where(on, dA, 0.0).astype(dt), ones)
        dk_row = dk_row + b * M
        dk_cols = dk_cols + _roll((a * q32 + b * k32) * E, -d)
    # G_i raises row i's entries and lowers column i's
    return dq, dk_row + dk_cols, q32 * dq + k32 * (dk_row - dk_cols)


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


def _params(carried=True):
    """``carried``: the chunks hand a state on and run in order.  (A
    backward step at 8 heads of 128 x 128 and a chunk of 64: fourteen
    blocks double-buffered, 5 MB, every head's state, 0.5 MB, and the
    unrolled heads' temporaries.)"""
    return _pallas.params("parallel", "parallel",
                          "arbitrary" if carried else "parallel")


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
        interpret=_pallas.INTERPRET,
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
        interpret=_pallas.INTERPRET,
        name="hvd_kda_chunk_bwd",
    )(*operands)
    return (dq.reshape(q.shape), dk.reshape(q.shape), dv.reshape(v.shape),
            dG.reshape(q.shape), dT, dA)


def _over_heads(hb, K, head):
    """``head(h, lanes)`` over a grid step's heads, ``lanes`` the head's
    channels of a ``[1, C, hb K]`` block: ``_TOGETHER`` heads an iteration
    fill one another's latencies, and the program stays a loop's size (with
    all eight unrolled a kernel lowers more than twice as long and runs no
    faster: my chip runs, PR 43)."""
    u = math.gcd(hb, _TOGETHER)

    def some(i, carry):
        for j in range(u):
            h = i * u + j
            head(h, pl.ds(pl.multiple_of(h * K, K), K))
        return carry

    lax.fori_loop(0, hb // u, some, None)


def _betas(bcol_ref, brow_ref, hb):
    """A grid step's beta a head: down the rows ``[hb, C, 1]``, along the
    columns ``[hb, 1, C]``."""
    return (jnp.stack([bcol_ref[0, 0, 0, :, h:h + 1] for h in range(hb)]),
            jnp.stack([brow_ref[0, 0, 0, h:h + 1, :] for h in range(hb)]))


def _tiles_fwd_kernel(q_ref, k_ref, g_ref, bcol_ref, brow_ref, t_ref, a_ref,
                      *, hb, K, s):
    """One chunk of one block of heads: no carry, every grid step its own.
    The scores head by head, ``A`` kept where ``T`` will lie; then every
    head's inverse at once: each a chain of small steps and products, and
    eight of them fill one another's latencies."""
    def scores(h, ks):
        t_ref[0, h, 0], a_ref[0, h, 0] = _scores(
            q_ref[0, :, ks], k_ref[0, :, ks], g_ref[0, :, ks], s)

    _over_heads(hb, K, scores)
    bcol, brow = _betas(bcol_ref, brow_ref, hb)
    t_ref[0, :, 0] = _blocked_inverse(bcol * t_ref[0, :, 0], s) * brow


def _tiles_bwd_kernel(q_ref, k_ref, g_ref, bcol_ref, brow_ref, dt_ref, da_ref,
                      dq_ref, dk_ref, dg_ref, dbcol_ref, dbrow_ref, a_scr,
                      *, hb, K, s):
    """The forward's stretches and back: ``A`` head by head into scratch;
    every head's inverse and its cotangent at once, ``dA`` left where ``A``
    lay; the two tiles' transposes head by head."""
    def scores(h, ks):
        a_scr[h], _ = _scores(q_ref[0, :, ks], k_ref[0, :, ks],
                              g_ref[0, :, ks], s, with_q=False)

    def transposes(h, ks):
        dq_ref[0, :, ks], dk_ref[0, :, ks], dg_ref[0, :, ks] = _scores_bwd(
            q_ref[0, :, ks], k_ref[0, :, ks], g_ref[0, :, ks], a_scr[h],
            da_ref[0, h, 0], s)

    _over_heads(hb, K, scores)
    a_scr[...], db_rows, db_cols = _inverse_bwd(
        a_scr[...], *_betas(bcol_ref, brow_ref, hb), dt_ref[0, :, 0], s)
    for h in range(hb):
        dbcol_ref[0, 0, 0, :, h:h + 1] = db_rows[h]
        dbrow_ref[0, 0, 0, h:h + 1, :] = db_cols[h]
    _over_heads(hb, K, transposes)


def _beta_blocks(beta, chunk, hb):
    """``beta [Bt, T, H]`` in float32 as the tile kernels read it: a chunk's
    down the rows ``[Bt, H / hb, T / C, C, hb]`` and along the columns
    ``[Bt, H / hb, T / C, hb, C]``, with the two blocks' specs."""
    Bt, T, H = beta.shape
    b = beta.astype(jnp.float32).reshape(Bt, T // chunk, chunk, H // hb, hb)
    at = lambda b_, j, c: (b_, j, c, 0, 0)
    return (jnp.transpose(b, (0, 3, 1, 2, 4)), jnp.transpose(b, (0, 3, 1, 4, 2)),
            pl.BlockSpec((1, 1, 1, chunk, hb), at),
            pl.BlockSpec((1, 1, 1, hb, chunk), at))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _tiles_fwd_call(q, k, G, beta, *, chunk, interpret):
    Bt, T, H, K = q.shape
    nk, hb = T // chunk, _head_block(H)
    keys, _, tile, _ = _specs(K, K, chunk, nk, hb, False)
    bcol, brow, cols, rows = _beta_blocks(beta, chunk, hb)
    operands = (_flat(q), _flat(k), _flat(G), bcol, brow)
    shape = _sds((Bt, H, nk, chunk, chunk), jnp.float32, *operands)
    return pl.pallas_call(
        functools.partial(_tiles_fwd_kernel, hb=hb, K=K,
                          s=math.gcd(chunk, _SUB)),
        grid=(Bt, H // hb, nk),
        in_specs=[keys, keys, keys, cols, rows],
        out_specs=[tile, tile],
        out_shape=[shape, shape],
        compiler_params=_params(carried=False),
        interpret=interpret,
        name="hvd_kda_tiles_fwd",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _tiles_bwd_call(q, k, G, beta, dT, dAqk, *, chunk, interpret):
    f32 = jnp.float32
    Bt, T, H, K = q.shape
    nk, hb = T // chunk, _head_block(H)
    keys, _, tile, _ = _specs(K, K, chunk, nk, hb, False)
    bcol, brow, cols, rows = _beta_blocks(beta, chunk, hb)
    operands = (_flat(q), _flat(k), _flat(G), bcol, brow, dT, dAqk)
    wide = _sds((Bt, T, H * K), f32, *operands)
    dq, dk, dG, db_rows, db_cols = pl.pallas_call(
        functools.partial(_tiles_bwd_kernel, hb=hb, K=K,
                          s=math.gcd(chunk, _SUB)),
        grid=(Bt, H // hb, nk),
        in_specs=[keys, keys, keys, cols, rows, tile, tile],
        out_specs=[keys, keys, keys, cols, rows],
        out_shape=[wide, wide, wide, _sds(bcol.shape, f32, *operands),
                   _sds(brow.shape, f32, *operands)],
        scratch_shapes=[pltpu.VMEM((hb, chunk, chunk), f32)],
        compiler_params=_params(carried=False),
        interpret=interpret,
        name="hvd_kda_tiles_bwd",
    )(*operands)
    dbeta = (jnp.transpose(db_rows, (0, 2, 3, 1, 4))
             + jnp.transpose(db_cols, (0, 2, 4, 1, 3))).reshape(Bt, T, H)
    return dq.reshape(q.shape), dk.reshape(q.shape), dG.reshape(q.shape), dbeta


# The two calls are nested ``jit``s: a program traces and lowers each kernel
# once, however many layers and passes call it (at every call site anew the
# Solar cell's ``lower_s`` read 58.8 s for the parent's 19.7, my chip run,
# PR 43).

def _tiles_fwd_pallas(q, k, G, beta, chunk):
    """:func:`_tiles` as a kernel: the same tiles, made in VMEM."""
    _count_tiles("fwd", "pallas")
    return _tiles_fwd_call(q, k, G, beta, chunk=chunk,
                           interpret=_pallas.INTERPRET)


def _tiles_bwd_pallas(q, k, G, beta, dT, dAqk, chunk):
    """-> (dq, dk, dG ``[Bt, T, H, K]``, dbeta ``[Bt, T, H]``), float32."""
    _count_tiles("bwd", "pallas")
    return _tiles_bwd_call(q, k, G, beta, dT, dAqk, chunk=chunk,
                           interpret=_pallas.INTERPRET)


# ------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, chunk):
    return _scan_fwd(q, k, v, g, beta, chunk)[0]


def _scan_fwd(q, k, v, g, beta, chunk):
    scale = q.shape[3] ** -0.5
    G = _running_sums(g.astype(_acc(q.dtype)), chunk)
    if supported(q, k, v, g, beta, chunk):
        Tm, Aqk = _tiles_fwd_pallas(q, k, G, beta, chunk)
        o, states = _states_fwd_pallas(q, k, v, G, Tm, Aqk, chunk, scale)
    else:
        _count("fwd", "xla")
        _count_tiles("fwd", "xla")
        Tm, Aqk = _tiles(q, k, G, beta, chunk)
        o, states = _states_fwd_xla(q, k, v, G, Tm, Aqk, chunk, scale)
    return o.astype(v.dtype), (q, k, v, g, beta, states)


def _scan_bwd(chunk, res, do):
    q, k, v, g, beta, states = res
    scale = q.shape[3] ** -0.5
    acc = _acc(q.dtype)
    G = _running_sums(g.astype(acc), chunk)
    if supported(q, k, v, g, beta, chunk):        # as the forward found
        Tm, Aqk = _tiles_fwd_pallas(q, k, G, beta, chunk)
        dq, dk, dv, dG, dT, dAqk = _states_bwd_pallas(
            q, k, v, G, Tm, Aqk, states, do, chunk, scale)
        dq2, dk2, dG2, dbeta = _tiles_bwd_pallas(q, k, G, beta, dT, dAqk, chunk)
    else:
        _count("bwd", "xla")
        _count_tiles("fwd", "xla")
        _count_tiles("bwd", "xla")
        (Tm, Aqk), tiles_vjp = jax.vjp(
            lambda q_, k_, G_, b_: _tiles(q_, k_, G_, b_, chunk), q, k, G, beta)
        dq, dk, dv, dG, dT, dAqk = _states_bwd_xla(
            q, k, v, G, Tm, Aqk, states, do, chunk, scale)
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
