"""The scan of a Mamba-2 mixer in its chunked form (the state-space dual,
arXiv 2405.21060, section 6), as Pallas kernels for TPU.

Per head ``h`` a state that is a ``[P, N]`` matrix under one scalar decay,

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T
    y_t = S_t C_t + D x_t

with ``S_{-1} = 0``; ``x_t [P]`` the head's channels, ``B_t, C_t [N]``
shared by the heads of a group.  Because the decay is a scalar a head, a
chunk of ``Q`` positions turns into matrix products.  With ``a_t =
delta_t A`` and ``cs`` its running sum within the chunk (float32, every
entry non-positive),

    L[i, j] = exp(cs_i - cs_j) for j <= i, else 0
    Y       = (C B^T * L) X  +  exp(cs) * (C S_prev^T),   X = delta x
    S_next  = exp(cs_last) S_prev + (exp(cs_last - cs) X)^T B

so only the ``T / Q`` chunk states are walked one after another, and no
``[T, H, P, N]`` array exists anywhere.  Every exponential is of a
difference of running sums that is never positive (never a quotient of
two exponentials), in float32, and so are the sums and the carried state;
the products take the operands' dtype and accumulate in float32.

Two kernels, one layout.  The kernels read a head's channels with the
positions on the lanes, ``x^T [P, Q]`` (the call transposes ``[Bt, T, H,
P]`` to ``[Bt, H, P, T]`` around them and XLA fuses that with the
neighbouring elementwise work), so that every tile is lane-dense at ``P =
64`` and the ``[Q, Q]`` tile of decays is made in VMEM from a row and a
column of ``cs``, never in HBM.  The grid is ``(batch, chunks, blocks of
heads)``; the state of every head stays in VMEM scratch while the chunks
go by, and ``B C^T`` is made once a group and chunk.

* ``hvd_ssd_chunk_fwd`` writes ``y`` and, as the backward's only residual
  beside the operands, the state each chunk starts from: ``[Bt, T / Q, H,
  P, N]`` float32.
* ``hvd_ssd_chunk_bwd`` walks the chunks in reverse with the state's
  cotangent as its carry: it makes a chunk's ``Y`` again for the decay's
  gradient (``d cs = dY . Y - X . dX``, row sums that need no second
  ``[Q, Q]`` tile), writes ``dx`` and the two rows ``d delta`` is made of,
  and adds ``dB``, ``dC`` of a group's heads into one block.

``D x``, ``dD``, the running sums and their reverse in the backward are
elementwise work on ``[Bt, T, H]`` arrays and stay in XLA.

``hvd_ssd_kernel_total{kernel, path}`` counts the calls built, once per
traced call site: ``kernel`` is ``fwd`` or ``bwd``, ``path`` is ``pallas``
or ``xla``.

Falls back cleanly: on another backend than a TPU and at shapes
:func:`supported` refuses, the same chunked form in ``jax.numpy`` (batched
products over the chunks, a ``lax.scan`` over the chunk states), with the
same residuals; the choice is from shapes and backend, no knob.
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

# heads a grid step takes: whole sublane tiles of float32 rows, 16 where
# they divide a group (a forward call 0.70 ms for 0.83 at 8, forward and
# backward 2.71 for 2.91, transposes included: my chip run, PR 40)
_HEADS = (16, 8)
# the grid (batch, chunks of the sequence, head blocks): a batch row's
# chunks hand the states on, its head blocks share scratch
_GRID = ("parallel", "arbitrary", "arbitrary")

_count = _pallas.kernel_counter(
    "hvd_ssd_kernel_total",
    "Chunked state-space (Mamba-2) scan calls built, one per traced call "
    "site; kernel is fwd or bwd, path is pallas (ops/ssd_scan.py's "
    "kernels) or xla (the same chunked form in jax.numpy)")


def _head_block(H: int, G: int) -> int:
    """Heads a grid step takes: they share a group's ``B`` and ``C``."""
    R = H // G
    sizes = _HEADS + (4, 2, 1) if _pallas.INTERPRET else _HEADS
    return next((hb for hb in sizes if R % hb == 0), 0)


def _refusal(x, delta, A, B, C, D, chunk) -> Optional[str]:
    """Which test keeps the Pallas kernels off this call; None = they
    run."""
    if (why := _pallas.off_chip()):
        return why
    if x.ndim != 4 or B.ndim != 4:
        return ("x must be [batch, T, heads, head channels] and B [batch, "
                "T, groups, states]")
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    if (delta.shape != (Bt, T, H) or A.shape != (H,) or D.shape != (H,)
            or B.shape != (Bt, T, G, N) or C.shape != B.shape or H % G):
        return "operands disagree on batch, T, heads, groups or states"
    if T % chunk:
        return f"{T} positions are no multiple of the chunk {chunk}"
    if not _head_block(H, G):
        return f"{H // G} heads a group are no multiple of {_HEADS[-1]}"
    if not _pallas.INTERPRET and (chunk % 128 or N % 128 or P % 8):
        return (f"chunk {chunk} and {N} states must be multiples of 128, "
                f"{P} channels a head of 8")
    return _pallas.dtype_refusal(x.dtype)


def supported(x, delta, A, B, C, D, chunk=256) -> bool:
    """True when the Pallas kernels can run these shapes on this
    backend."""
    return _verdict("ssd_scan", _refusal(x, delta, A, B, C, D, chunk),
                    x, B)


def _running_sums(delta, A, chunk):
    """``cs [Bt, T, H]`` float32: ``delta A`` summed from each chunk's
    first position on."""
    Bt, T, H = delta.shape
    a = delta.astype(jnp.float32) * A.astype(jnp.float32)
    return jnp.cumsum(a.reshape(Bt, T // chunk, chunk, H), axis=2).reshape(
        Bt, T, H)


def _finish_backward(x, delta, A, D, dy, dx, dXx, dcs, chunk, heads=2):
    """What the chunks' backward leaves to elementwise work: ``d cs``
    summed back over each chunk's later positions into ``d a``, ``d
    delta``, ``dA``, ``dD`` and ``D``'s part of ``dx``.  ``heads``: the
    axis of ``x``, ``dy`` and ``dx`` that counts heads (2 of ``[Bt, T, H,
    P]``, 1 of the kernels' ``[Bt, H, P, T]``)."""
    f32 = jnp.float32
    Bt, T, H = delta.shape
    da = lax.cumsum(dcs.reshape(Bt, T // chunk, chunk, H), axis=2,
                    reverse=True).reshape(Bt, T, H)
    dl32, dy32, x32 = delta.astype(f32), dy.astype(f32), x.astype(f32)
    ddelta = da * A.astype(f32) + dXx
    dA = (da * dl32).sum((0, 1))
    dD = (dy32 * x32).sum(tuple(a for a in range(4) if a != heads))
    dx = dx.astype(f32) + _a_head(D, heads) * dy32
    return (dx.astype(x.dtype), ddelta.astype(delta.dtype),
            dA.astype(A.dtype), dD.astype(D.dtype))


def _a_head(D, heads):
    """``D [H]`` float32 against a rank-4 array whose axis ``heads`` counts
    heads."""
    return jnp.expand_dims(D.astype(jnp.float32), tuple(range(1, 4 - heads)))


# ------------------------------------------------------------ plain path
# The same chunked form in jax.numpy, every chunk at once: the [Q, Q]
# tiles reach HBM here, [Bt, T / Q, H, Q, Q] float32.

def _chunks(x, delta, A, B, C, chunk):
    f32 = jnp.float32
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    nc, R = T // chunk, H // G
    cs = _running_sums(delta, A, chunk).reshape(Bt, nc, chunk, G, R)
    dl = delta.astype(f32).reshape(Bt, nc, chunk, G, R)
    xc = x.reshape(Bt, nc, chunk, G, R, P)
    Bc, Cc = (a.reshape(Bt, nc, chunk, G, N) for a in (B, C))
    tri = jnp.tri(chunk, dtype=bool)[:, :, None, None]          # [i, j]
    diff = cs[:, :, :, None] - cs[:, :, None]                   # [.. i j G R]
    L = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    last = cs[:, :, -1]                                         # [Bt nc G R]
    return xc, dl, cs, Bc, Cc, L, last


def _scan_fwd_xla(x, delta, A, B, C, chunk):
    """-> (y without ``D x`` [Bt, T, H, P] float32, the chunks' first
    states [Bt, T / chunk, H, P, N] float32)."""
    f32, dt = jnp.float32, x.dtype
    Bt, T, H, P = x.shape
    N = B.shape[3]
    xc, dl, cs, Bc, Cc, L, last = _chunks(x, delta, A, B, C, chunk)
    X32 = xc.astype(f32) * dl[..., None]
    CB = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc, preferred_element_type=f32)
    M = (CB[..., None] * L).astype(dt)
    Yd = jnp.einsum("bcijgr,bcjgrp->bcigrp", M, X32.astype(dt),
                    preferred_element_type=f32)
    Xd = (X32 * jnp.exp(last[:, :, None] - cs)[..., None]).astype(dt)
    own = jnp.einsum("bcjgrp,bcjgn->bcgrpn", Xd, Bc,
                     preferred_element_type=f32)

    def carry(S, at):
        own_c, last_c = at
        return jnp.exp(last_c)[..., None, None] * S + own_c, S

    # zeros that vary over the mesh as x does (check_vma's carry types)
    S0 = jnp.zeros(own.shape[:1] + own.shape[2:], f32) + 0 * own[:, 0]
    _, states = lax.scan(carry, S0, (jnp.moveaxis(own, 1, 0),
                                     jnp.moveaxis(last, 1, 0)))
    states = jnp.moveaxis(states, 0, 1)                  # [Bt nc G R P N]
    Yo = jnp.einsum("bcign,bcgrpn->bcigrp", Cc, states.astype(dt),
                    preferred_element_type=f32) * jnp.exp(cs)[..., None]
    return ((Yd + Yo).reshape(Bt, T, H, P),
            states.reshape(Bt, T // chunk, H, P, N))


def _scan_bwd_xla(x, delta, A, B, C, states, dy, chunk):
    """-> (dx without ``D dy`` [Bt, T, H, P] float32, ``sum_p dX x`` and
    ``d cs`` [Bt, T, H] float32, dB, dC [Bt, T, G, N] float32)."""
    f32, dt = jnp.float32, x.dtype
    Bt, T, H, P = x.shape
    G, N = B.shape[2:]
    nc, R = T // chunk, H // G
    xc, dl, cs, Bc, Cc, L, last = _chunks(x, delta, A, B, C, chunk)
    S = states.reshape(Bt, nc, G, R, P, N)
    dyc = dy.reshape(xc.shape)
    x32, dy32 = xc.astype(f32), dyc.astype(f32)
    X32 = x32 * dl[..., None]
    X = X32.astype(dt)
    e, w = jnp.exp(cs)[..., None], jnp.exp(last[:, :, None] - cs)[..., None]
    dYe, Xd = (dy32 * e).astype(dt), (X32 * w).astype(dt)
    CB = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc, preferred_element_type=f32)
    M = (CB[..., None] * L).astype(dt)

    # the state's cotangent, from the last chunk back
    into = jnp.einsum("bcigrp,bcign->bcgrpn", dYe, Cc,
                      preferred_element_type=f32)

    def carry(dS, at):
        into_c, last_c = at
        return jnp.exp(last_c)[..., None, None] * dS + into_c, dS

    dS0 = jnp.zeros(into.shape[:1] + into.shape[2:], f32) + 0 * into[:, 0]
    _, dS = lax.scan(carry, dS0, (jnp.moveaxis(into, 1, 0),
                                  jnp.moveaxis(last, 1, 0)), reverse=True)
    dS = jnp.moveaxis(dS, 0, 1)          # each chunk's next state's cotangent

    Y = (jnp.einsum("bcijgr,bcjgrp->bcigrp", M, X, preferred_element_type=f32)
         + jnp.einsum("bcign,bcgrpn->bcigrp", Cc, S.astype(dt),
                      preferred_element_type=f32) * e)
    bds = jnp.einsum("bcjgn,bcgrpn->bcjgrp", Bc, dS.astype(dt),
                     preferred_element_type=f32)
    dX = jnp.einsum("bcijgr,bcigrp->bcjgrp", M, dyc,
                    preferred_element_type=f32) + bds * w
    dM = jnp.einsum("bcigrp,bcjgrp->bcijgr", dyc, X,
                    preferred_element_type=f32)
    dCB = (dM * L).sum(-1).astype(dt)                           # [.. i j G]
    dC = (jnp.einsum("bcijg,bcjgn->bcign", dCB, Bc,
                     preferred_element_type=f32)
          + jnp.einsum("bcigrp,bcgrpn->bcign", dYe, S.astype(dt),
                       preferred_element_type=f32))
    dB = (jnp.einsum("bcijg,bcign->bcjgn", dCB, Cc,
                     preferred_element_type=f32)
          + jnp.einsum("bcjgrp,bcgrpn->bcjgn", Xd, dS.astype(dt),
                       preferred_element_type=f32))
    dcs = (dy32 * Y).sum(-1) - (X32 * dX).sum(-1)               # [.. Q G R]
    dlast = ((X32 * bds * w).sum((2, 5))
             + jnp.exp(last) * (S * dS).sum((4, 5)))            # [Bt nc G R]
    dcs = dcs.at[:, :, -1].add(dlast)
    flat = lambda a: a.reshape(Bt, T, H)
    return ((dX * dl[..., None]).reshape(Bt, T, H, P),
            flat((dX * x32).sum(-1)), flat(dcs),
            dB.reshape(Bt, T, G, N), dC.reshape(Bt, T, G, N))


# --------------------------------------------------------------- kernels
# Blocks, a grid step (b, k, j): x^T, y^T and their cotangents (1, hb, P, Q)
# of [Bt, H, P, T]; delta and cs as rows (1, hb, Q) of [Bt, H, T]; cs as
# columns (1, 1, Q, hb) of [Bt, H / hb, T, hb]; B and C (1, Q, N) of
# [Bt, T, G N]; the saved states (1, 1, hb, P, N) of [Bt, T / Q, H, P, N].

def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _triangle(Q):
    """``[j, i]``: whether position ``i`` of a chunk sees position ``j``."""
    return (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            <= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _decays(csr_ref, csc_ref, h, tri):
    """A head's ``L^T [j, i] = exp(cs_i - cs_j)`` for ``j <= i`` (zero
    above), its ``cs`` as a row and the last entry."""
    cs_r = csr_ref[0, h:h + 1, :]                               # [1, Q]
    cs_c = csc_ref[0, 0, :, h:h + 1]                            # [Q, 1]
    LT = jnp.exp(jnp.where(tri, cs_r - cs_c, -jnp.inf))
    return LT, cs_r, cs_r[:, -1:]


def _fwd_kernel(xt_ref, dl_ref, csr_ref, csc_ref, b_ref, c_ref, yt_ref,
                bound_ref, state_ref, cbt_ref, *, hb, per_group):
    k, j = pl.program_id(1), pl.program_id(2)
    f32, dt = jnp.float32, xt_ref.dtype
    Q = xt_ref.shape[3]

    @pl.when(k == 0)
    def _():
        state_ref[j] = jnp.zeros(state_ref.shape[1:], f32)

    Bm, Cm = b_ref[0], c_ref[0]                                 # [Q, N]

    @pl.when(j % per_group == 0)
    def _():
        cbt_ref[...] = _dot(Bm, Cm, _NT)                        # [j, i]

    tri = _triangle(Q)
    for h in range(hb):
        LT, cs_r, last = _decays(csr_ref, csc_ref, h, tri)
        x32 = xt_ref[0, h].astype(f32) * dl_ref[0, h:h + 1, :]  # X^T [P, Q]
        S = state_ref[j, h]                                     # [P, N]
        bound_ref[0, 0, h] = S
        y = (_dot(x32.astype(dt), (cbt_ref[...] * LT).astype(dt))
             + _dot(S.astype(dt), Cm, _NT) * jnp.exp(cs_r))
        yt_ref[0, h] = y.astype(yt_ref.dtype)
        state_ref[j, h] = (jnp.exp(last) * S + _dot(
            (x32 * jnp.exp(last - cs_r)).astype(dt), Bm))


def _bwd_kernel(xt_ref, dyt_ref, dl_ref, csr_ref, csc_ref, b_ref, c_ref,
                bound_ref, dxt_ref, dxx_ref, dcs_ref, db_ref, dc_ref,
                dstate_ref, cbt_ref, dcbt_ref, *, hb, per_group):
    """One chunk of one block of heads, the chunks coming last first;
    ``dstate_ref`` holds what the later chunk hands to this one's last
    state."""
    k, j = pl.program_id(1), pl.program_id(2)
    f32, dt = jnp.float32, xt_ref.dtype
    Q = xt_ref.shape[3]

    @pl.when(k == 0)
    def _():
        dstate_ref[j] = jnp.zeros(dstate_ref.shape[1:], f32)

    Bm, Cm = b_ref[0], c_ref[0]

    @pl.when(j % per_group == 0)
    def _():
        cbt_ref[...] = _dot(Bm, Cm, _NT)
        dcbt_ref[...] = jnp.zeros(dcbt_ref.shape, f32)
        db_ref[...] = jnp.zeros(db_ref.shape, f32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, f32)

    lane = lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    tri = _triangle(Q)
    dB = jnp.zeros(db_ref.shape[1:], f32)
    dC = jnp.zeros(dc_ref.shape[1:], f32)
    for h in range(hb):
        LT, cs_r, last = _decays(csr_ref, csc_ref, h, tri)
        e, w = jnp.exp(cs_r), jnp.exp(last - cs_r)              # [1, Q]
        MT = (cbt_ref[...] * LT).astype(dt)
        xt32 = xt_ref[0, h].astype(f32)
        X32 = xt32 * dl_ref[0, h:h + 1, :]
        X = X32.astype(dt)
        dyt = dyt_ref[0, h]
        dy32 = dyt.astype(f32)
        dye = (dy32 * e).astype(dt)
        S, dS = bound_ref[0, 0, h], dstate_ref[j, h]            # [P, N]
        Sd, dSd = S.astype(dt), dS.astype(dt)
        y = _dot(X, MT) + _dot(Sd, Cm, _NT) * e                 # Y^T again
        bds = _dot(dSd, Bm, _NT)                                # [P, Q]
        dX = _dot(dyt, MT, _NT) + bds * w
        dcbt_ref[...] += _dot(X, dyt, _TN) * LT
        dlast = (jnp.sum(X32 * bds * w, keepdims=True)
                 + jnp.exp(last) * jnp.sum(S * dS, keepdims=True))
        dcs = (jnp.sum(dy32 * y, axis=0, keepdims=True)
               - jnp.sum(X32 * dX, axis=0, keepdims=True))
        dcs_ref[0, h:h + 1, :] = dcs + jnp.where(lane == Q - 1, dlast, 0.0)
        dxx_ref[0, h:h + 1, :] = jnp.sum(dX * xt32, axis=0, keepdims=True)
        dxt_ref[0, h] = (dX * dl_ref[0, h:h + 1, :]).astype(dxt_ref.dtype)
        dC = dC + _dot(dye, Sd, _TN)                            # [Q, N]
        dB = dB + _dot((X32 * w).astype(dt), dSd, _TN)
        dstate_ref[j, h] = jnp.exp(last) * dS + _dot(dye, Cm)

    db_ref[0] += dB
    dc_ref[0] += dC

    @pl.when((j + 1) % per_group == 0)
    def _():
        d = dcbt_ref[...].astype(dt)                            # [j, i]
        db_ref[0] += _dot(d, Cm)
        dc_ref[0] += _dot(d, Bm, _TN)


def _kernel_operands(xt, delta, A, B, C, chunk, hb):
    """The call's arrays in the kernels' layout: x^T [Bt, H, P, T] as it
    comes, delta and cs as rows [Bt, H, T], cs as columns [Bt, H / hb, T,
    hb], B and C [Bt, T, G N]."""
    Bt, T, H = delta.shape
    cs = _running_sums(delta, A, chunk)
    rows = lambda a: jnp.transpose(a, (0, 2, 1))
    cols = jnp.transpose(cs.reshape(Bt, T, H // hb, hb), (0, 2, 1, 3))
    flat = lambda a: a.reshape(Bt, T, -1)
    return (xt, rows(delta.astype(jnp.float32)), rows(cs), cols, flat(B),
            flat(C))


def _turn(x):
    """``[Bt, T, H, P]`` -> the kernels' ``[Bt, H, P, T]``."""
    return jnp.transpose(x, (0, 2, 3, 1))


def _back(xt):
    return jnp.transpose(xt, (0, 3, 1, 2))


def _specs(H, P, G, N, chunk, nk, hb, reverse):
    at = (lambda k: nk - 1 - k) if reverse else (lambda k: k)
    per_group = H // G // hb
    tile = pl.BlockSpec((1, hb, P, chunk), lambda b, k, j: (b, j, 0, at(k)))
    row = pl.BlockSpec((1, hb, chunk), lambda b, k, j: (b, j, at(k)))
    col = pl.BlockSpec((1, 1, chunk, hb), lambda b, k, j: (b, j, at(k), 0))
    grp = pl.BlockSpec((1, chunk, N),
                       lambda b, k, j: (b, at(k), j // per_group))
    bound = pl.BlockSpec((1, 1, hb, P, N),
                         lambda b, k, j: (b, at(k), j, 0, 0))
    return per_group, tile, row, col, grp, bound


def _chunks_fwd_pallas(xt, delta, A, B, C, chunk):
    """-> (y^T without ``D x`` [Bt, H, P, T], the chunks' first states)."""
    Bt, H, P, T = xt.shape
    G, N = B.shape[2:]
    nk, hb = T // chunk, _head_block(H, G)
    per_group, tile, row, col, grp, bound = _specs(H, P, G, N, chunk, nk, hb,
                                                   False)
    _count("fwd", "pallas")
    operands = _kernel_operands(xt, delta, A, B, C, chunk, hb)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, per_group=per_group),
        grid=(Bt, nk, H // hb),
        in_specs=[tile, row, row, col, grp, grp],
        out_specs=[tile, bound],
        out_shape=[_sds((Bt, H, P, T), xt.dtype, *operands),
                   _sds((Bt, nk, H, P, N), jnp.float32, *operands)],
        scratch_shapes=[pltpu.VMEM((H // hb, hb, P, N), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_pallas.params(*_GRID),
        interpret=_pallas.INTERPRET,
        name="hvd_ssd_chunk_fwd",
    )(*operands)


def _scan_fwd_pallas(x, delta, A, B, C, chunk):
    yt, states = _chunks_fwd_pallas(_turn(x), delta, A, B, C, chunk)
    return _back(yt), states


def _chunks_bwd_pallas(xt, delta, A, B, C, states, dyt, chunk):
    """-> (dx^T [Bt, H, P, T], the two ``[Bt, T, H]`` float32 arrays ``d
    delta`` is made of, dB, dC)."""
    f32 = jnp.float32
    Bt, H, P, T = xt.shape
    G, N = B.shape[2:]
    nk, hb = T // chunk, _head_block(H, G)
    per_group, tile, row, col, grp, bound = _specs(H, P, G, N, chunk, nk, hb,
                                                   True)
    _count("bwd", "pallas")
    xt, dl, csr, csc, Bf, Cf = _kernel_operands(xt, delta, A, B, C, chunk, hb)
    operands = (xt, dyt.astype(xt.dtype), dl, csr, csc, Bf, Cf, states)
    dxt, dxx, dcs, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, per_group=per_group),
        grid=(Bt, nk, H // hb),
        in_specs=[tile, tile, row, row, col, grp, grp, bound],
        out_specs=[tile, row, row, grp, grp],
        out_shape=[_sds((Bt, H, P, T), xt.dtype, *operands),
                   _sds((Bt, H, T), f32, *operands),
                   _sds((Bt, H, T), f32, *operands),
                   _sds((Bt, T, G * N), f32, *operands),
                   _sds((Bt, T, G * N), f32, *operands)],
        scratch_shapes=[pltpu.VMEM((H // hb, hb, P, N), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32)],
        # a step at 16 heads of 64 x 128 and a chunk of 256: every head's
        # state (2 MB), three [Q, Q] float32 tiles, eleven blocks
        # double-buffered (9 MB) and the unrolled heads' temporaries
        compiler_params=_pallas.params(*_GRID),
        interpret=_pallas.INTERPRET,
        name="hvd_ssd_chunk_bwd",
    )(*operands)
    rows = lambda a: jnp.transpose(a, (0, 2, 1))
    return dxt, rows(dxx), rows(dcs), dB.reshape(B.shape), dC.reshape(C.shape)


def _scan_bwd_pallas(x, delta, A, B, C, states, dy, chunk):
    dxt, *rest = _chunks_bwd_pallas(_turn(x), delta, A, B, C, states,
                                    _turn(dy), chunk)
    return (_back(dxt), *rest)


# ------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_scan(x, delta, A, B, C, D, chunk=256):
    """``y [Bt, T, H, P]`` of the recurrence in the module docstring, in
    ``x``'s dtype.  ``x [Bt, T, H, P]``; ``delta [Bt, T, H]``; ``A, D
    [H]``; ``B, C [Bt, T, G, N]`` with ``G`` dividing ``H``.
    Differentiable in all six.  ``chunk``: positions a chunk (the plain
    path takes the largest common divisor with ``T``)."""
    return _ssd_scan_fwd(x, delta, A, B, C, D, chunk)[0]


def _ssd_scan_fwd(x, delta, A, B, C, D, chunk):
    if supported(x, delta, A, B, C, D, chunk):
        y, states = _scan_fwd_pallas(x, delta, A, B, C, chunk)
    else:
        _count("fwd", "xla")
        y, states = _scan_fwd_xla(x, delta, A, B, C,
                                  math.gcd(chunk, x.shape[1]))
    f32 = jnp.float32
    y = y.astype(f32) + D.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype), (x, delta, A, B, C, D, states)


def _ssd_scan_bwd(chunk, res, dy):
    x, delta, A, B, C, D, states = res
    if supported(x, delta, A, B, C, D, chunk):    # as the forward found
        back = _scan_bwd_pallas
    else:
        _count("bwd", "xla")
        back, chunk = _scan_bwd_xla, math.gcd(chunk, x.shape[1])
    dx, dXx, dcs, dB, dC = back(x, delta, A, B, C, states, dy, chunk)
    dx, ddelta, dA, dD = _finish_backward(x, delta, A, D, dy, dx, dXx, dcs,
                                          chunk)
    return dx, ddelta, dA, dB.astype(B.dtype), dC.astype(C.dtype), dD


ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


# ------------------------------------------- the same in the kernels' layout

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_turned(xt, delta, A, B, C, D, chunk):
    return _scan_turned_fwd(xt, delta, A, B, C, D, chunk)[0]


def _scan_turned_fwd(xt, delta, A, B, C, D, chunk):
    f32 = jnp.float32
    yt, states = _chunks_fwd_pallas(xt, delta, A, B, C, chunk)
    yt = yt.astype(f32) + _a_head(D, 1) * xt.astype(f32)
    return yt.astype(xt.dtype), (xt, delta, A, B, C, D, states)


def _scan_turned_bwd(chunk, res, dyt):
    xt, delta, A, B, C, D, states = res
    dxt, dXx, dcs, dB, dC = _chunks_bwd_pallas(xt, delta, A, B, C, states,
                                               dyt, chunk)
    dxt, ddelta, dA, dD = _finish_backward(xt, delta, A, D, dyt, dxt, dXx,
                                           dcs, chunk, heads=1)
    return dxt, ddelta, dA, dB.astype(B.dtype), dC.astype(C.dtype), dD


_scan_turned.defvjp(_scan_turned_fwd, _scan_turned_bwd)


def ssd_scan_turned(xt, delta, A, B, C, D, chunk=256):
    """:func:`ssd_scan` on ``x^T [Bt, H, P, T]``, giving ``y^T [Bt, H, P,
    T]``: the kernels' own layout taken and returned, for a caller whose
    neighbouring kernels write and read it (``ops/mamba2_mixer.py``), so
    that nothing is transposed in HBM around the call; the other operands
    as :func:`ssd_scan` takes them.  Where the kernels do not run, that
    function between two transposes."""
    Bt, H, P, T = xt.shape
    x = jax.ShapeDtypeStruct((Bt, T, H, P), xt.dtype)
    if supported(x, delta, A, B, C, D, chunk):
        return _scan_turned(xt, delta, A, B, C, D, chunk)
    return _turn(ssd_scan(_back(xt), delta, A, B, C, D, chunk))
