"""The selective scan of a Mamba-1 mixer as Pallas kernels for TPU.

The recurrence over the sequence that a state-space layer is made of
(:mod:`horovod_tpu.models.hybrid`, the Mamba mixer): per channel ``c`` a
state of ``N`` numbers,

    S_t[c, n] = exp(delta_t[c] A[c, n]) S_{t-1}[c, n]
                + delta_t[c] xs_t[c] B_t[n]
    s_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] xs_t[c]

with ``S_{-1} = 0``.  XLA's choices are ``T`` sequential steps of a
``lax.scan`` or a ``[T, C, N]`` float32 expansion, 2.7 GB an array at
8,192 positions of 5,120 channels of 16; here the state of a block of
channels stays in VMEM while the kernel walks the sequence, and no
``[T, C, N]`` array reaches HBM in either direction.

Two kernels, one layout.  A block's state is ``[N, cb]`` float32: the
state index on the sublanes, ``cb`` channels on the lanes (512 where
they divide ``C``: eight registers), so a step's ``delta_t`` and ``xs_t``
are rows spread over the sublanes and its ``B_t`` and ``C_t`` columns
spread over the lanes; the columns arrive spread already, ``[T, N,
128]`` float32 made by the caller's program (67 MB an operand at 8,192
positions, read once a call: the channel blocks of one chunk of the
sequence follow each other and share the block).  The grid is ``(batch,
chunks of the sequence, channel blocks)``.

* ``hvd_ssm_scan_fwd`` walks a chunk (``_CHUNK`` positions) eight steps
  at a time, writes ``s`` and keeps, as the backward's only residual
  beside the operands, the state each chunk starts from: ``[T / _CHUNK,
  N, C]`` float32.
* ``hvd_ssm_scan_bwd`` walks the chunks in reverse: it makes a chunk's
  states again from its boundary into VMEM scratch, then walks the chunk
  backwards with the state's cotangent as its carry and writes ``dxs``
  and ``ddelta``, adds ``dA`` into a block that stays in VMEM a batch
  row, and ``dB`` and ``dC`` as 128 partial sums a (position, state
  index), over the lanes, that meet in the caller's program (a step's
  sum over all channels would be a cross-lane reduction a step).  ``dD``
  is a plain sum of ``ds * xs`` and stays in XLA.

State and accumulators are float32 whatever the operands' dtype (the
operands are cast where the call is built); the results come back in
their operands' dtypes.

``hvd_ssm_scan_kernel_total{kernel, path}`` counts the calls built, once
per traced call site: ``kernel`` is ``fwd`` or ``bwd``, ``path`` is
``pallas`` or ``xla``.

Falls back cleanly: on another backend than a TPU and at shapes
:func:`supported` refuses, a plain ``lax.scan`` over the positions, its
backward by autodiff from the operands (as ``grouped_matmul`` falls back
to ``lax.ragged_dot``); the choice is from shapes and backend, no knob.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _pallas
from ._pallas import LANES as _LANES, sds as _sds, verdict as _verdict

_CHUNK = 128        # positions between two saved states
_UNROLL = 8         # steps a loop iteration takes: one aligned tile of rows
# the grid (batch, chunks of the sequence, channel blocks): a batch row's
# chunks hand the state on, its channel blocks share scratch
_GRID = ("parallel", "arbitrary", "arbitrary")

_count = _pallas.kernel_counter(
    "hvd_ssm_scan_kernel_total",
    "Selective-scan calls built, one per traced call site; kernel is fwd "
    "or bwd, path is pallas (ops/selective_scan.py) or xla (lax.scan)")


def _channel_block(C: int) -> int:
    return next((cb for cb in (512, 256, 128) if C % cb == 0), 0)


def _refusal(xs, delta, A, B, C, D) -> Optional[str]:
    """Which test keeps the Pallas kernels off this call; None = they
    run."""
    if (why := _pallas.off_chip()):
        return why
    if xs.ndim != 3 or B.ndim != 3:
        return "xs must be [batch, T, channels] and B [batch, T, states]"
    Bt, T, Ch = xs.shape
    N = A.shape[-1]
    if (delta.shape != xs.shape or A.shape != (Ch, N) or D.shape != (Ch,)
            or B.shape != (Bt, T, N) or C.shape != B.shape):
        return "operands disagree on batch, T, channels or states"
    if not _channel_block(Ch):
        return f"{Ch} channels are no multiple of {_LANES}"
    if N % 8:
        return f"{N} states are no multiple of 8"
    if T % min(_CHUNK, T) or min(_CHUNK, T) % _UNROLL:
        return (f"{T} positions are no multiple of the chunk "
                f"{min(_CHUNK, T)}, or it of {_UNROLL}")
    return _pallas.dtype_refusal(xs.dtype)


def supported(xs, delta, A, B, C, D) -> bool:
    """True when the Pallas kernels can run these shapes on this
    backend."""
    return _verdict("selective_scan", _refusal(xs, delta, A, B, C, D),
                    xs, A, B)


# ------------------------------------------------------------ plain path

def _scan_xla(xs, delta, A, B, C, D):
    """The recurrence as a ``lax.scan`` over the positions, float32."""
    f32 = jnp.float32
    A32, D32 = A.astype(f32), D.astype(f32)

    def step(S, at):
        x, d, b, c = at                     # [Bt, Ch] twice, [Bt, N] twice
        S = (jnp.exp(d[..., None] * A32) * S
             + (d * x)[..., None] * b[:, None, :])
        return S, (S * c[:, None, :]).sum(-1) + D32 * x

    time_major = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)
    # zeros that vary over the mesh as xs does (a shard_map under check_vma
    # wants the carry's type in and out alike)
    S0 = jnp.zeros((xs.shape[0],) + A.shape, f32) + 0 * xs[:, 0, :, None]
    _, s = lax.scan(step, S0, tuple(map(time_major, (xs, delta, B, C))))
    return jnp.moveaxis(s, 0, 1).astype(xs.dtype)


# --------------------------------------------------------------- kernels
# Blocks: xs, delta, s and their cotangents (1, chunk, cb); A transposed
# (N, cb); D (1, cb); B and C over the lanes (1, chunk, N, 128); the saved
# states (1, 1, N, cb) of [batch, chunks, N, C].

def _over_lanes(col, cb):
    """``[N, 128]`` with every lane of a row alike, as ``[N, cb]``."""
    return col if cb == _LANES else jnp.tile(col, (1, cb // _LANES))


def _fold_lanes(x):
    """``[N, cb]`` -> ``[N, 128]``: the lane tiles added up."""
    return functools.reduce(
        jnp.add, (x[:, c:c + _LANES] for c in range(0, x.shape[1], _LANES)))


def _fwd_kernel(xs_ref, dl_ref, at_ref, bb_ref, cc_ref, d_ref, s_ref,
                bound_ref, state_ref, *, chunk):
    k, j = pl.program_id(1), pl.program_id(2)
    cb = at_ref.shape[1]

    @pl.when(k == 0)
    def _():
        state_ref[j] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    bound_ref[0, 0] = state_ref[j]
    At, Drow = at_ref[...], d_ref[...]

    def tile(i, S):
        t0 = pl.multiple_of(i * _UNROLL, _UNROLL)
        x8 = xs_ref[0, pl.ds(t0, _UNROLL), :]
        d8 = dl_ref[0, pl.ds(t0, _UNROLL), :]
        rows = []
        for r in range(_UNROLL):
            x, d = x8[r:r + 1], d8[r:r + 1]
            S = (jnp.exp(d * At) * S
                 + (d * x) * _over_lanes(bb_ref[0, t0 + r], cb))
            rows.append((S * _over_lanes(cc_ref[0, t0 + r], cb)).sum(
                axis=0, keepdims=True) + Drow * x)
        s_ref[0, pl.ds(t0, _UNROLL), :] = jnp.concatenate(rows, axis=0)
        return S

    state_ref[j] = lax.fori_loop(0, chunk // _UNROLL, tile, state_ref[j])


def _bwd_kernel(xs_ref, dl_ref, ds_ref, at_ref, bb_ref, cc_ref, d_ref,
                bound_ref, dxs_ref, ddl_ref, da_ref, dbp_ref, dcp_ref,
                states_ref, carry_ref, *, chunk):
    """One chunk of one channel block, the chunks coming last first.
    ``states_ref [chunk + 1, N, cb]``: slot 0 the state the chunk starts
    from, slot t + 1 the state after its step t.  ``carry_ref [blocks, N,
    cb]``: the cotangent that the later chunk hands to this one's last
    state, already through that step's decay."""
    k, j = pl.program_id(1), pl.program_id(2)
    cb = at_ref.shape[1]
    lanes = pl.ds(pl.multiple_of(j * cb, cb), cb)

    @pl.when(k == 0)
    def _():
        carry_ref[j] = jnp.zeros(carry_ref.shape[1:], jnp.float32)
        da_ref[0, :, lanes] = jnp.zeros((da_ref.shape[1], cb), jnp.float32)

    @pl.when(j == 0)
    def _():
        dbp_ref[...] = jnp.zeros(dbp_ref.shape, jnp.float32)
        dcp_ref[...] = jnp.zeros(dcp_ref.shape, jnp.float32)

    At, Drow = at_ref[...], d_ref[...]
    states_ref[0] = bound_ref[0, 0]

    def again(i, S):
        t0 = pl.multiple_of(i * _UNROLL, _UNROLL)
        x8 = xs_ref[0, pl.ds(t0, _UNROLL), :]
        d8 = dl_ref[0, pl.ds(t0, _UNROLL), :]
        for r in range(_UNROLL):
            x, d = x8[r:r + 1], d8[r:r + 1]
            S = (jnp.exp(d * At) * S
                 + (d * x) * _over_lanes(bb_ref[0, t0 + r], cb))
            states_ref[t0 + r + 1] = S
        return S

    lax.fori_loop(0, chunk // _UNROLL, again, states_ref[0])

    def back(i, carry):
        K, dA = carry
        t0 = pl.multiple_of((chunk // _UNROLL - 1 - i) * _UNROLL, _UNROLL)
        x8 = xs_ref[0, pl.ds(t0, _UNROLL), :]
        d8 = dl_ref[0, pl.ds(t0, _UNROLL), :]
        g8 = ds_ref[0, pl.ds(t0, _UNROLL), :]
        dx_rows, dd_rows = [None] * _UNROLL, [None] * _UNROLL
        for r in reversed(range(_UNROLL)):
            x, d, g = x8[r:r + 1], d8[r:r + 1], g8[r:r + 1]
            t = t0 + r
            bt = _over_lanes(bb_ref[0, t], cb)
            e = jnp.exp(d * At)
            H = g * _over_lanes(cc_ref[0, t], cb) + K
            dcp_ref[0, t] += _fold_lanes(g * states_ref[t + 1])
            dbp_ref[0, t] += _fold_lanes(H * (d * x))
            dE = H * states_ref[t] * e
            hb = (H * bt).sum(axis=0, keepdims=True)
            dd_rows[r] = (dE * At).sum(axis=0, keepdims=True) + hb * x
            dx_rows[r] = hb * d + Drow * g
            dA = dA + dE * d
            K = e * H
        dxs_ref[0, pl.ds(t0, _UNROLL), :] = jnp.concatenate(dx_rows, axis=0)
        ddl_ref[0, pl.ds(t0, _UNROLL), :] = jnp.concatenate(dd_rows, axis=0)
        return K, dA

    K, dA = lax.fori_loop(0, chunk // _UNROLL, back,
                          (carry_ref[j], jnp.zeros_like(At)))
    carry_ref[j] = K
    da_ref[0, :, lanes] += dA


def _kernel_operands(xs, delta, A, B, C, D):
    f32 = jnp.float32
    spread = lambda a: jnp.broadcast_to(a.astype(f32)[..., None],
                                        a.shape + (_LANES,))
    return (xs.astype(f32), delta.astype(f32), A.astype(f32).T, spread(B),
            spread(C), D.astype(f32)[None])


def _specs(Ch, N, chunk, nk, reverse):
    cb = _channel_block(Ch)
    at = (lambda k: nk - 1 - k) if reverse else (lambda k: k)
    row = pl.BlockSpec((1, chunk, cb), lambda b, k, j: (b, at(k), j))
    mat = pl.BlockSpec((N, cb), lambda b, k, j: (0, j))
    col = pl.BlockSpec((1, chunk, N, _LANES),
                       lambda b, k, j: (b, at(k), 0, 0))
    vec = pl.BlockSpec((1, cb), lambda b, k, j: (0, j))
    bound = pl.BlockSpec((1, 1, N, cb), lambda b, k, j: (b, at(k), 0, j))
    return cb, row, mat, col, vec, bound


def _scan_fwd_pallas(xs, delta, A, B, C, D):
    """-> (s [Bt, T, Ch] in xs's dtype, the chunks' first states [Bt,
    T / chunk, N, Ch] float32)."""
    Bt, T, Ch = xs.shape
    N = A.shape[1]
    chunk = min(_CHUNK, T)
    nk = T // chunk
    cb, row, mat, col, vec, bound = _specs(Ch, N, chunk, nk, False)
    _count("fwd", "pallas")
    operands = _kernel_operands(xs, delta, A, B, C, D)
    s, bounds = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(Bt, nk, Ch // cb),
        in_specs=[row, row, mat, col, col, vec],
        out_specs=[row, bound],
        out_shape=[_sds((Bt, T, Ch), jnp.float32, *operands),
                   _sds((Bt, nk, N, Ch), jnp.float32, *operands)],
        scratch_shapes=[pltpu.VMEM((Ch // cb, N, cb), jnp.float32)],
        compiler_params=_pallas.params(*_GRID),
        interpret=_pallas.INTERPRET,
        name="hvd_ssm_scan_fwd",
    )(*operands)
    return s.astype(xs.dtype), bounds


def _scan_bwd_pallas(xs, delta, A, B, C, D, bounds, ds):
    Bt, T, Ch = xs.shape
    N = A.shape[1]
    chunk = min(_CHUNK, T)
    nk = T // chunk
    cb, row, mat, col, vec, bound = _specs(Ch, N, chunk, nk, True)
    _count("bwd", "pallas")
    operands = _kernel_operands(xs, delta, A, B, C, D)
    xs32, dl32, at, bb, cc, drow = operands
    ds32 = ds.astype(jnp.float32)
    whole = pl.BlockSpec((1, N, Ch), lambda b, k, j: (b, 0, 0))
    dxs, ddl, dA, dbp, dcp = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(Bt, nk, Ch // cb),
        in_specs=[row, row, row, mat, col, col, vec, bound],
        out_specs=[row, row, whole, col, col],
        out_shape=[_sds((Bt, T, Ch), jnp.float32, *operands, ds),
                   _sds((Bt, T, Ch), jnp.float32, *operands, ds),
                   _sds((Bt, N, Ch), jnp.float32, *operands, ds),
                   _sds((Bt, T, N, _LANES), jnp.float32, *operands, ds),
                   _sds((Bt, T, N, _LANES), jnp.float32, *operands, ds)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, N, cb), jnp.float32),
                        pltpu.VMEM((Ch // cb, N, cb), jnp.float32)],
        # a step at 512 channels a block and 16 states: a chunk's states
        # (4.2 MB), eleven blocks double-buffered (13 MB)
        compiler_params=_pallas.params(*_GRID),
        interpret=_pallas.INTERPRET,
        name="hvd_ssm_scan_bwd",
    )(xs32, dl32, ds32, at, bb, cc, drow, bounds)
    dD = (ds32 * xs32).sum((0, 1))
    return (dxs.astype(xs.dtype), ddl.astype(delta.dtype),
            dA.sum(0).T.astype(A.dtype), dbp.sum(-1).astype(B.dtype),
            dcp.sum(-1).astype(C.dtype), dD.astype(D.dtype))


# ------------------------------------------------------------- public op

@jax.custom_vjp
def selective_scan(xs, delta, A, B, C, D):
    """``s [Bt, T, Ch]`` of the recurrence in the module docstring.
    ``xs, delta [Bt, T, Ch]``; ``A [Ch, N]``; ``B, C [Bt, T, N]``; ``D
    [Ch]``.  Differentiable in all six."""
    return _selective_scan_fwd(xs, delta, A, B, C, D)[0]


def _selective_scan_fwd(xs, delta, A, B, C, D):
    if supported(xs, delta, A, B, C, D):
        s, bounds = _scan_fwd_pallas(xs, delta, A, B, C, D)
    else:
        _count("fwd", "xla")
        s, bounds = _scan_xla(xs, delta, A, B, C, D), None
    return s, (xs, delta, A, B, C, D, bounds)


def _selective_scan_bwd(res, ds):
    *operands, bounds = res
    if bounds is not None:
        return _scan_bwd_pallas(*operands, bounds, ds)
    _count("bwd", "xla")
    return jax.vjp(_scan_xla, *operands)[1](ds)


selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)
