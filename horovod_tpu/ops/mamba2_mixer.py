"""The Mamba-2 mixer's two elementwise chains as Pallas kernels for TPU, each
with a backward of its own: one pass over HBM a kernel, bf16 in and out,
float32 only in VMEM.

* :func:`conv_silu_split`: ``[x ; B ; C] = silu(conv(xBC) + b)``, the
  depthwise causal convolution ``Kc`` wide over ``xBC [Bt, T, C]`` with the
  channels on the lanes,

      pre[t] = b + sum_j w[j] xBC[t - (Kc - 1) + j],   zeros before t = 0

  written as ``len(sizes)`` results so that no slice follows.  A grid step
  takes a block of positions by all channels and, as a second view of the
  operand, the 16 positions before it (the halo: ``Kc - 1`` of them are
  used); the shifts are sublane rotations of a tile in VMEM: no padded copy
  exists.  ``hvd_conv_silu_bwd`` makes ``pre`` again from ``xBC``, ``d pre
  = d out * silu'(pre)``, and ``dxBC[t] = sum_j w[j] d pre[t + (Kc - 1) -
  j]``: the halo is now the *later* positions, so the blocks go by last
  first with the first rows of ``d pre`` carried in VMEM scratch;
  ``dconv_w[j]`` and ``dconv_b`` are float32 sums carried over the blocks in
  an output block the grid revisits (eight partial rows each, added up
  outside).  Residuals: the operands.
* :func:`gated_rmsnorm`: ``g = y * silu(z)``, ``o = g * rsqrt(mean(g^2) +
  eps) * w`` over all channels of a position at once (one group), a block
  of positions by all channels.  ``hvd_gated_norm_bwd`` makes ``g`` and the
  inverse norm again and writes ``dy``, ``dz`` and ``dw`` (a float32 sum
  over the blocks, as above).  Residuals: the operands.

A kernel walks its block tile by tile, ``_ROWS`` positions by ``_CHANNELS``
channels: few enough vector registers a value that a chain stays in them
(the whole block's width at once spilled every value: the convolution's
backward 0.87 ms a call for 0.48, my chip runs, PR 41).  The tiles of
channels are one unrolled loop (:func:`_fold`) and each call is a nested
``jit`` (:func:`_statics`), so a kernel is traced and lowered once a program
and not once a layer and pass.

Results and cotangents take the operands' dtype, as the plain form's do.

**Turned.**  ``turned=True`` gives the convolution's first result as ``[Bt,
sizes[0], T]`` and takes the gate's ``y`` as ``[Bt, C, T]``, the positions
on the lanes: the layout of :func:`horovod_tpu.ops.ssd_scan.ssd_scan_turned`,
so that between the three nothing is transposed in HBM.  The kernels turn
tiles of ``_TURN`` positions by channels through a VMEM scratch the size of
a block, after the walk (a result) or before it (an operand); their
cotangents come and go turned too.

``hvd_mixer_kernel_total{kernel, path}`` counts the calls built, once per
traced call site: ``kernel`` is ``conv_fwd``, ``conv_bwd``, ``norm_fwd`` or
``norm_bwd``, ``path`` is ``pallas`` or ``xla``.

Falls back cleanly: on another backend than a TPU, at channel counts (or
split sizes) the 128 lanes do not divide and at a ``T`` the block of
positions does not divide, the plain ``jax.numpy`` form under autodiff,
reached by a Python branch outside the ``custom_vjp``: the choice is from
shapes and backend, no knob.
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
from ._pallas import sds as _sds, verdict as _verdict

_BLOCK = 256        # positions a grid step: 2.2 MB of bf16 at 4,352 channels
_ROWS = 32          # positions a tile of the walk inside a block
_CHANNELS = 256     # channels a tile of the walk (two registers' width)
_TURN = (128, 256)  # positions by channels a tile turned in VMEM
_HALO = 16          # positions of the second view: one packed bf16 tile

_count = _pallas.kernel_counter(
    "hvd_mixer_kernel_total",
    "Mamba-2 mixer elementwise chains built, one per traced call site; "
    "kernel is conv_fwd, conv_bwd (convolution + SiLU + split), norm_fwd "
    "or norm_bwd (gate + RMSNorm), path is pallas (ops/mamba2_mixer.py's "
    "kernels) or xla (the plain form; its backward is autodiff's and is "
    "not counted)")


def _blocks(T: int):
    """(positions a grid step, positions a tile of the walk inside it)."""
    bt = min(_BLOCK, T)
    return bt, min(_ROWS, bt)


def _refusal(a, widths, turned=False) -> Optional[str]:
    """Which test keeps the Pallas kernels off an operand ``[Bt, T,
    channels]`` (an array or its shape and dtype) cut into ``widths``, the
    first of them ``turned`` or not; None = they run."""
    if (why := _pallas.off_chip()):
        return why
    if len(a.shape) != 3 or sum(widths) != a.shape[2]:
        return f"operand must be [batch, T, {sum(widths)} channels]"
    if not _pallas.INTERPRET and any(w % 128 for w in widths):
        return f"channels {tuple(widths)} are no multiples of the 128 lanes"
    bt, rows = _blocks(a.shape[1])
    if a.shape[1] % bt or bt % rows or rows % _HALO:
        return (f"{a.shape[1]} positions are no multiple of the block "
                f"{_BLOCK} (or of {_HALO})")
    if turned and not _pallas.INTERPRET and bt % 128:
        return f"a block of {bt} positions turns into no whole lanes"
    return _pallas.dtype_refusal(a.dtype)


def supported(a, widths, turned=False) -> bool:
    """True when the Pallas kernels can run an operand of ``a``'s shape
    ``[Bt, T, channels]`` and dtype, cut into ``widths``, on this backend."""
    return _verdict("mamba2_mixer", _refusal(a, tuple(widths), turned), a)


def _partials(a):
    """``[rows, C]`` float32 -> ``[8, C]``: the rows added up eight apart
    (whole sublane tiles: no reduction across sublanes in the kernel)."""
    return functools.reduce(
        jnp.add, (a[i:i + 8] for i in range(0, a.shape[0], 8)))


def _dsilu(pre, s):
    """``silu'(pre)`` given ``s = sigmoid(pre)``."""
    return s * (1.0 + pre * (1.0 - s))


def _lanes(at, j, width):
    """The ``j``-th tile of ``width`` channels from channel ``at`` on, as a
    slice of a ref's lanes (a whole number of them on the chip)."""
    return pl.ds(pl.multiple_of(at + j * width, math.gcd(at, width)), width)


def _fold(n, body, init=0):
    """``body(n - 1, ... body(0, init))`` as one unrolled loop: traced once
    and laid out ``n`` times, since the tiles of channels a kernel walks are
    few and one after another in a rolled loop they ran half as fast (the
    gate's forward 0.66 ms a call for 0.30, my chip runs, PR 41)."""
    return lax.fori_loop(0, n, body, init, unroll=True)


def _turn(src_ref, dst_ref, turn, back=False):
    """``dst[0] = src[0]^T``, tile by tile of ``turn`` positions by
    channels: a block ``(1, bt, C)`` into ``(1, C, bt)`` or, ``back``, the
    other way."""
    (P, C), (tp, tc) = dst_ref.shape[1:] if back else src_ref.shape[1:], turn
    tc = math.gcd(C, tc)

    def tile(j, _):
        cols = _lanes(0, j, tc)
        for p in range(0, P, tp):
            rows = slice(p, min(p + tp, P))
            if back:
                dst_ref[0, rows, cols] = src_ref[0, cols, rows].T
            else:
                dst_ref[0, cols, rows] = src_ref[0, rows, cols].T
        return 0

    _fold(C // tc, tile)


def _swap(a):
    return jnp.swapaxes(a, 1, 2)


# ------------------------------------------------------------ plain forms

def _conv_silu_split_xla(xBC, conv_w, conv_b, sizes, turned=False):
    # models/hybrid.py::_conv_silu's expression and the split after it
    f32 = jnp.float32
    Kc, T = conv_w.shape[0], xBC.shape[1]
    padded = jnp.pad(xBC.astype(f32), ((0, 0), (Kc - 1, 0), (0, 0)))
    out = jax.nn.silu(sum(padded[:, j:j + T] * conv_w[j].astype(f32)
                          for j in range(Kc))
                      + conv_b.astype(f32)).astype(xBC.dtype)
    outs = tuple(jnp.split(out, _cuts(sizes), axis=-1))
    return (_swap(outs[0]),) + outs[1:] if turned else outs


def _gated_rmsnorm_xla(y, z, w, eps, turned=False):
    f32 = jnp.float32
    g = (_swap(y) if turned else y).astype(f32) * jax.nn.silu(z.astype(f32))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g * w.astype(f32)).astype(y.dtype)


def _cuts(sizes):
    cuts, at = [], 0
    for s in sizes[:-1]:
        at += s
        cuts.append(at)
    return tuple(cuts)


# ---------------------------------------------------- convolution kernels
# Blocks, a grid step (b, k): xBC and its cotangent (1, bt, C) of [Bt, T, C];
# the halo (1, 16, C) of the same array, the 16 positions before the block;
# each result and its cotangent (1, bt, size), a turned one (1, size, bt);
# w (Kc, C) and b (1, C) float32 whole; the sums (1, 8 (Kc + 1), C) of [Bt,
# 8 (Kc + 1), C] float32.

def _results(refs, lanes):
    """(a result's or cotangent's ref, its first channel of all, channels a
    tile of it) for each of ``refs``, blocks ``(1, bt, size)``."""
    out, at = [], 0
    for ref in refs:
        out.append((ref, at, math.gcd(ref.shape[2], lanes)))
        at += ref.shape[2]
    return out


def _pre(x_ref, halo_ref, cols, w, b, r0, rows, first):
    """A tile's pre-activation ``[rows, width]`` float32 and the ``Kc``
    shifted views of ``xBC`` it is made of (``views[j][i] = xBC[r0 + i - (Kc
    - 1) + j]``); ``cols``: the tile's channels; ``first``: the block is the
    row's first (zeros before it)."""
    f32 = jnp.float32
    Kc = w.shape[0]
    cur = x_ref[0, pl.ds(r0, rows), cols].astype(f32)
    at = pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO)
    before = x_ref[0, pl.ds(at, _HALO), cols]
    halo = jnp.where(first, jnp.zeros_like(before), halo_ref[0, :, cols])
    before = jnp.where(r0 == 0, halo, before).astype(f32)[_HALO - 8:]
    both = jnp.concatenate([before, cur], axis=0)           # [8 + rows, width]
    views = [pltpu.roll(both, Kc - 1 - j, axis=0)[8:] for j in range(Kc - 1)]
    views.append(cur)
    pre = b + sum(v * w[j:j + 1] for j, v in enumerate(views))
    return pre, views


def _conv_fwd_kernel(x_ref, halo_ref, w_ref, b_ref, *refs, rows, lanes,
                     turn):
    """``refs``: the results; turned (``turn`` the tile, else None), the
    first is ``(1, size, bt)`` and a scratch ``(1, bt, size)`` that the walk
    writes comes last."""
    o_refs = (refs[-1],) + refs[1:-1] if turn else refs
    first = pl.program_id(1) == 0
    for o_ref, at, width in _results(o_refs, lanes):
        def lane_tile(j, _):
            cols = _lanes(at, j, width)
            w, b = w_ref[:, cols], b_ref[:, cols]

            def tile(i, _):
                r0 = pl.multiple_of(i * rows, rows)
                pre, _ = _pre(x_ref, halo_ref, cols, w, b, r0, rows, first)
                o_ref[0, pl.ds(r0, rows), _lanes(0, j, width)] = (
                    pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)
                return 0

            return lax.fori_loop(0, x_ref.shape[1] // rows, tile, 0)

        _fold(o_ref.shape[2] // width, lane_tile)
    if turn:
        _turn(refs[-1], refs[0], turn)


def _conv_bwd_kernel(x_ref, halo_ref, w_ref, b_ref, *refs, rows, lanes,
                     turn):
    """One block of positions, the blocks coming last first; ``carry_ref``
    holds the first eight rows of the later block's ``d pre``.  Turned, the
    first cotangent is ``(1, size, bt)`` and a scratch ``(1, bt, size)`` to
    turn it into comes last."""
    if turn:
        *do_refs, dx_ref, sums_ref, carry_ref, turn_ref = refs
        _turn(do_refs[0], turn_ref, turn, back=True)
        do_refs[0] = turn_ref
    else:
        *do_refs, dx_ref, sums_ref, carry_ref = refs
    f32 = jnp.float32
    k = pl.program_id(1)
    Kc = w_ref.shape[0]
    tiles = x_ref.shape[1] // rows

    @pl.when(k == 0)
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, f32)
        sums_ref[...] = jnp.zeros(sums_ref.shape, f32)

    last = k == pl.num_programs(1) - 1
    for do_ref, at, width in _results(do_refs, lanes):
        def lane_tile(j, _):
            cols = _lanes(at, j, width)
            w, b = w_ref[:, cols], b_ref[:, cols]

            def tile(i, carry):
                later, sums = carry
                r0 = pl.multiple_of((tiles - 1 - i) * rows, rows)
                pre, views = _pre(x_ref, halo_ref, cols, w, b, r0, rows, last)
                do = do_ref[0, pl.ds(r0, rows), _lanes(0, j, width)]
                dpre = do.astype(f32) * _dsilu(pre, jax.nn.sigmoid(pre))
                both = jnp.concatenate([dpre, later], axis=0)   # [rows + 8, .]
                dx = dpre * w[Kc - 1:Kc]
                for t in range(Kc - 1):
                    # both[i + (Kc - 1 - t)]: a rotation towards the front
                    dx = dx + pltpu.roll(both, rows + 8 - (Kc - 1 - t),
                                         axis=0)[:rows] * w[t:t + 1]
                dx_ref[0, pl.ds(r0, rows), cols] = dx.astype(dx_ref.dtype)
                sums = tuple(s + _partials(dpre if v is None else dpre * v)
                             for s, v in zip(sums, views + [None]))
                return dpre[:8], sums

            zeros = jnp.zeros((8, width), f32)
            later, sums = lax.fori_loop(
                0, tiles, tile, (carry_ref[:, cols], (zeros,) * (Kc + 1)))
            carry_ref[:, cols] = later
            for t, s in enumerate(sums):        # dw[0] .. dw[Kc - 1], db
                sums_ref[0, 8 * t:8 * t + 8, cols] += s
            return 0

        _fold(do_ref.shape[2] // width, lane_tile)


def _conv_specs(C, Kc, bt, at):
    per = bt // _HALO
    block = lambda width: pl.BlockSpec((1, bt, width),
                                       lambda b, k: (b, at(k), 0))
    turned = lambda width: pl.BlockSpec((1, width, bt),
                                        lambda b, k: (b, 0, at(k)))
    halo = pl.BlockSpec((1, _HALO, C),
                        lambda b, k: (b, jnp.maximum(at(k) * per - 1, 0), 0))
    whole = lambda n: pl.BlockSpec((n, C), lambda b, k: (0, 0))
    return block, turned, halo, whole(Kc), whole(1)


def _conv_operands(conv_w, conv_b):
    f32 = jnp.float32
    return conv_w.astype(f32), conv_b.astype(f32)[None]


def _statics(T, turned):
    """What the calls below are built from beside their operands, as
    hashable arguments: a call of one kind and shape is traced and lowered
    once a program, however many layers make it (a nested ``jit``; with
    every call site lowering its kernel anew the cell's ``lower_s`` read
    24.1 s for the parent's 17.3, my chip runs, PR 41)."""
    bt, rows = _blocks(T)
    return dict(bt=bt, rows=rows, lanes=_CHANNELS,
                turn=_TURN if turned else None, interpret=_pallas.INTERPRET)


@functools.partial(jax.jit, static_argnames=(
    "sizes", "bt", "rows", "lanes", "turn", "interpret"))
def _conv_fwd_call(xBC, conv_w, conv_b, *, sizes, bt, rows, lanes, turn,
                   interpret):
    Bt, T, C = xBC.shape
    block, turned, halo, w_spec, b_spec = _conv_specs(C, conv_w.shape[0], bt,
                                                      lambda k: k)
    first = ((turned, (Bt, sizes[0], T)) if turn
             else (block, (Bt, T, sizes[0])))
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, rows=rows, lanes=lanes, turn=turn),
        grid=(Bt, T // bt),
        in_specs=[block(C), halo, w_spec, b_spec],
        out_specs=[first[0](sizes[0])] + [block(s) for s in sizes[1:]],
        out_shape=[_sds(shape, xBC.dtype, xBC, conv_w, conv_b)
                   for shape in [first[1]] + [(Bt, T, s) for s in sizes[1:]]],
        scratch_shapes=[pltpu.VMEM((1, bt, sizes[0]), xBC.dtype)] * bool(turn),
        compiler_params=_pallas.params("parallel", "parallel"),
        interpret=interpret,
        name="hvd_conv_silu_fwd",
    )(xBC, xBC, *_conv_operands(conv_w, conv_b))


@functools.partial(jax.jit, static_argnames=(
    "sizes", "bt", "rows", "lanes", "turn", "interpret"))
def _conv_bwd_call(xBC, conv_w, conv_b, douts, *, sizes, bt, rows, lanes,
                   turn, interpret):
    Bt, T, C = xBC.shape
    Kc, nk = conv_w.shape[0], T // bt
    block, turned, halo, w_spec, b_spec = _conv_specs(C, Kc, bt,
                                                      lambda k: nk - 1 - k)
    sums = pl.BlockSpec((1, 8 * (Kc + 1), C), lambda b, k: (b, 0, 0))
    operands = (xBC, xBC, *_conv_operands(conv_w, conv_b),
                *(d.astype(xBC.dtype) for d in douts))
    dx, partial = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, rows=rows, lanes=lanes, turn=turn),
        grid=(Bt, nk),
        in_specs=[block(C), halo, w_spec, b_spec,
                  (turned if turn else block)(sizes[0])]
        + [block(s) for s in sizes[1:]],
        out_specs=[block(C), sums],
        out_shape=[_sds(xBC.shape, xBC.dtype, *operands),
                   _sds((Bt, 8 * (Kc + 1), C), jnp.float32, *operands)],
        scratch_shapes=[pltpu.VMEM((8, C), jnp.float32)]
        + [pltpu.VMEM((1, bt, sizes[0]), xBC.dtype)] * bool(turn),
        compiler_params=_pallas.params("parallel", "arbitrary"),
        interpret=interpret,
        name="hvd_conv_silu_bwd",
    )(*operands)
    partial = partial.reshape(Bt, Kc + 1, 8, C).sum((0, 2))
    return (dx, partial[:Kc].astype(conv_w.dtype),
            partial[Kc].astype(conv_b.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_pallas(xBC, conv_w, conv_b, sizes, turned):
    return _conv_pallas_fwd(xBC, conv_w, conv_b, sizes, turned)[0]


def _conv_pallas_fwd(xBC, conv_w, conv_b, sizes, turned):
    _count("conv_fwd", "pallas")
    outs = _conv_fwd_call(xBC, conv_w, conv_b, sizes=sizes,
                          **_statics(xBC.shape[1], turned))
    return tuple(outs), (xBC, conv_w, conv_b)


def _conv_pallas_bwd(sizes, turned, res, douts):
    xBC, conv_w, conv_b = res
    _count("conv_bwd", "pallas")
    return _conv_bwd_call(xBC, conv_w, conv_b, tuple(douts), sizes=sizes,
                          **_statics(xBC.shape[1], turned))


_conv_pallas.defvjp(_conv_pallas_fwd, _conv_pallas_bwd)


def conv_silu_split(xBC, conv_w, conv_b, sizes, turned=False):
    """``silu(conv(xBC) + conv_b)`` cut along the channels into ``sizes``, a
    tuple of arrays in ``xBC``'s dtype, ``[Bt, T, size]`` each but, where
    ``turned``, the first: ``[Bt, sizes[0], T]``.  ``xBC [Bt, T, C]``;
    ``conv_w [Kc, C]`` (depthwise, causal: entry ``Kc - 1`` weighs the
    position itself); ``conv_b [C]``; ``sizes`` adds up to ``C`` (one entry
    is no split).  Differentiable in the three arrays."""
    sizes = tuple(sizes)
    reason = _refusal(xBC, sizes, turned)
    if reason is None and (conv_w.ndim != 2 or conv_w.shape[0] > 8
                           or conv_w.shape[1:] != xBC.shape[2:]
                           or conv_b.shape != xBC.shape[2:]):
        reason = "conv_w must be [up to 8, C] and conv_b [C]"
    if _verdict("conv_silu_split", reason, xBC):
        return _conv_pallas(xBC, conv_w, conv_b, sizes, bool(turned))
    _count("conv_fwd", "xla")
    return _conv_silu_split_xla(xBC, conv_w, conv_b, sizes, turned)


# ------------------------------------------------------ gate-norm kernels
# Blocks, a grid step (b, k): y, z, o and their cotangents (1, bt, C) of
# [Bt, T, C], a turned y and its cotangent (1, C, bt); w (1, C) float32
# whole; dw's sums (1, 8, C) of [Bt, 8, C].  A kernel walks its block
# ``rows`` positions at a time and those tile by tile of ``lanes`` channels,
# twice: the gate and the sums over all channels, kept in VMEM scratch
# ``[rows, C]`` float32, then what the sums scale.

def _row_sum(a):
    return jnp.sum(a, axis=-1, keepdims=True)


def _norm_fwd_kernel(y_ref, z_ref, w_ref, o_ref, g_ref, *turn_ref, rows,
                     lanes, turn, eps):
    """``turn_ref``: where ``y`` comes turned, a scratch ``(1, bt, C)`` to
    turn it into."""
    f32 = jnp.float32
    if turn_ref:
        _turn(y_ref, turn_ref[0], turn, back=True)
        y_ref = turn_ref[0]
    C = z_ref.shape[2]
    width = math.gcd(C, lanes)

    def walk(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)

        def gate(j, ss):
            cols = _lanes(0, j, width)
            z = z_ref[0, at, cols].astype(f32)
            g = y_ref[0, at, cols].astype(f32) * (z * jax.nn.sigmoid(z))
            g_ref[:, cols] = g
            return ss + _row_sum(g * g)

        ss = _fold(C // width, gate, jnp.zeros((rows, 1), f32))
        r = lax.rsqrt(ss / C + eps)

        def scale(j, _):
            cols = _lanes(0, j, width)
            o_ref[0, at, cols] = (g_ref[:, cols] * r * w_ref[:, cols]).astype(
                o_ref.dtype)
            return 0

        return _fold(C // width, scale)

    lax.fori_loop(0, z_ref.shape[1] // rows, walk, 0)


def _norm_bwd_kernel(y_ref, z_ref, w_ref, do_ref, dy_ref, dz_ref, dw_ref,
                     g_ref, s_ref, *turn_refs, rows, lanes, turn, eps):
    """With ``n = g r`` the normed gate and ``dn = do w``: ``dg = r (dn - n
    mean(dn n)) = r dn - g r^3 mean(dn g)``, the two sums over all channels
    (of ``g^2`` and ``dn g``) made in the first walk.  ``turn_refs``: where
    ``y`` comes and ``dy`` goes turned, two scratches ``(1, bt, C)``."""
    f32 = jnp.float32
    dyt_ref = dy_ref
    if turn_refs:
        _turn(y_ref, turn_refs[0], turn, back=True)
        y_ref, dy_ref = turn_refs
    C = z_ref.shape[2]
    width = math.gcd(C, lanes)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, f32)

    def walk(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)

        def gate(j, sums):
            cols = _lanes(0, j, width)
            z = z_ref[0, at, cols].astype(f32)
            s = jax.nn.sigmoid(z)
            g = y_ref[0, at, cols].astype(f32) * (z * s)
            g_ref[:, cols], s_ref[:, cols] = g, s
            dn = do_ref[0, at, cols].astype(f32) * w_ref[:, cols]
            return sums[0] + _row_sum(g * g), sums[1] + _row_sum(dn * g)

        zero = jnp.zeros((rows, 1), f32)
        ss, dd = _fold(C // width, gate, (zero, zero))
        r = lax.rsqrt(ss / C + eps)
        k = r * r * r * (dd / C)

        def back(j, _):
            cols = _lanes(0, j, width)
            y, z = (ref[0, at, cols].astype(f32) for ref in (y_ref, z_ref))
            do = do_ref[0, at, cols].astype(f32)
            g, s = g_ref[:, cols], s_ref[:, cols]
            dw_ref[0, :, cols] += _partials(do * (g * r))
            dg = do * w_ref[:, cols] * r - g * k
            dy_ref[0, at, cols] = (dg * (z * s)).astype(dy_ref.dtype)
            dz_ref[0, at, cols] = (dg * y * _dsilu(z, s)).astype(dz_ref.dtype)
            return 0

        return _fold(C // width, back)

    lax.fori_loop(0, z_ref.shape[1] // rows, walk, 0)
    if turn_refs:
        _turn(dy_ref, dyt_ref, turn)


def _norm_specs(C, bt):
    return (pl.BlockSpec((1, bt, C), lambda b, k: (b, k, 0)),
            pl.BlockSpec((1, C, bt), lambda b, k: (b, 0, k)),
            pl.BlockSpec((1, C), lambda b, k: (0, 0)))


@functools.partial(jax.jit, static_argnames=(
    "eps", "bt", "rows", "lanes", "turn", "interpret"))
def _norm_fwd_call(y, z, w, *, eps, bt, rows, lanes, turn, interpret):
    Bt, T, C = z.shape
    block, turned, w_spec = _norm_specs(C, bt)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, rows=rows, lanes=lanes, turn=turn,
                          eps=eps),
        grid=(Bt, T // bt),
        in_specs=[turned if turn else block, block, w_spec],
        out_specs=block,
        out_shape=_sds(z.shape, y.dtype, y, z, w),
        scratch_shapes=[pltpu.VMEM((rows, C), jnp.float32)]
        + [pltpu.VMEM((1, bt, C), y.dtype)] * bool(turn),
        compiler_params=_pallas.params("parallel", "parallel"),
        interpret=interpret,
        name="hvd_gated_norm_fwd",
    )(y, z, w.astype(jnp.float32)[None])


@functools.partial(jax.jit, static_argnames=(
    "eps", "bt", "rows", "lanes", "turn", "interpret"))
def _norm_bwd_call(y, z, w, do, *, eps, bt, rows, lanes, turn, interpret):
    Bt, T, C = z.shape
    block, turned, w_spec = _norm_specs(C, bt)
    y_spec = turned if turn else block
    operands = (y, z, w.astype(jnp.float32)[None], do.astype(y.dtype))
    dy, dz, dw = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, rows=rows, lanes=lanes, turn=turn,
                          eps=eps),
        grid=(Bt, T // bt),
        in_specs=[y_spec, block, w_spec, block],
        out_specs=[y_spec, block,
                   pl.BlockSpec((1, 8, C), lambda b, k: (b, 0, 0))],
        out_shape=[_sds(y.shape, y.dtype, *operands),
                   _sds(z.shape, z.dtype, *operands),
                   _sds((Bt, 8, C), jnp.float32, *operands)],
        scratch_shapes=[pltpu.VMEM((rows, C), jnp.float32)] * 2
        + [pltpu.VMEM((1, bt, C), y.dtype)] * (2 * bool(turn)),
        compiler_params=_pallas.params("parallel", "arbitrary"),
        interpret=interpret,
        name="hvd_gated_norm_bwd",
    )(*operands)
    return dy, dz, dw.sum((0, 1)).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm_pallas(y, z, w, eps, turned):
    return _norm_pallas_fwd(y, z, w, eps, turned)[0]


def _norm_pallas_fwd(y, z, w, eps, turned):
    _count("norm_fwd", "pallas")
    out = _norm_fwd_call(y, z, w, eps=eps, **_statics(z.shape[1], turned))
    return out, (y, z, w)


def _norm_pallas_bwd(eps, turned, res, do):
    y, z, w = res
    _count("norm_bwd", "pallas")
    return _norm_bwd_call(y, z, w, do, eps=eps,
                          **_statics(z.shape[1], turned))


_norm_pallas.defvjp(_norm_pallas_fwd, _norm_pallas_bwd)


def gated_rmsnorm(y, z, w, eps, turned=False):
    """``RMSNorm(y * silu(z)) * w`` over the channels, the gate before the
    norm, ``[Bt, T, C]`` in ``y``'s dtype.  ``z [Bt, T, C]``; ``y`` the same
    or, where ``turned``, ``[Bt, C, T]``; ``w [C]``; ``eps`` a Python float.
    Differentiable in the three arrays."""
    reason = _refusal(z, z.shape[2:], turned)
    if reason is None and (
            y.shape != ((z.shape[0], z.shape[2], z.shape[1]) if turned
                        else z.shape)
            or z.dtype != y.dtype or w.shape != z.shape[2:]):
        reason = "y must be as z, or as z turned, and w [C]"
    if _verdict("gated_rmsnorm", reason, z):
        return _norm_pallas(y, z, w, float(eps), bool(turned))
    _count("norm_fwd", "xla")
    return _gated_rmsnorm_xla(y, z, w, eps, turned)
