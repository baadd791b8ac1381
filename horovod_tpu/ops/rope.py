"""The rotate-half rotation of rotary positions as one Pallas kernel pass for
TPU, with a backward of its own: each operand read once and each result
written once, in the operands' dtype, float32 only in VMEM.

``x [B, T, H, 128]`` is read as the rows it is in memory, ``[B, T, H *
128]`` (the layout the flash kernels read: nothing is transposed between
them), a head a vector register's width.  A head's two halves are ``x1``
and ``x2``, and with ``cos`` and ``sin`` ``[1 or B, T, 64]`` float32

    out = [x1 cos - x2 sin ; x1 sin + x2 cos]
        = x32 * [cos ; cos] + roll(x32, 64 lanes) * [-sin ; sin]

in float32, rounded once: ``models/llama.py::rotate``'s values to the bit
(``a + (-b)`` is ``a - b``).  XLA makes the halves in two fusions on 64 of
128 lanes each and joins them in a third; here a grid step takes ``_BLOCK``
positions by every head and walks them ``_ROWS`` at a time, the two
full-width tables made once a tile of positions and used by every head.

:func:`split_rotate` takes the rows of several such arrays joined along
their columns, ``a [B, T, sum(widths)]`` (a fused ``wqkv`` product), and
writes each part as an array of its own, rotated or not: no slice of the
product is made before the call, and backward the parts' cotangents are
written into one joined array, no concatenate after it.

The transpose of a rotation is the rotation by the negated angle, so
``hvd_rope_bwd`` is the same kernel body with ``sin`` negated and the
residuals are the tables alone: nothing of ``x`` is saved, and a remat'd
layer reruns ``hvd_rope_fwd`` as it reran XLA's fusions.  The tables are
constants of the rotation (made from integer positions and a
configuration's numbers): no cotangent flows to them, as
``lax.stop_gradient`` says where they come in.

``hvd_rope_kernel_total{kernel, path}`` counts the calls built, once per
traced call site: ``kernel`` is ``fwd`` or ``bwd`` (``norm_fwd``,
``norm_bwd`` for the pass below), ``path`` is ``pallas`` or ``xla`` (the
caller's own form, counted by it through :func:`count_xla`; its backward
is autodiff's and is not counted).

There is no plain form here: the caller keeps its own (``llama.rotate``
around a ``jnp.split``, in ``models/hybrid.py``'s layer) and asks
:func:`supported` first, which refuses another backend than a TPU, another
head width than the 128 lanes, a ``T`` the block of positions does not
divide (a decoded row of one position), tables that are not float32 ``[1 or
B, T, 64]`` and operands that are neither bfloat16 nor float32: the choice
is from shapes and backend, no knob.

:func:`norm_rotate` is the scanned llama trunk's pass where it norms ``q``
and ``k`` (``llama._attention`` under ``cfg.qk_norm``, SDAR): the same
walk with the RMS norm of a head in front of the rotation, on a ``wq`` or
``wk`` product's rows as the convolution fusion writes them, so nothing
positions-minor and nothing in float32 lies in HBM between the product and
the flash kernels.  The rotation alone lost there, behind a norm left to
XLA (PERF.md, PR 47); this pass takes the norm with it (PR 48).  It is
float32 inside and rounds once, where ``_rmsnorm`` as written rounds the
scaled row and its product with the weight to the rows' dtype before
``_rope`` widens them again: XLA drops those two round trips for the chip
(excess precision; of the 67,108,864 numbers of SDAR's ``q`` none differs
from the chip's own compile of the standing form, and 31% would by one unit
in the last place with ``_rmsnorm``'s first rounding kept: PERF.md, PR 48),
and the CPU's compile keeps the first of them, so there the pass is within
two units of the standing form (on float32 rows, where nothing is rounded
on the way, within the few that another order of the lane sums makes).  Its
backward reads the product's rows again beside the cotangent's and makes
the inverse root again, so the residuals are those rows, the weight and
the tables.  :func:`norm_supported` is its :func:`supported`; a scanned
trunk without q/k norm keeps XLA's ``llama._rope``.
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
from ._pallas import LANES, sds as _sds, verdict as _verdict

_BLOCK = 256        # positions a grid step: 2 MiB of bf16 at 32 heads
_ROWS = 32          # positions a tile of the walk inside a block
_PACK = 16          # positions a packed bf16 tile holds

_count = _pallas.kernel_counter(
    "hvd_rope_kernel_total",
    "Rotary rotations built, one per traced call site; kernel is fwd or "
    "bwd (the rotation by the negated angle), norm_fwd or norm_bwd (q/k "
    "norm and the rotation as one pass, an operand a call), path is "
    "pallas (ops/rope.py's kernel) or xla (models/llama.py's split-and-"
    "concatenate form, behind its RMS norm under norm_fwd, for q and k "
    "together; its backward is autodiff's and is not counted)")


def count_xla(normed: bool = False) -> None:
    """The caller built a rotation in its own ``jax.numpy`` form, behind
    its own q/k norm where ``normed``."""
    _count("norm_fwd" if normed else "fwd", "xla")


def _batched(shape):
    """A table's shape ``[1 or B, T, 64]``; one of ``[T, 64]`` is every
    row's."""
    return (1,) + tuple(shape) if len(shape) == 2 else tuple(shape)


def _constants(cos, sin):
    """The tables as ``[1 or B, T, 64]``, and no cotangent flows to them."""
    return tuple(lax.stop_gradient(t).reshape(_batched(t.shape))
                 for t in (cos, sin))


def _blocks(T: int):
    """(positions a grid step, positions a tile of the walk inside it)."""
    bt = min(_BLOCK, T)
    return bt, min(_ROWS, bt)


def _refusal(a, cos, sin, widths) -> Optional[str]:
    """Which test keeps the kernel off rows ``a [B, T, sum(widths)]`` (an
    array or its shape and dtype) under tables ``cos`` and ``sin``; None =
    it runs."""
    if (why := _pallas.off_chip()):
        return why
    if len(a.shape) != 3 or sum(widths) != a.shape[2]:
        return f"operand must be [batch, T, {sum(widths)} columns]"
    cos_shape, sin_shape = _batched(cos.shape), _batched(sin.shape)
    if len(cos_shape) != 3 or 2 * cos_shape[2] != LANES:
        return (f"tables {tuple(cos.shape)} turn no heads of the {LANES} "
                "lanes")
    if any(w % LANES for w in widths):
        return (f"parts of {tuple(widths)} columns are no whole heads of "
                f"{LANES}")
    B, T = a.shape[:2]
    bt, rows = _blocks(T)
    if T % bt or bt % rows or rows % _PACK:
        return (f"{T} positions are no multiple of the block {_BLOCK} (or "
                f"of {_PACK})")
    if (cos_shape != sin_shape or cos_shape[1] != T
            or cos_shape[0] not in (1, B)):
        return (f"tables {tuple(cos.shape)} and {tuple(sin.shape)} are not "
                f"[1 or {B}, {T}, {LANES // 2}]")
    if cos.dtype != jnp.float32 or sin.dtype != jnp.float32:
        return f"tables of {cos.dtype} and {sin.dtype}, not float32"
    return _pallas.dtype_refusal(a.dtype)


def supported(a, cos, sin, widths) -> bool:
    """True when the kernel can run rows ``a [B, T, sum(widths)]`` under
    tables ``cos``, ``sin`` ``[1 or B, T, 64]`` (or ``[T, 64]``, every
    row's) float32 on this backend; said once at WARNING where a TPU takes
    the caller's form instead."""
    return _verdict("rope", _refusal(a, cos, sin, tuple(widths)), a, cos)


# ----------------------------------------------------------------- kernel
# Blocks, a grid step (b, k): the joined rows (1, bt, W) of [B, T, W]; each
# part (1, bt, width) of [B, T, width]; cos and sin (1, bt, 64) of [1 or B,
# T, 64] float32.

def _kernel(cos_ref, sin_ref, *refs, widths, turned, rows, back):
    """Forward ``refs`` is the joined operand, then the parts (results);
    ``back`` the parts' cotangents, then the joined result."""
    if back:
        *parts, a_ref = refs
    else:
        a_ref, *parts = refs
    f32 = jnp.float32

    def tile(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        c, s = cos_ref[0, at, :], sin_ref[0, at, :]
        cc = jnp.concatenate([c, c], axis=1)
        ss = jnp.concatenate([s, -s] if back else [-s, s], axis=1)
        first = 0
        for part_ref, width, turn in zip(parts, widths, turned):
            size = LANES if turn else width     # a head, or all as it is
            for col in range(0, width, size):
                joined = (a_ref, pl.ds(first + col, size))
                own = (part_ref, pl.ds(col, size))
                (src, sc), (dst, dc) = (own, joined) if back else (joined, own)
                x = src[0, at, sc]
                if turn:
                    x = x.astype(f32)
                    x = x * cc + pltpu.roll(x, LANES // 2, axis=1) * ss
                dst[0, at, dc] = x.astype(dst.dtype)
            first += width
        return 0

    lax.fori_loop(0, a_ref.shape[1] // rows, tile, 0)


def _table_block(cos, B, bt):
    """A grid step's block of a table ``[1 or B, T, 64]``: every row's, or
    the row's own."""
    return pl.BlockSpec(
        (1, bt, LANES // 2),
        (lambda b, k: (b, k, 0)) if cos.shape[0] == B else
        (lambda b, k: (0, k, 0)))


@functools.partial(jax.jit, static_argnames=(
    "widths", "turned", "bt", "rows", "back", "interpret"))
def _call(operands, cos, sin, *, widths, turned, bt, rows, back, interpret):
    """``operands``: forward the joined rows ``(a,)``, ``back`` the parts'
    cotangents.  A nested ``jit``: a program traces and lowers each kernel
    once, however many layers and passes call it."""
    B, T = operands[0].shape[:2]
    dtype = operands[0].dtype
    block = lambda width: pl.BlockSpec((1, bt, width), lambda b, k: (b, k, 0))
    table = _table_block(cos, B, bt)
    split = [block(w) for w in widths]
    joined = [block(sum(widths))]
    shapes = [(B, T, sum(widths))] if back else [(B, T, w) for w in widths]
    return pl.pallas_call(
        functools.partial(_kernel, widths=widths, turned=turned, rows=rows,
                          back=back),
        grid=(B, T // bt),
        in_specs=[table, table] + (split if back else joined),
        out_specs=joined if back else split,
        out_shape=[_sds(shape, dtype, *operands, cos, sin)
                   for shape in shapes],
        compiler_params=_pallas.params("parallel", "parallel"),
        interpret=interpret,
        name="hvd_rope_bwd" if back else "hvd_rope_fwd",
    )(cos, sin, *operands)


def _statics(T):
    bt, rows = _blocks(T)
    return dict(bt=bt, rows=rows, interpret=_pallas.INTERPRET)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rotated(a, cos, sin, widths, turned):
    return _rotated_fwd(a, cos, sin, widths, turned)[0]


def _rotated_fwd(a, cos, sin, widths, turned):
    _count("fwd", "pallas")
    parts = _call((a,), cos, sin, widths=widths, turned=turned, back=False,
                  **_statics(a.shape[1]))
    return tuple(parts), (cos, sin)


def _rotated_bwd(widths, turned, res, dparts):
    cos, sin = res
    _count("bwd", "pallas")
    (da,) = _call(tuple(dparts), cos, sin, widths=widths, turned=turned,
                  back=True, **_statics(dparts[0].shape[1]))
    return da, None, None       # the tables take no cotangent


_rotated.defvjp(_rotated_fwd, _rotated_bwd)


def split_rotate(a, cos, sin, widths, turned):
    """``a [B, T, sum(widths)]`` cut along its columns into ``widths``, a
    tuple of arrays ``[B, T, width]`` in ``a``'s dtype; a part whose
    ``turned`` is true is rotated head by head of 128 columns (rotate-half)
    by ``cos`` and ``sin`` ``[1 or B, T, 64]`` or ``[T, 64]`` float32, the
    others come as they are.  Differentiable in ``a``.  Only where
    :func:`supported`."""
    cos, sin = _constants(cos, sin)
    return _rotated(a, cos, sin, tuple(widths), tuple(map(bool, turned)))


# ------------------------------------------------- q/k norm and rotation
# Blocks, a grid step (b, k): the product's rows, the cotangent's and the
# results (1, bt, W) of [B, T, W]; cos and sin as above; the norm's weight
# (1, 128) float32, whole; backward the weight's partial sums (1, 1, 1, 128)
# of [B, T / bt, 1, 128] float32.

def _lane_sums(v, exact_in: int):
    """``v [rows, 128]`` float32 -> each row's sum, in every lane of the
    row.  On the MXU, which the pass leaves idle: ``v`` cut into
    ``exact_in`` bfloat16 pieces (the rounded value, then what rounding
    left, ...), each times a matrix of ones, added in float32.  Two pieces
    hold the square of a bfloat16 number whole (16 bits), three a float32.
    The vector unit's own lane reduction was four fifths of the pass: q's
    forward 2.02 ms for 0.47, backward 2.02 for 0.69 (PERF.md, PR 48)."""
    ones = jnp.ones((LANES, LANES), jnp.bfloat16)
    total = None
    for left in range(exact_in, 0, -1):
        piece = v.astype(jnp.bfloat16)
        if left > 1:
            v = v - piece.astype(jnp.float32)
        part = jnp.dot(piece, ones, preferred_element_type=jnp.float32)
        total = part if total is None else total + part
    return total


def _norm_kernel(cos_ref, sin_ref, w_ref, a_ref, *refs, eps, rows, back):
    """Forward ``refs`` is the result; ``back`` the result's cotangent,
    then the product's cotangent and the weight's partial sums.  A head
    ``x`` of the product's rows, float32 in VMEM and rounded once:

        r = rsqrt(mean(x x) + eps)    xh = x r    y = xh w
        out = round(y [cos;cos] + roll(y) [-sin;sin])

    the values XLA compiles ``llama._rmsnorm`` then ``llama._rope`` to on
    the chip, where it drops the two bfloat16 round trips between them as
    excess precision (module docstring).  Backward, with ``dy`` the
    cotangent turned by the negated angle:

        dw += dy xh    dn = dy w
        da = round(r (dn - xh mean(dn xh)))
    """
    f32 = jnp.float32
    w = w_ref[...]
    # a float32 row's square needs three pieces, a bfloat16 row's two; the
    # backward's second sum is of float32 products, and is held as near
    exact_in = 2 if a_ref.dtype == jnp.bfloat16 else 3
    mean = lambda v: _lane_sums(v, exact_in) * (1.0 / LANES)
    if back:
        g_ref, da_ref, dw_ref = refs
    else:
        (out_ref,) = refs

    def tile(i, dw):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        c, s = cos_ref[0, at, :], sin_ref[0, at, :]
        cc = jnp.concatenate([c, c], axis=1)
        ss = jnp.concatenate([s, -s] if back else [-s, s], axis=1)
        turn = lambda v: v * cc + pltpu.roll(v, LANES // 2, axis=1) * ss
        for col in range(0, a_ref.shape[2], LANES):
            head = pl.ds(col, LANES)
            x = a_ref[0, at, head].astype(f32)
            r = lax.rsqrt(mean(x * x) + eps)
            xh = x * r
            if back:
                dy = turn(g_ref[0, at, head].astype(f32))
                dw = dw + dy * xh
                dn = dy * w
                da_ref[0, at, head] = (r * (dn - xh * mean(dn * xh))).astype(
                    da_ref.dtype)
            else:
                out_ref[0, at, head] = turn(xh * w).astype(out_ref.dtype)
        return dw

    dw = lax.fori_loop(0, a_ref.shape[1] // rows, tile,
                       jnp.zeros((rows, LANES), f32))
    if back:
        dw_ref[0, 0] = jnp.sum(dw, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=(
    "eps", "bt", "rows", "back", "interpret"))
def _norm_call(operands, w, cos, sin, *, eps, bt, rows, back, interpret):
    """``operands``: the product's rows ``(a,)``, ``back`` the result's
    cotangent after them.  A nested ``jit``, as :func:`_call`."""
    a = operands[0]
    B, T, W = a.shape
    block = pl.BlockSpec((1, bt, W), lambda b, k: (b, k, 0))
    table = _table_block(cos, B, bt)
    weight = pl.BlockSpec((1, LANES), lambda b, k: (0, 0))
    rows_out = _sds((B, T, W), a.dtype, *operands, w, cos, sin)
    sums = pl.BlockSpec((1, 1, 1, LANES), lambda b, k: (b, k, 0, 0))
    sums_out = _sds((B, T // bt, 1, LANES), jnp.float32, *operands, w, cos,
                    sin)
    return pl.pallas_call(
        functools.partial(_norm_kernel, eps=eps, rows=rows, back=back),
        grid=(B, T // bt),
        in_specs=[table, table, weight] + [block] * len(operands),
        out_specs=[block, sums] if back else [block],
        out_shape=[rows_out, sums_out] if back else [rows_out],
        # the blocks of rows a step holds (the operands' and the result's),
        # twice for the pipeline's two buffers, and 4 MiB for Mosaic's own:
        # XLA keeps a call's limit clear of its own fast-memory buffers, and
        # under ``_pallas.STEP_VMEM`` a gather of the experts' backward lost
        # its operand's place there, 4.9 ms a step for 0.9 (PERF.md, PR 48)
        compiler_params=_pallas.params(
            "parallel", "parallel",
            vmem=2 * (len(operands) + 1) * bt * W * a.dtype.itemsize
            + 4 * 1024 * 1024),
        interpret=interpret,
        name="hvd_rope_norm_bwd" if back else "hvd_rope_norm_fwd",
    )(cos, sin, w, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _normed(a, w, cos, sin, eps):
    return _normed_fwd(a, w, cos, sin, eps)[0]


def _normed_fwd(a, w, cos, sin, eps):
    _count("norm_fwd", "pallas")
    (out,) = _norm_call((a,), w, cos, sin, eps=eps, back=False,
                        **_statics(a.shape[1]))
    return out, (a, w, cos, sin)


def _normed_bwd(eps, res, dout):
    a, w, cos, sin = res
    _count("norm_bwd", "pallas")
    da, sums = _norm_call((a, dout), w, cos, sin, eps=eps, back=True,
                          **_statics(a.shape[1]))
    return da, sums.sum((0, 1)), None, None


_normed.defvjp(_normed_fwd, _normed_bwd)


def norm_supported(a, w, cos, sin) -> bool:
    """:func:`supported` for :func:`norm_rotate`: rows ``a [B, T, H *
    128]`` of whole heads under a weight ``w [128]``."""
    reason = _refusal(a, cos, sin, (a.shape[-1],))
    if reason is None and tuple(w.shape) != (LANES,):
        reason = f"a norm weight of {tuple(w.shape)}, not ({LANES},)"
    return _verdict("rope", reason, a, cos)


def norm_rotate(a, w, cos, sin, eps):
    """``a [B, T, H * 128]`` (a ``wq`` or ``wk`` product's rows), each head
    of 128 columns scaled to unit root mean square with ``eps`` and by
    ``w [128]`` (``llama._rmsnorm``; ``w`` in ``a``'s dtype, as it casts
    it), then rotated by ``cos`` and ``sin`` ``[1 or B, T, 64]`` float32
    (``llama._rope``), in float32 and rounded once to ``a``'s dtype: one
    read of ``a``, one write of the result.  Differentiable in ``a`` and
    ``w``; the residuals are ``a``, ``w`` and the tables (the inverse root
    is made again in VMEM).  Only where :func:`norm_supported`."""
    cos, sin = _constants(cos, sin)
    w = w.astype(a.dtype).astype(jnp.float32).reshape(1, LANES)
    return _normed(a, w, cos, sin, float(eps))
