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
traced call site: ``kernel`` is ``fwd`` or ``bwd``, ``path`` is ``pallas``
or ``xla`` (the caller's own form, counted by it through
:func:`count_xla`; its backward is autodiff's and is not counted).

There is no plain form here: the caller keeps its own (``llama.rotate``
around a ``jnp.split``, in ``models/hybrid.py``'s layer) and asks
:func:`supported` first, which refuses another backend than a TPU, another
head width than the 128 lanes, a ``T`` the block of positions does not
divide (a decoded row of one position), tables that are not float32 ``[1 or
B, T, 64]`` and operands that are neither bfloat16 nor float32: the choice
is from shapes and backend, no knob.  The scanned llama trunk's
``llama._rope`` stays XLA's: behind q/k norm, which XLA fuses with the
rotation, the kernel lost on the chip (PERF.md, PR 47).
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
    "bwd (the rotation by the negated angle), path is pallas "
    "(ops/rope.py's kernel) or xla (models/llama.py's split-and-"
    "concatenate form around a jnp.split, for q and k together; its "
    "backward is autodiff's and is not counted)")


def count_xla() -> None:
    """The caller built a rotation in its own ``jax.numpy`` form."""
    _count("fwd", "xla")


def _batched(shape):
    """A table's shape ``[1 or B, T, 64]``; one of ``[T, 64]`` is every
    row's."""
    return (1,) + tuple(shape) if len(shape) == 2 else tuple(shape)


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


@functools.partial(jax.jit, static_argnames=(
    "widths", "turned", "bt", "rows", "back", "interpret"))
def _call(operands, cos, sin, *, widths, turned, bt, rows, back, interpret):
    """``operands``: forward the joined rows ``(a,)``, ``back`` the parts'
    cotangents.  A nested ``jit``: a program traces and lowers each kernel
    once, however many layers and passes call it."""
    B, T = operands[0].shape[:2]
    dtype = operands[0].dtype
    block = lambda width: pl.BlockSpec((1, bt, width), lambda b, k: (b, k, 0))
    table = pl.BlockSpec(
        (1, bt, LANES // 2),
        (lambda b, k: (b, k, 0)) if cos.shape[0] == B else
        (lambda b, k: (0, k, 0)))
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
    cos, sin = (lax.stop_gradient(t).reshape(_batched(t.shape))
                for t in (cos, sin))
    return _rotated(a, cos, sin, tuple(widths), tuple(map(bool, turned)))
