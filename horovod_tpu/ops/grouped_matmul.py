"""Grouped matrix products as Pallas kernels for TPU.

What a routed expert layer that drops no token spends its MXU time on
(:mod:`horovod_tpu.models.moe`, the dropless path): rows ``[R, K]``
sorted by group, ``sizes [G]`` rows each, and one weight matrix a group.
XLA's own (``lax.ragged_dot``) held 36.5% of the products' compute bound
in the benchmark's SDAR cell (ledger, PR 29); these hold their operands
in VMEM a tile of rows at a time and leave the epilogues to the caller.

Two kernels, one walk.  :func:`_plan` turns ``sizes`` into a table of
*visits* on the device, read from SMEM (scalar prefetch): the (row tile,
group) pairs that hold a row, in order, so a tile that straddles groups
is visited once for each with the other groups' rows masked, a group's
weights are fetched once (consecutive visits of one group name the same
block), and tiles past ``sizes.sum()`` cost no product: the visits left
over write zeros to them, one store a tile, or do nothing.  The grid is
``tiles + groups - 1`` visits, the most any ``sizes`` can need.

* :func:`gmm` (``hvd_moe_gmm_<name>``) — for every visit ``body`` gets
  the row tiles and the group's weight matrices and returns the output
  tiles; rows of other groups keep what their own visit writes, rows of
  no group come out zero.  The products and whatever follows them on
  the fp32 accumulators (an activation, a row's weight, a second product
  into the same accumulator) are the caller's: :func:`dot` is the
  product, bf16 or float32 operands and fp32 accumulation, with the
  weights read as stored or transposed.
* :func:`tgmm` (``hvd_moe_tgmm_<name>``) — rows-transposed x rows, a
  group at a time, added to a float32 ``[G, K, N]`` accumulator that is
  the call's input and, aliased, its output: a group's block stays in
  VMEM over its visits and is written once; a group without rows keeps
  what it held.  How much of the block one straight-line body adds is
  chosen from the shapes for speed (:func:`_tgmm_slab`, the numbers at
  ``_TGMM_BODY``): a visit whose whole product is over the line walks the
  block's rows in a loop, every element still one product over the
  tile's rows added once, so the sums are the whole walk's to the bit.

And what follows the products, :func:`combine`
(``hvd_moe_combine_<name>``): ``out[tok[r]] += rows[r]``, the rows back
to their tokens, float32.  XLA's scatter-add took 0.11 us a row whatever
a row held, 2.62 ms a call at 22% of the bytes' bound (my chip runs, PR
30).  Inside a group the tokens ascend, so the rows of one group that
land in one tile of tokens are one run of consecutive rows: the kernel
walks token tiles, a tile stays in VMEM while its runs come in from HBM,
and is written once.

And what precedes them, :func:`dispatch` (``hvd_moe_dispatch_<name>``):
``rows[r] = table[tok[r]]``, a chunk's rows out of their tokens, in the
products' dtype: the combine's walk with the copies turned around.  XLA's
gather copies every row of a chunk, the third that no product reads with
the rest, and from HBM at a third of the bytes' bound (0.818 ms a call of
the SDAR cell's cotangents, my chip runs, PR 31).  The kernel walks the
same tiles of tokens: a tile comes through VMEM once (as words of 32 bits,
two columns each: a row of a packed dtype cannot be read alone), each row
of a run is moved to its place in a block of 32 rows staged a group, and a
block leaves for HBM when its last row is in; a block that holds rows of
two groups or more is staged once for all of them and leaves last.  Rows
past ``sizes.sum()`` are never written.  The name is no ``hvd_moe_gmm`` /
``tgmm`` / ``combine``: the benchmark's rooflines sum kernels by those
prefixes.

``hvd_moe_gmm_kernel_total{kernel, path}`` counts the calls built, once
per traced call site: ``path=pallas`` here, ``path=xla`` where the
caller fell back to ``lax.ragged_dot`` (:func:`count_xla`), the combine
to ``.at[].add`` or the dispatch (``kernel=gather``) to ``table[tok]``.

Falls back cleanly: :func:`supported`, :func:`combine` and
:func:`dispatch` gate on backend, shapes and dtype (no knob); on a TPU
backend each refused shape is logged once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _pallas
# a grid step may hold _STEP_VMEM: a group's float32 [2048, 768]
# accumulator, in and out and double-buffered, is 25 MiB of it
from ._pallas import (LANES as _LANES, STEP_VMEM as _STEP_VMEM, sds as _sds,
                      verdict as _verdict)

# Rows a tile, by the kernels alone at the SDAR cell's shapes (my chip
# runs, PR 30): gmm is fastest at 256 (a straddling tile costs a whole
# visit: 99 visits of 84 tiles, where 512 rows make 57 of 42; 128 rows are
# no faster), tgmm at 512 (it adds a float32 [K, N] block in VMEM a visit:
# 6.4 us a visit at 256 rows against 4.1 of MXU time, 10.1 against 8.2 at
# 512)
_TILE = 256
_TGMM_TILE = 512
# The 128^3 products (K/128 x N/128 x 4 at 512 rows) that one straight-line
# body of tgmm may hold.  Mosaic unrolls a visit's product, and a body over
# the line runs at a quarter of the MXU whatever it computes; tgmm alone, 8
# groups of 2,065 rows, 40 visits, bf16, "MXU us" the call's products at
# 197 TFLOP/s over its visits (my chip runs, PR 54):
#
#   accumulator [K, N]        products  us a visit  MXU us
#   2048 x 768  (SDAR)           384       11.4       6.6
#   2304 x 896  (Mellum)         504       14.4       8.6
#   2048 x 1280 (Solar's block)  640       48.8      11.0
#   2048 x 1536                  768       57.0      13.2
#   2048 x 1792 (LFM2)           896       65.5      15.4
#   the same, cut by hand to 1024 x 1792 (448): 13.0 for 7.7; as two such
#   dots written one after the other (unrolled: 896 again): 65.1; as two
#   passes of a lax.fori_loop (448 a pass): 24.2; 4 of 512 rows 24.9, 8 of
#   256 26.0, 16 of 128 26.8; a grid of two blocks 24.6
#
# and the transposes read the same to 0.3 us.  So it is the length of the
# unrolled body, not the product's float32 temporary (two dots of half the
# size in one body are as slow as one) and not HBM: the line lies between
# 504 and 640, and what PR 30 met at 1,024 rows a tile, or with two
# accumulators a call, was this (768 either way).
_TGMM_BODY = 512
# the combine: tokens a grid step holds, rows a copy brings, copies in flight
_COMBINE_TILE = 512
_COMBINE_CHUNK = 32
_COMBINE_RING = 8
_COMBINE_UNROLL = 4         # rows whose loads go before their stores
# scalar memory the token ids may take (one int32 a row), the combine's
# and the dispatch's alike
_COMBINE_SMEM = 256 * 1024
# the dispatch: rows a copy writes (two bfloat16 tiles': a block's
# bookkeeping and its pass from words to rows cost as much as six rows'
# moves, and 32 rows a block ran 13% faster than 16 at the Mellum cell's
# shapes, my chip runs, PR 52), copies in flight
_DISPATCH_BLOCK = 32
_DISPATCH_RING = 8
_DISPATCH_UNROLL = 4

_count = _pallas.kernel_counter(
    "hvd_moe_gmm_kernel_total",
    "Grouped matrix product and combine calls built, one per traced call "
    "site; kernel is gmm (rows x a group's weights), tgmm "
    "(rows-transposed x rows a group), combine (rows added to their "
    "tokens) or gather (a chunk's rows out of their tokens), path is "
    "pallas (ops/grouped_matmul.py: a gmm call may hold two products) or "
    "xla (lax.ragged_dot; the scatter-add; the gather)")


def count_xla(gmm: int = 0, tgmm: int = 0, gather: int = 0) -> None:
    """The caller built that many products as ``lax.ragged_dot`` (or had
    autodiff build them), that many gathers of a chunk's rows as XLA's."""
    for kernel, n in (("gmm", gmm), ("tgmm", tgmm), ("gather", gather)):
        if n:
            _count(kernel, "xla", n=n)


def _refusal(rows, *weights) -> Optional[str]:
    """Which test keeps the Pallas kernels off these products; None =
    they run.  ``rows [R, K]``; ``weights`` every ``[G, K, N]`` the
    layer's products read, as stored."""
    if (why := _pallas.off_chip()):
        return why
    if rows.ndim != 2 or any(w.ndim != 3 for w in weights):
        return "rows must be rank 2 and weights rank 3"
    if rows.shape[0] % max(_TILE, _TGMM_TILE):
        return (f"{rows.shape[0]} rows are no multiple of "
                f"{max(_TILE, _TGMM_TILE)}")
    if (why := _pallas.dtype_refusal(rows.dtype)):
        return why
    for w in weights:
        if w.dtype != rows.dtype or w.shape[0] != weights[0].shape[0]:
            return "weights must have the rows' dtype and one group count"
        if w.shape[1] % _LANES or w.shape[2] % _LANES:
            return (f"weights {w.shape[1:]} are no multiples of {_LANES} "
                    "both ways")
    # the dearest calls: gmm's two weight matrices, a row tile in and a
    # float32 one out, every buffer twice; tgmm's float32 accumulator in
    # and out and its two row tiles, in as many blocks of the accumulator's
    # rows as make them fit (:func:`_tgmm_split`)
    k = max(w.shape[1] * w.shape[2] for w in weights)
    wide, item = max(max(w.shape[1:]) for w in weights), rows.dtype.itemsize
    need = 2 * (2 * k * item + _TILE * wide * (item + 4))
    if need > _STEP_VMEM or not all(
            _tgmm_split(w.shape[1], w.shape[2], item) for w in weights):
        return (f"a grid step needs over the {_STEP_VMEM} bytes of VMEM a "
                f"step may hold (weights {weights[0].shape[1:]})")
    return None


def _tgmm_split(K: int, N: int, item: int) -> int:
    """In how many blocks of its rows :func:`tgmm` walks a ``[K, N]``
    accumulator, so that a block in and out and the two row tiles, every
    buffer twice, fit a grid step: 1 at the SDAR cell's 2,048 x 768, 2 at
    4,096 x 1,280; 0 = no whole number of lanes does."""
    for split in (1, 2, 4, 8):
        kb = K // split
        if K % (split * _LANES) == 0 and 2 * (
                2 * kb * N * 4 + 2 * _TGMM_TILE * max(kb, N) * item
                ) <= _STEP_VMEM:
            return split
    return 0


def _tgmm_slab(kb: int, N: int) -> int:
    """How many rows of a ``[kb, N]`` block (``x``'s columns) one pass of a
    visit's loop adds in :func:`tgmm`: the most that divide the block in
    whole numbers of lanes and keep the pass under ``_TGMM_BODY`` products;
    ``kb`` = the visit makes its product at once, with no loop (2,048 x
    768, 2,304 x 896 and their transposes; 1,024 of 2,048 x 1,792)."""
    per = (N // _LANES) * (_TGMM_TILE // _LANES)    # products, 128 rows
    return max((s for s in range(_LANES, kb + 1, _LANES)
                if kb % s == 0 and s // _LANES * per <= _TGMM_BODY),
               default=_LANES)


@functools.lru_cache(maxsize=None)
def _say_walk(K: int, N: int, split: int, slab: int) -> None:
    _pallas.logger.info(
        "tgmm walks a [%d, %d] accumulator in %d block(s) of %d rows, %d "
        "rows a pass", K, N, split, K // split, slab)


def supported(rows, *weights) -> bool:
    """True when the Pallas kernels can run the grouped products of
    ``rows [R, K]`` with these ``[G, K, N]`` weights on this backend."""
    return _verdict("grouped_matmul", _refusal(rows, *weights), rows,
                    *weights)


def dot(x, w, transposed: bool = False):
    """``x [m, k] @ w [k, n]`` (``w [n, k]`` read transposed) on the MXU,
    fp32 accumulation: the product a :func:`gmm` body makes."""
    return lax.dot_general(
        x, w, (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _plan(sizes, rows: int, tm: int):
    """``(table [4 V], bounds [2 G], V)`` int32, made on the device: for
    visit ``v`` its group, the row tile it writes, the row tile it reads
    and flags at ``table[4 v : 4 v + 4]``; group ``g`` holds rows
    ``bounds[2 g] <= r < bounds[2 g + 1]``.  Flags: ``% 4`` is 1 for a
    (tile, group) pair that holds a row, 2 for a tile past the rows that
    this visit zeroes, 0 for nothing to do; ``+ 4`` on a written tile's
    first visit, ``+ 8`` on a group's first.  Visits past the pairs keep
    naming the last pair's group and read tile, so they fetch nothing."""
    G, tiles = sizes.shape[0], rows // tm
    V = tiles + G - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    vend = jnp.cumsum(n)
    active = vend[-1]
    v = jnp.arange(V, dtype=jnp.int32)
    va = jnp.minimum(v, jnp.maximum(active - 1, 0))
    g = jnp.minimum(jnp.searchsorted(vend, va, side="right"),
                    G - 1).astype(jnp.int32)
    read = first[g] + va - (vend - n)[g]
    dead = (ends[-1] + tm - 1) // tm + v - active
    written = jnp.where(v < active, read, jnp.minimum(dead, tiles - 1))
    kind = jnp.where(v < active, 1, jnp.where(dead < tiles, 2, 0))
    before = lambda a: jnp.concatenate([jnp.full((1,), -1, jnp.int32), a[:-1]])
    flags = kind + 4 * (written != before(written)) + 8 * (g != before(g))
    table = jnp.stack([g, written, read, flags], axis=1).reshape(-1)
    bounds = jnp.stack([starts, ends], axis=1).reshape(-1)
    return table.astype(jnp.int32), bounds.astype(jnp.int32), V


def _visit(tbl, bounds, tm, axis=0):
    """This grid step's ``(flags, whole, mask)``: ``whole`` when the read
    tile lies inside the group, ``mask()`` ``[tm, 1]`` the tile's rows
    that are the group's.  ``axis``: the grid's axis that counts visits."""
    v = pl.program_id(axis)
    g, flags = tbl[4 * v], tbl[4 * v + 3]
    r0 = tbl[4 * v + 2] * tm
    lo, hi = bounds[2 * g], bounds[2 * g + 1]

    def mask():
        row = r0 + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        return (row >= lo) & (row < hi)

    return flags, (lo <= r0) & (hi >= r0 + tm), mask


def _gmm_kernel(tbl, bounds, *refs, body, n_rows, n_weights, tm):
    ins, outs = refs[:n_rows + n_weights], refs[n_rows + n_weights:]
    flags, whole, mask = _visit(tbl, bounds, tm)
    pair = flags % 4 == 1

    def tiles():
        return body(*(r[...] for r in ins[:n_rows]),
                    *(r[0] for r in ins[n_rows:]))

    @pl.when(pair & whole)
    def _():
        for o, y in zip(outs, tiles()):
            o[...] = y.astype(o.dtype)

    @pl.when(pair & jnp.logical_not(whole))
    def _():
        @pl.when(flags % 8 >= 4)       # the tile's first visit
        def _():
            for o in outs:
                o[...] = jnp.zeros_like(o)

        m = mask()
        for o, y in zip(outs, tiles()):
            o[...] = jnp.where(m, y.astype(o.dtype), o[...])

    @pl.when(flags % 4 == 2)
    def _():
        for o in outs:
            o[...] = jnp.zeros_like(o)


def _limit(blocks: int, temporaries: int, axes: int = 1):
    """The call's VMEM limit: its blocks double-buffered, the fp32 tiles
    in flight, and room."""
    return _pallas.params(
        *("arbitrary",) * axes,
        vmem=2 * blocks + temporaries + 16 * 1024 * 1024)


def gmm(body, rows, weights, sizes, outs, name: str):
    """Grouped products with the caller's epilogue.  ``rows``: arrays
    ``[R, *]`` sorted by group, cut into tiles of ``_TILE`` rows (which
    divides ``R``: :func:`supported`); ``weights``: arrays ``[G, *, *]``;
    ``sizes [G]`` int32 rows a group; ``outs``: ``(width, dtype)`` of each
    output ``[R, width]``.  For every (tile, group) pair that holds a
    row, ``body(*row_tiles, *weight_matrices)`` returns the output tiles,
    of which the group's rows are kept; rows past ``sizes.sum()`` come
    out zero and their tiles are not computed."""
    R, tm = rows[0].shape[0], _TILE
    table, bounds, V = _plan(sizes, R, tm)
    _count("gmm", "pallas")
    row = lambda width, at: pl.BlockSpec(
        (tm, width), lambda v, t, b: (t[4 * v + at], 0))
    held = lambda w: pl.BlockSpec(
        (1,) + w.shape[1:], lambda v, t, b: (t[4 * v], 0, 0))
    blocks = (sum(tm * r.shape[1] * r.dtype.itemsize for r in rows)
              + sum(w[0].size * w.dtype.itemsize for w in weights)
              + sum(tm * width * jnp.dtype(d).itemsize for width, d in outs))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, body=body, n_rows=len(rows),
                          n_weights=len(weights), tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(V,),
            in_specs=[row(r.shape[1], 2) for r in rows]
            + [held(w) for w in weights],
            out_specs=[row(width, 1) for width, _ in outs]),
        out_shape=[_sds((R, width), d, sizes, *rows, *weights)
                   for width, d in outs],
        compiler_params=_limit(
            blocks, 3 * 4 * tm * max(max(width for width, _ in outs),
                                     max(r.shape[1] for r in rows))),
        interpret=_pallas.INTERPRET,
        name="hvd_moe_gmm_" + name,
    )(table, bounds, *rows, *weights)


def _tgmm_kernel(tbl, bounds, x_ref, y_ref, held, acc, *, tm, axis, slab):
    flags, whole, mask = _visit(tbl, bounds, tm, axis)
    pair = flags % 4 == 1
    opens = flags >= 8                 # the group's first visit
    kb = acc.shape[1]

    def add(first, masked):
        # other groups' rows, and whatever lies past the last, reach no
        # product from either side
        keep = (lambda a: jnp.where(mask(), a, jnp.zeros_like(a))) \
            if masked else (lambda a: a)

        def piece(at):                 # the block's rows ``at``
            acc[0, at] = (held if first else acc)[0, at] + lax.dot_general(
                keep(x_ref[:, at]), keep(y_ref[...]),
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        if slab == kb:
            piece(slice(None))
        else:                          # a loop Mosaic does not unroll
            lax.fori_loop(0, kb // slab, lambda i, _: piece(
                pl.ds(pl.multiple_of(i * slab, slab), slab)), None)

    for first in (True, False):
        for masked in (True, False):
            pl.when(pair & (opens == first) & (whole != masked))(
                functools.partial(add, first, masked))

    # no pair at all: the one block the pipeline still writes back keeps
    # what it held
    @pl.when((pl.program_id(axis) == 0) & jnp.logical_not(pair))
    def _():
        acc[...] = held[...]


def tgmm(x, y, sizes, acc, name: str):
    """``acc[g] + x[rows of g].T @ y[rows of g]`` for every group ``g``:
    ``x [R, K]`` and ``y [R, N]`` sorted by group, ``acc [G, K, N]``
    float32, given up to the call (the output takes its place).  An
    accumulator too large for a grid step is walked in blocks of its rows
    (``x``'s columns), the visits once a block: a grid ``(blocks,
    visits)``; a block whose product is too long a body to run fast is
    added :func:`_tgmm_slab` rows a pass of a loop inside the visit."""
    R, K, N, tm = x.shape[0], x.shape[1], y.shape[1], _TGMM_TILE
    table, bounds, V = _plan(sizes, R, tm)
    _count("tgmm", "pallas")
    split = _tgmm_split(K, N, x.dtype.itemsize) or 1
    kb = K // split
    slab = _tgmm_slab(kb, N)
    _say_walk(K, N, split, slab)
    # an index map's arguments: the grid's ids, then table and bounds
    at = (lambda a: (0, a[0], a[-2])) if split == 1 else (
        lambda a: (a[0], a[1], a[-2]))

    def row(width, column):
        def index(*a):
            j, v, t = at(a)
            return t[4 * v + 2], j if column else 0
        return pl.BlockSpec((tm, width), index)

    def group(*a):
        j, v, t = at(a)
        return t[4 * v], j, 0

    held = pl.BlockSpec((1, kb, N), group)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, axis=int(split > 1),
                          slab=slab),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(V,) if split == 1 else (split, V),
            in_specs=[row(kb, True), row(N, False), held], out_specs=held),
        out_shape=_sds(acc.shape, jnp.float32, sizes, x, y, acc),
        input_output_aliases={4: 0},
        compiler_params=_limit(tm * (kb + N) * x.dtype.itemsize
                               + 2 * kb * N * 4, slab * N * 4,
                               1 + int(split > 1)),
        interpret=_pallas.INTERPRET,
        name="hvd_moe_tgmm_" + name,
    )(table, bounds, x, y, acc)


# ----------------------------------------------------------- the combine

def _combine_refusal(rows, out) -> Optional[str]:
    """Which test keeps the Pallas combine off ``out[tok] += rows``; None
    = it runs.  ``rows [R, D]``, ``out [N, D]``."""
    if (why := _pallas.off_chip()):
        return why
    if rows.ndim != 2 or out.ndim != 2 or rows.shape[1] != out.shape[1]:
        return "rows and out must be rank 2 and of one width"
    if rows.dtype != jnp.float32 or out.dtype != jnp.float32:
        return f"dtypes {rows.dtype} and {out.dtype} are not both float32"
    if rows.shape[0] % _COMBINE_CHUNK or out.shape[0] % _COMBINE_TILE:
        return (f"{rows.shape[0]} rows are no multiple of {_COMBINE_CHUNK} "
                f"or {out.shape[0]} tokens none of {_COMBINE_TILE}")
    if rows.shape[1] % _LANES:
        return f"width {rows.shape[1]} is no multiple of {_LANES}"
    if rows.shape[0] * 4 > _COMBINE_SMEM:
        return (f"{rows.shape[0]} token ids are over the {_COMBINE_SMEM} "
                "bytes of scalar memory they may take")
    need = (4 * _COMBINE_TILE + _COMBINE_RING * _COMBINE_CHUNK) \
        * rows.shape[1] * 4
    if need > _STEP_VMEM:
        return (f"a grid step needs {need} bytes of VMEM, over the "
                f"{_STEP_VMEM} a step may hold")
    return None


def _run_edges(tok, sizes, tokens: int):
    """``edge [tiles + 1, G]`` int32, made on the device.  Inside a group
    the tokens ascend, so the rows of group ``g`` that land in tile ``i``
    of ``_COMBINE_TILE`` tokens are one run of consecutive rows, ``edge[i,
    g] <= r < edge[i + 1, g]``.  Rows past ``sizes.sum()`` are in no run.
    The combine's plan and the dispatch's are made from it, and a chunk
    that calls both makes it once (one expression, which XLA keeps
    once)."""
    tt = _COMBINE_TILE
    rows, G, tiles = tok.shape[0], sizes.shape[0], tokens // tt
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    r = jnp.arange(rows, dtype=jnp.int32)
    group = (r[:, None] >= ends[None]).sum(1).astype(jnp.int32)
    # ascending over the rows the sizes cover, past every edge after them
    key = jnp.where(r < ends[-1], group * tokens + tok, G * tokens)
    edges = (jnp.arange(tiles + 1, dtype=jnp.int32)[:, None] * tt
             + jnp.arange(G, dtype=jnp.int32)[None] * tokens).reshape(-1)
    return (key[None] < edges[:, None]).sum(1).astype(jnp.int32).reshape(
        tiles + 1, G)


def _combine_plan(tok, sizes, tokens: int):
    """``(chunks [3 Q], first [tiles + 1])`` int32, made on the device.
    A run (:func:`_run_edges`) is copied in chunks of ``_COMBINE_CHUNK``
    rows from the multiple of 8 at or under its first row.  Chunks are
    listed tile by tile, group by group: for chunk ``q`` the row its copy
    starts at and the first and one past the last of the copied rows that
    are the run's, at ``chunks[3 q : 3 q + 3]``; tile ``i`` takes the
    chunks ``first[i] <= q < first[i + 1]``."""
    ch = _COMBINE_CHUNK
    rows, G, tiles = tok.shape[0], sizes.shape[0], tokens // _COMBINE_TILE
    edge = _run_edges(tok, sizes, tokens)
    lo, hi = edge[:-1].reshape(-1), edge[1:].reshape(-1)
    start = lo // 8 * 8
    n = jnp.where(hi > lo, (hi - start + ch - 1) // ch, 0)
    before = jnp.cumsum(n) - n
    Q = rows // ch + 2 * tiles * G                # the most any sizes need
    q = jnp.arange(Q, dtype=jnp.int32)
    # the run a chunk is of: the last that starts at or before it (runs
    # without a row start where the next one does)
    run = (q[:, None] >= before[None]).sum(1) - 1
    start, lo, hi, base = jnp.stack([start, lo, hi, before], axis=1)[run].T
    s0 = start + (q - base) * ch
    s = jnp.minimum(s0, rows - ch)
    chunks = jnp.stack([s, jnp.maximum(lo, s0) - s,
                        jnp.minimum(hi, s0 + ch) - s], axis=1)
    first = jnp.concatenate([before[::G], (before[-1] + n[-1])[None]])
    return chunks.reshape(-1).astype(jnp.int32), first.astype(jnp.int32)


def _combine_kernel(tok, chunks, first, fresh, rows, held, out, buf, sem, *,
                    tt, ch, ring, unroll):
    i = pl.program_id(0)
    total = first[pl.num_programs(0)]

    def copy(q):
        slot = q % ring
        return pltpu.make_async_copy(
            rows.at[pl.ds(pl.multiple_of(chunks[3 * q], 8), ch)],
            buf.at[slot], sem.at[slot])

    def ahead(q):
        # the ring runs on into the next tile's chunks
        pl.when(q < total)(lambda: copy(q).start())

    @pl.when(i == 0)
    def _():
        for q in range(ring - 1):
            ahead(q)

    @pl.when(fresh[0] == 0)
    def _():
        out[...] = held[...]

    @pl.when(fresh[0] != 0)            # nothing held: its tiles are not read
    def _():
        out[...] = jnp.zeros_like(out)

    t0 = i * tt

    def chunk(q, _):
        ahead(q + ring - 1)
        copy(q).wait()
        slot, s = q % ring, chunks[3 * q]
        lo, hi = chunks[3 * q + 1], chunks[3 * q + 2]

        def add(j, n):
            # a run's tokens are distinct: n rows' loads before their
            # stores, so that none waits for another's
            ts = [tok[s + j + u] - t0 for u in range(n)]
            sums = [out[pl.ds(t, 1), :] + buf[slot, pl.ds(j + u, 1), :]
                    for u, t in enumerate(ts)]
            for t, y in zip(ts, sums):
                out[pl.ds(t, 1), :] = y
            return 0

        many = (hi - lo) // unroll
        lax.fori_loop(0, many, lambda m, _: add(lo + m * unroll, unroll), 0)
        return lax.fori_loop(lo + many * unroll, hi, lambda j, _: add(j, 1), 0)

    lax.fori_loop(first[i], first[i + 1], chunk, 0)


def combine(rows, tok, sizes, out, name: str, fresh=False):
    """``out`` with ``rows[r]`` added to ``out[tok[r]]`` for the rows the
    sizes cover (rows past ``sizes.sum()`` add nothing, whatever they
    hold): ``rows [R, D]`` float32 sorted by group, ``sizes [G]`` rows
    each, ``tok [R]`` int32 the token of each row, ascending inside a
    group (a token meets a group once); ``out [N, D]`` float32, given up
    to the call; ``fresh`` (a bool, traced or not): the caller says
    ``out`` holds zeros, and the kernel does not read it.  The kernel walks
    tiles of tokens: a tile stays in VMEM while the runs of rows that
    land in it are copied in from HBM, a ring of copies ahead, and each
    row is added to its token, in the rows' order; the tile is written
    once.  Elsewhere (:func:`_combine_refusal`) XLA's scatter-add."""
    reason = _combine_refusal(rows, out)
    if not _verdict("moe_combine", reason, rows, out):
        _count("combine", "xla")
        live = (jnp.arange(rows.shape[0]) < sizes.sum())[:, None]
        return out.at[tok].add(jnp.where(live, rows, 0.0))
    _count("combine", "pallas")
    (R, D), N = rows.shape, out.shape[0]
    tt, ch, ring = _COMBINE_TILE, _COMBINE_CHUNK, _COMBINE_RING
    chunks, first = _combine_plan(tok, sizes, N)
    fresh = jnp.asarray(fresh, jnp.int32).reshape(1)
    tile = pl.BlockSpec((tt, D), lambda i, *_: (i, 0))
    # a fresh target's first tile is the only one fetched
    held = pl.BlockSpec((tt, D), lambda i, t, c, f, fresh: (
        jnp.where(fresh[0] != 0, 0, i), 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tt=tt, ch=ch, ring=ring,
                          unroll=_COMBINE_UNROLL),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(N // tt,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), held],
            out_specs=tile,
            scratch_shapes=[pltpu.VMEM((ring, ch, D), jnp.float32),
                            pltpu.SemaphoreType.DMA((ring,))]),
        out_shape=_sds((N, D), jnp.float32, rows, tok, sizes, out),
        input_output_aliases={5: 0},
        compiler_params=_limit(2 * tt * D * 4, ring * ch * D * 4),
        interpret=_pallas.INTERPRET,
        name="hvd_moe_combine_" + name,
    )(tok.astype(jnp.int32), chunks, first, fresh, rows, out)


# ---------------------------------------------------------- the dispatch

def _dispatch_vmem(table, groups: int):
    """``(block, scratch)`` bytes of VMEM a dispatch's grid step holds: a
    tile of the table as it comes, and the words of that tile, of the
    blocks staged and of the copies in flight."""
    D, tt, blk = table.shape[1], _COMBINE_TILE, _DISPATCH_BLOCK
    return (tt * D * table.dtype.itemsize,
            (tt + (2 * groups + 1 + _DISPATCH_RING) * blk) * D * 2)


def _dispatch_refusal(table, tok, sizes, dtype) -> Optional[str]:
    """Which test keeps the Pallas dispatch off ``table[tok]``; None = it
    runs.  ``table [N, D]``, ``tok [R]``, ``sizes [G]``, ``dtype`` the
    rows'."""
    if (why := _pallas.off_chip()):
        return why
    if table.ndim != 2 or tok.ndim != 1:
        return "the table must be rank 2 and the token ids rank 1"
    if (why := _pallas.dtype_refusal(table.dtype)):
        return why
    if dtype != jnp.bfloat16:
        return f"rows of {jnp.dtype(dtype)} are not bfloat16"
    if tok.shape[0] % _DISPATCH_BLOCK or table.shape[0] % _COMBINE_TILE:
        return (f"{tok.shape[0]} rows are no multiple of {_DISPATCH_BLOCK} "
                f"or {table.shape[0]} tokens none of {_COMBINE_TILE}")
    if table.shape[1] % (2 * _LANES):
        return f"width {table.shape[1]} is no multiple of {2 * _LANES}"
    if tok.shape[0] * 4 > _COMBINE_SMEM:
        return (f"{tok.shape[0]} token ids are over the {_COMBINE_SMEM} "
                "bytes of scalar memory they may take")
    block, scratch = _dispatch_vmem(table, sizes.shape[0])
    if 2 * block + scratch > _STEP_VMEM:
        return (f"a grid step needs {2 * block + scratch} bytes of VMEM, "
                f"over the {_STEP_VMEM} a step may hold")
    return None


def _dispatch_plan(tok, sizes, tokens: int):
    """``(edge [(tiles + 1) G], blocks [3 (G + 1)])`` int32, made on the
    device: the runs' edges (:func:`_run_edges`), and for border ``g``
    (where group ``g - 1`` ends and ``g`` starts; border ``G`` is the end
    of the rows the sizes cover) at ``blocks[3 g : 3 g + 3]`` the row it
    lies at, the first row of the block of ``_DISPATCH_BLOCK`` rows that
    holds that row, and the first border of the same block: a block that
    holds a border is staged once, under that border's number, whatever
    groups its rows are of."""
    edge = _run_edges(tok, sizes, tokens)
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    at = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    block = at // _DISPATCH_BLOCK * _DISPATCH_BLOCK
    first = (block[:, None] > block[None]).sum(1)   # blocks ascend
    return (edge.reshape(-1), jnp.stack(
        [at, block, first], axis=1).reshape(-1).astype(jnp.int32))


def _dispatch_kernel(tok, edge, blocks, table, rows, pairs, stage, buf, sem,
                     sent, *, tt, G, blk, ring, unroll):
    half = table.shape[1] // 2

    def bits(x):               # rounded to bfloat16, in a word's high half
        return lax.bitcast_convert_type(
            x.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32)

    # a row of a packed dtype cannot be read alone, so rows move as words
    # of 32 bits: column c over column c + D / 2, half the sublanes (and
    # half the VMEM) that float32 rows would take
    pairs[...] = bits(table[:, :half]) | (bits(table[:, half:]) >> 16)
    i = pl.program_id(0)
    t0 = i * tt

    @pl.when(i == 0)
    def _():
        sent[0] = 0

    def copy(slot, row):
        return pltpu.make_async_copy(
            buf.at[slot], rows.at[pl.ds(pl.multiple_of(row, blk), blk)],
            sem.at[slot])

    def send(held, row):
        """The staged block ``held`` to the rows from ``row`` on."""
        slot = sent[0] % ring
        pl.when(sent[0] >= ring)(lambda: copy(slot, row).wait())
        words = stage[held]
        value = lambda w: lax.bitcast_convert_type(w, jnp.float32).astype(
            buf.dtype)
        buf[slot, :, :half] = value(words & jnp.uint32(0xFFFF0000))
        buf[slot, :, half:] = value(words << 16)
        copy(slot, row).start()
        sent[0] = sent[0] + 1

    def run(g, _):
        lo, hi = edge[i * G + g], edge[(i + 1) * G + g]
        head, tail = blocks[3 * g + 1], blocks[3 * g + 4]

        def block(b, _):
            row = b * blk
            # a block that holds a border waits for the other groups' rows
            held = jnp.where(row == head, blocks[3 * g + 2], jnp.where(
                row == tail, blocks[3 * g + 5], G + 1 + g))
            s, e = jnp.maximum(lo, row), jnp.minimum(hi, row + blk)

            def move(r, n):
                # loads before stores, so that none waits for another's
                ts = [tok[r + u] - t0 for u in range(n)]
                got = [pairs[pl.ds(t, 1), :] for t in ts]
                for u, x in enumerate(got):
                    stage[held, pl.ds(r + u - row, 1), :] = x
                return 0

            many = (e - s) // unroll
            lax.fori_loop(0, many, lambda m, _: move(s + m * unroll, unroll),
                          0)
            lax.fori_loop(s + many * unroll, e, lambda r, _: move(r, 1), 0)
            pl.when((e == row + blk) & (held > G))(lambda: send(held, row))
            return 0

        return lax.fori_loop(lo // blk, jnp.where(
            hi > lo, (hi + blk - 1) // blk, lo // blk), block, 0)

    lax.fori_loop(0, G, run, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        def border(g, _):
            row = blocks[3 * g + 1]
            # once a block, and not the one past the rows
            pl.when((blocks[3 * g + 2] == g) & (row < blocks[3 * G]))(
                lambda: send(g, row))
            return 0

        lax.fori_loop(0, G + 1, border, 0)
        for slot in range(ring):
            pl.when(slot < sent[0])(lambda slot=slot: copy(slot, 0).wait())


def dispatch(table, tok, sizes, dtype, name: str):
    """``rows [R, D]`` of ``dtype`` (bfloat16) with ``rows[r] =
    table[tok[r]]`` for the rows the sizes cover, the combine's walk
    turned around: ``table [N, D]`` bfloat16 or float32 (rounded on the
    way), ``tok [R]`` int32 ascending inside a group, ``sizes [G]`` rows
    each.  Rows past ``sizes.sum()`` are not written and hold whatever the
    buffer held: the grouped products and the combine read none of them
    into a result.  The kernel walks tiles of tokens: a tile comes through
    VMEM once, each row of a run is moved to its place in a block of
    ``_DISPATCH_BLOCK`` rows staged a group, and a block leaves for HBM
    when its last row is in, a ring of copies in flight; the blocks that
    hold rows of two groups or more leave last.  Elsewhere
    (:func:`_dispatch_refusal`) XLA's gather, in the table's dtype."""
    reason = _dispatch_refusal(table, tok, sizes, dtype)
    if not _verdict("moe_dispatch", reason, table, tok):
        _count("gather", "xla")
        return table[tok]
    _count("gather", "pallas")
    (N, D), R, G = table.shape, tok.shape[0], sizes.shape[0]
    tt, blk, ring = _COMBINE_TILE, _DISPATCH_BLOCK, _DISPATCH_RING
    edge, blocks = _dispatch_plan(tok, sizes, N)
    return pl.pallas_call(
        functools.partial(_dispatch_kernel, tt=tt, G=G, blk=blk, ring=ring,
                          unroll=_DISPATCH_UNROLL),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // tt,),
            in_specs=[pl.BlockSpec((tt, D), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((tt, D // 2), jnp.uint32),
                pltpu.VMEM((2 * G + 1, blk, D // 2), jnp.uint32),
                pltpu.VMEM((ring, blk, D), dtype),
                pltpu.SemaphoreType.DMA((ring,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=_sds((R, D), dtype, table, tok, sizes),
        compiler_params=_limit(*_dispatch_vmem(table, G)),
        interpret=_pallas.INTERPRET,
        name="hvd_moe_dispatch_" + name,
    )(tok.astype(jnp.int32), edge, blocks, table)
