"""Fused flash-attention Pallas kernels for TPU.

The hot op of the flagship model (SURVEY.md §6: the rebuild's headline
benchmark is transformer training throughput).  The reference keeps its
hot loops in hand-written CUDA (`horovod/common/ops/cuda/cuda_kernels.cu`
per SURVEY §2.1); the TPU-native equivalent is a Pallas kernel: the
online-softmax recurrence runs in VMEM so the ``[T, T]`` score matrix
never touches HBM, q/k tiles feed the MXU directly, and the backward
pass recomputes score tiles from the saved logsumexp instead of storing
them.

Public layout contract (matches :mod:`horovod_tpu.parallel.ring_attention`):
  q: ``[B, T, H, D]``   k/v: ``[B, Tk, Hkv, D]`` with ``Hkv | H`` (GQA —
  query head h reads kv head ``h // (H//Hkv)``).  The values may
  have a width of their own, ``v [B, Tk, Hkv, Dv]`` (differential
  attention's 128 beside 64-wide queries and keys): such a call takes the
  masked path and ``out`` is ``Dv`` wide; at ``Dv = D`` every kernel is
  built as it was.

The logsumexp residual is stored blocked as ``[B, H, nq, bq]`` — the
(nq, bq) trailing dims are full blocks, which satisfies Mosaic's tiling
rule without the 128-lane padding the naive ``[B, H, T]`` layout needs.

Two paths, chosen from the shapes and the mask (:func:`_pack`; no knob):

* **packed** — no ``mask`` and the whole sequence is one block (BERT's
  128 tokens; any ``T <= 512``): a grid step costs about 0.4 us on a v5e
  whatever it does, and one (batch, head) pair of a short sequence is
  less work than that.  So a step takes a pack of ``bb`` batch rows x
  ``hb`` heads, as large as ``_VMEM_BUDGET`` holds.  The kernels read the
  caller's layout as ``[B, T, H*D]`` (a free reshape: no transposes
  around the call), cut on 128-lane tiles; with ``D = 64`` a tile holds
  two heads and each head's products run on the whole tile with the
  other head's lanes zeroed, which costs the MXU nothing (it is 128 wide
  either way) and keeps every load and store aligned.  With all of S in
  one block the backward is one kernel, ``hvd_flash_bwd``: S, P, dP and
  dS are computed once and dq, dk, dv written from them.  Same products
  in the same dtypes as the masked kernels.

* **masked** — everything else: several blocks of ``_BLOCK`` positions,
  or one that does not pack (``hvd_flash_fwd`` / ``hvd_flash_dq`` /
  ``hvd_flash_dkv``), under a mask that is data: for every query
  row two half-open ranges of key positions, ``[T, 4]`` or ``[B, T, 4]``
  int32 ``(lo1, hi1, lo2, hi2)``; a key is seen when it lies in either.
  Causal, sliding-window, packed-document and block-diffusion masks are
  all such ranges (:func:`causal_ranges`, :func:`window_ranges`); no mode
  per model.  The caller gives them as ``mask=``; without one,
  ``causal=True`` is :func:`causal_ranges` and ``causal=False`` is
  :func:`full_ranges`, made where the call is built.  :func:`tile_classes`
  sorts the (query tile, key tile) pairs into dead (no live pair:
  skipped, no load and no product), full (every pair live: no mask
  applied) and mixed (cut by the mask).  **A mixed tile is walked by its
  live sub-tiles** of ``_SUB`` = 256 positions a side, not visited whole:
  where the classes are made (:func:`_mask_plan`) the same
  :func:`tile_classes` classes every mixed tile's sub-tiles
  (:func:`_sub_words`; numpy where the mask is numpy, traced where it is
  traced) and the kernels get a word of bits a tile in SMEM beside the
  tables they had.  ``hvd_flash_dkv`` visits a mixed pair's sub-tiles
  that are not dead one by one, ``dk`` and ``dv`` accumulating by the
  key sub-tile's rows; ``hvd_flash_fwd`` and ``hvd_flash_dq`` take each
  band of 256 query rows on the span from its first live key sub-tile to
  its last in one visit, so that what a visit costs beside its products
  (the rows' maxima, sums and accumulators read and written) is paid once
  a band, as a tile taken whole pays it.  Every such visit is masked from
  the ranges spread once a step (the mask is exact to the pair, so a full
  sub-tile inside a span loses nothing but the mask's few percent); a
  row that sees no key in a visit keeps its maximum, sum and accumulator
  as a mixed tile's fully masked rows always did.  The diagonal tile of a
  causal mask, and a tile of a window as wide as a tile, so cost three
  quarters of a tile, the 24 mixed tiles of the block-diffusion mask of 2
  x 4,096 positions two thirds.  A call whose mask cuts no tile (every
  tile full or dead, the mask known where the call is built), or whose
  tile is no larger than a sub-tile, builds no such table and the loops
  as they were.  The width is one number for every call, chosen on the
  chip between 256 and 128 (the runs stand beside ``_SUB``; 128 visits
  less and costs more in every call measured).  ``hvd_flash_fwd`` and
  ``hvd_flash_dq`` walk each query tile's live key tiles from a table in
  SMEM.  A forward grid step takes that query tile of several query
  heads of one GQA group (they share the resident k and v), unrolled, so
  that one head's softmax is scheduled under another's products: as many
  as :func:`_fwd_heads` finds room for from the shapes (a divisor of the
  group, under ``_MASKED_STEP_VMEM``; 4 of 8 at 512 x 512 tiles and
  ``head_dim`` 128; one where the group is one head or two do not fit;
  no knob).  Its running maxima, sums and accumulators live in VMEM
  scratch, the maxima alike in every lane and the sums as 128 partial
  sums a row that meet after the last tile, and the rows' ranges are
  spread over the lanes once a step, so a tile's only cross-lane work is
  its row maximum.  A ``hvd_flash_dq`` grid step is laid out the same
  way: the query tile of as many heads of a group as :func:`_dq_heads`
  finds room for (4 of 8 at the same shape; its step also holds ``do``
  and ``dq``, so never more than the forward's), unrolled
  inside a key tile's step so that k and v tiles are loaded once for all
  of them; ``dq`` accumulates in float32 VMEM scratch ``[hb, bq, D]`` and
  is cast out after the last tile; the heads' rows of ``lse`` and of
  ``delta`` become columns alike in every lane by one transpose each and
  the rows' ranges are spread over the lanes, once a step, and a masked
  visit's mask is made once for all the step's heads, in the forward
  too.
  ``hvd_flash_dkv`` has one grid step per live (key
  tile, query tile) pair and takes the query tiles of its GQA group one
  at a time, so it holds ``g x bq x D`` of ``q`` and ``do``, not
  ``g x T x D``: 8 query heads a kv head at 8,192 positions run.  It
  forms its tile keys by queries, ``S^T = K Q^T [bk, bq]``: a query's
  ``lse``, ``delta`` and ranges (handed over as ``[Bm, 4, T]``, a row a
  bound) lie along the lanes as they are stored and go down the sublanes
  for nothing, one mask serves the pair's ``g`` heads, and ``dv = P^T do``
  and ``dk = dS^T q`` are plain ``[bk, bq] x [bq, D]`` products.  The
  backward of a call without a second pair is two kernels and 7 products
  a live pair of a head (S and dP made again in each), bf16 operands,
  float32 accumulation; a call with one takes one kernel ("The one
  backward", below).  Every
  query row has to see at least one key.  Two layouts, chosen from the
  widths where the call is built (no knob): where ``D`` and ``Dv`` are
  whole lane tiles (multiples of 128) the kernels read ``q``, ``k``,
  ``v``, ``do`` and write ``out``, ``dq``, ``dk``, ``dv`` as the caller
  holds them, ``[B, T, H*D]`` (a free reshape), a head being ``D`` lanes
  at a static, aligned offset of its block (``rows``: nothing is
  transposed around the call, forward or backward); a 64-wide head is
  half a tile and cannot be a block of ``[B, T, H*64]``, so such a call
  is transposed to ``[B, H, T, D]`` around the kernels (``heads``).  One
  set of kernel bodies; the block specs and :func:`_head` differ.

**A second pair** (``flash_attention(pair=(q2, k2))``; the masked path
alone).  Multi-head latent attention scores a head as ``q_nope . k_nope +
q_pe . k_pe``: the first product 128 wide with a key head a query head,
the second 64 wide with ONE rotary key for every head.  Joined into one
192-wide key the rotary key is copied a head into HBM (67 MB a layer at 32
heads and 16,384 positions, again under remat, its cotangent summed over
the heads on the way back), 192 is no whole lane tile, so the call takes
the ``heads`` route and q, k, v, out and their gradients are transposed
around the kernels, and at 16,384 keys the resident ``Tk x (192 + 128) x
2`` bytes are ``_VMEM_BUDGET`` to the byte.  So the kernels take the second
pair as it is: ``q2 [B, T, H, D2]``, ``k2 [B, Tk, H2, D2]`` with ``H2 |
Hkv`` (a grouping of its own: one key head of the pair serves ``H / H2``
query heads), and add ``q2 k2^T`` to each score tile before the mask and
the softmax; the backward accumulates and writes ``dq2`` beside ``dq``
and, its grid step being a kv head, writes that kv head's part
of ``dk2`` in float32 and the caller adds the ``Hkv / H2`` parts of the kv
heads that share a key head of the pair (one XLA reduction of ``[B, Tk,
Hkv, D2]`` float32: 268 MB read a layer at the shape above, a third of a
millisecond beside kernels of tens).  ``q``, ``k``, ``v``, ``out`` and
their cotangents stay the caller's ``[B, T, H*D]`` rows (``D`` and ``Dv``
have to be whole lane tiles: nothing is transposed), and ``k2`` is never
copied a head.  **The pair's heads are padded to whole lane tiles**, zeros
after their ``D2`` columns (``q2 [B, T, H * 128]``, ``k2 [B, Tk, H2 *
128]`` at ``D2`` 64), where the call is built, so that a head of the pair
is a block at an aligned lane offset exactly as a head of ``q`` is and the
kernel bodies gain three lines each.  The choice against the packed path's
two heads a lane tile: that needs the other head's lanes zeroed a step (a
select over ``[bq, 128]`` a head a step, and ``k2`` doubled to 128 lanes
all the same), for nothing on the MXU, which contracts 128 lanes whether 64
of them are zeros or another head's; what padding costs is HBM bytes, ``q2``
and ``dq2`` at twice their 64 columns: 4 x 67 MB a layer forward and
backward, about a third of a millisecond at 819 GB/s beside about 70 ms of
kernels, and 4 MB of VMEM for ``k2`` resident at 16,384 keys (a step of one
head then holds 13 MB of blocks; :func:`_pair_refusal` refuses a shape
whose step does not fit ``_MASKED_STEP_VMEM``, and the caller joins the
pair instead: ``ring_attention.local_attention``).  A call without a pair
builds every kernel as it was; one with it counts as path ``paired``.

**The one backward** (``hvd_flash_dqkv``; a call with a second pair).
``hvd_flash_dq`` and ``hvd_flash_dkv`` each make ``S`` (two products with a
pair), ``dP`` (one) and ``exp`` of the tile, and each load q, k, v, ``do``,
``lse`` and ``delta``: 5 + 6 lane-tile products a live pair of a head where
8 are needed.  What stops one kernel is where ``dq`` lives while the kernel
walks key tiles: all ``T`` rows of a kv head's group in float32, ``g x T x
(D + D2) x 4`` bytes.  Latent attention's group is one head (a key and
value head a query head): 16.8 MB at 16,384 rows of 128 + the pair's 128,
which VMEM holds.  So such a call's backward is ``hvd_flash_dkv``'s kernel
body, grid ``(B, Hkv, P)`` over the live pairs in key-tile order and tiles
keys by queries, given a group of ``dq`` refs as ``pair=`` is a group
(``_mdkv_kernel(dq=)``): a visit's ``dS^T [bk, bq]`` is turned once (bf16,
on the XLU beside the MXU's eight products) and ``dq[query rows] += dS k``,
``dq2[query rows] += dS k2`` accumulate in ``[g, T, D]`` and ``[g, T, D2]``
float32 scratch, zeroed at the kv head's first grid step and cast out at
its last into ``dq`` / ``dq2`` blocks that are the group's whole ``[1, T, g
x D]`` columns of the caller's rows, at an index that stands still along
``P``, so the pipeline writes them to HBM once a kv head.  The same
products in the same dtypes and the same sub-tile walk; ``dk``, ``dv`` and
``dk2`` are ``hvd_flash_dkv``'s to the bit, ``dq`` and ``dq2`` differ from
``hvd_flash_dq``'s by float32's order of summation alone.  **Which calls
take it** (:func:`_one_backward`; from the shapes, no knob): those with a
second pair whose step, reckoned by :func:`_dqkv_step_bytes` (blocks twice,
scratch, tiles: 41.0 MB at the shape above, of which the accumulators 16.8
and the two out blocks twice 16.8), fits ``_DQKV_STEP_VMEM`` (48 MiB); the
call declares that sum and 4 MiB to Mosaic, not a blanket limit.  A paired
call that does not fit (a longer ``T``, a larger group under one rotary
key) keeps ``hvd_flash_dq`` + ``hvd_flash_dkv`` with ``pair=``.  Calls
without a pair keep their two kernels whatever their bytes: at the other
models' shapes a kv head's ``dq`` is 8 to 17 MB (8 heads x 4,096 x 128) or
67 MB (8 x 16,384 x 128), and the benchmark's yardstick for them counts the
backward at 7 products; taking the one backward to those that fit is
ROADMAP.md D3.

Residuals are named.  Both paths' ``custom_vjp`` keep ``(q, k, v, out,
lse)`` for the backward kernels, and the forward rules pass ``out`` and
``lse`` through ``jax.ad_checkpoint.checkpoint_name`` as ``OUT_NAME``
(``hvd_flash_out``) and ``LSE_NAME`` (``hvd_flash_lse``), in the kernels'
own layout (masked: ``[B, T, H*Dv]``, what the caller's next product
reads, or on the ``heads`` route ``[B, H, T, Dv]``, and ``[B, H, nq,
bq]``; packed: ``[B, T, H*D]`` and ``[B, H, 1, T]``), ``out`` in the
operands' dtype and ``lse`` in float32 as the kernel wrote them.  A
caller that rematerializes around the op and saves these names
(``jax.checkpoint_policies.save_only_these_names``, as
``models/llama.py::remat_policy`` does for the decoder trunk's layer
stack and for ``models/bert.py``'s encoder) gets the backward's
residuals from memory, ``B x T x H x D x itemsize + B x H x T x 4``
bytes a call; one that does not reruns the forward kernel in its
backward pass to make them again, and the names change nothing in its
program.

``hvd_flash_kernel_total{kernel, path, layout}`` counts the kernels
built, once per traced call site, so a program says which path (``packed``,
``masked``, or ``paired``: the masked kernels built with a second pair) and
which layout (``rows`` or ``heads``; packed calls are ``rows``) its shapes
took, and which backward a paired call took (``kernel`` ``dqkv``, or ``dq``
and ``dkv``);
``hvd_flash_tiles_total{kernel, state}`` counts a masked call's tiles
(``live`` = full, ``masked`` = mixed, ``skipped`` = dead) where the call
is built, when the mask is known there (a numpy array), and
``hvd_flash_subtiles_total{kernel, state}`` the sub-tiles of its mixed
tiles in the same three states: no series where no tile is walked by
sub-tiles.

Falls back cleanly: :func:`supported` gates on platform/shape so callers
(e.g. ``local_attention``) can pick the XLA blockwise path on CPU meshes
or odd shapes — on a TPU backend each refused shape is logged once, at
WARNING, with the test that refused it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from .. import metrics as _metrics
from . import _pallas
from ._pallas import LANES as _LANES, sds as _sds, verdict as _verdict

NEG_INF = -1e30
_VMEM_BUDGET = 10 * 1024 * 1024  # soft cap for resident kernel buffers
_BLOCK = 512  # query and key positions a block
# positions a side of the sub-tiles by which a tile that the mask cuts is
# walked (module docstring, "a mixed tile").  Chosen on a v5e between 256
# and 128, a lane tile (PERF.md, PR 44; the kernels alone, ms a call of
# fwd + dq + dkv, a mixed tile whole / by 256 / by 128): the SDAR call
# (block diffusion, 32 heads over 4 at head_dim 128) 17.66 / 16.46 /
# 17.04, a Phi window call (20 heads over 10 at 64, values 128) 2.77 /
# 2.60 / 3.41, a Phi causal call 9.90 / 9.80 / 10.23, a causal call of 8
# heads over 1 at 128 3.64 / 3.58 / 3.65: 128 visits less (0.85 of 256's
# area under block diffusion) and pays more for it, a visit's rows of
# statistics and accumulators being read and written whatever its width.
_SUB = 256
# what one grid step of the masked forward or of dq may hold: a quarter of
# a v5e core's 128 MiB of VMEM, half of the smallest there is (v7x: 64 MiB)
_MASKED_STEP_VMEM = 32 * 1024 * 1024
# what one grid step of the one backward of a call with a second pair may
# hold, the kv head's ``dq`` in float32 among it (``_dqkv_step_bytes``):
# under half of a v5e core's VMEM and three quarters of a v7x core's
_DQKV_STEP_VMEM = 48 * 1024 * 1024
# checkpoint names of the forward kernels' two results ("Residuals are
# named", above)
OUT_NAME = "hvd_flash_out"
LSE_NAME = "hvd_flash_lse"

_count = _pallas.kernel_counter(
    "hvd_flash_kernel_total",
    "Flash-attention Pallas kernels built, one per traced call site; "
    "path is packed, masked (tiled stopped occurring: several blocks "
    "without mask= count as masked) or paired (the masked kernels with a "
    "second query/key pair; its backward is kernel dqkv, one kernel, where "
    "a kv head's dq fits VMEM, else dq and dkv); layout is rows where the "
    "kernels read and write the caller's [B, T, H*D], heads where the call "
    "is transposed to [B, H, T, D] around them",
    labels=("kernel", "path", "layout"))


_count_tile = _pallas.kernel_counter(
    "hvd_flash_tiles_total",
    "Tiles of masked flash-attention calls by class, counted where the "
    "call is built from a mask known there",
    labels=("kernel", "state"))
_count_subtile = _pallas.kernel_counter(
    "hvd_flash_subtiles_total",
    "Sub-tiles (_SUB positions a side) of the mixed tiles of masked "
    "flash-attention calls by class, counted where the call is built from "
    "a mask known there; no series where no tile is walked by sub-tiles",
    labels=("kernel", "state"))
_TILE_STATES = ("skipped", "masked", "live")     # class 0, 1, 2


def _count_tiles(kernel: str, classes, sub=None) -> None:
    """``classes``: the call's tile classes when known at build time;
    ``sub``: its mixed tiles' sub-tile classes (:func:`_sub_words`)."""
    if not (_metrics.ACTIVE and isinstance(classes, np.ndarray)):
        return
    for c, state in enumerate(_TILE_STATES):
        _count_tile(kernel, state, n=int((classes == c).sum()))
    if sub is not None:
        codes = sub_codes(sub.words[classes == 1], math.prod(sub.grid))
        for c, state in enumerate(_TILE_STATES):
            _count_subtile(kernel, state, n=int((codes == c).sum()))


def _block_sizes(t_q: int, t_kv: int):
    """Query/key block sizes of the kernel grid: ``_BLOCK``, or the whole
    of a shorter sequence.  :func:`supported` refuses results that do not
    divide the lengths or are no multiples of 128."""
    return min(_BLOCK, t_q), min(_BLOCK, t_kv)


def _lane_tile(D):
    """(lanes, heads) of one lane tile of the packed layout ``[B, T, H*D]``:
    128 lanes holding ``128 // D`` heads, or one head of ``D`` lanes."""
    lanes = max(D, _LANES)
    return lanes, lanes // D


def _packed_resident(bb, hb, g, T, Tk, D, itemsize):
    """VMEM bytes a packed grid step holds, counted for the backward
    kernel, the larger: its nine blocks (q, do, dq of ``hb`` heads, k, v,
    dk, dv of ``hb // g``, two rows of statistics), each double-buffered
    by the pipeline, and six fp32 ``[T, Tk]`` temporaries of the one pair
    in flight."""
    blocks = (itemsize * D * (3 * T * hb + 4 * Tk * (hb // g))
              + 2 * hb * 8 * T * 4)         # a (1, T) fp32 row pads to 8
    return 2 * bb * blocks + 6 * T * Tk * 4


def _pack(B, H, Hkv, T, Tk, D, itemsize):
    """``(bb, hb)``: batch rows and query heads one grid step takes.

    ``(1, 1)`` is the masked path.  A sequence that is one block
    (``nq == nkv == 1``) is packed: the largest ``hb`` dividing ``H``,
    then the largest ``bb`` dividing ``B``, that
    :func:`_packed_resident` keeps under ``_VMEM_BUDGET``.  The packed
    kernels cut ``[B, T, H*D]`` on 128-lane tiles, so ``hb`` holds whole
    tiles (two heads at ``D = 64``) and whole GQA groups; where a head
    neither fills nor evenly divides 128 lanes, or a tile of several
    heads would meet a GQA group, the shape stays on the masked path."""
    g = H // Hkv
    lanes, per_tile = _lane_tile(D)
    if ((T, Tk) != _block_sizes(T, Tk) or lanes % _LANES
            or per_tile * D != lanes or (per_tile > 1 and g > 1)):
        return 1, 1

    def fits(bb, hb):
        return _packed_resident(bb, hb, g, T, Tk, D,
                                itemsize) <= _VMEM_BUDGET

    for hb in range(H, 0, -1):
        if H % hb == 0 and hb % (per_tile * g) == 0 and fits(1, hb):
            return max(b for b in range(1, B + 1)
                       if B % b == 0 and fits(b, hb)), hb
    return 1, 1


def _row_widths(D, Dv):
    """``(D, Dv)`` where the masked kernels read the caller's ``[B, T,
    H*D]``: heads that are whole lane tiles are blocks of it at aligned
    lane offsets.  None where a head is half a tile (64): that call is
    transposed to ``[B, H, T, D]`` around the kernels (the packed path's
    zeroed lanes need query and key heads at one lane offset, which the
    pairs of a GQA group are not)."""
    return (D, Dv) if D % _LANES == 0 and Dv % _LANES == 0 else None


def _pair_refusal(q, k, v, pair) -> Optional[str]:
    """Which test keeps a second query/key pair off the masked kernels of
    a call that runs without it; None = they take it."""
    q2, k2 = pair
    B, T, H, D = q.shape
    Tk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    if (q2.ndim != 4 or k2.ndim != 4 or q2.shape[:3] != q.shape[:3]
            or k2.shape[:2] != k.shape[:2] or k2.shape[3] != q2.shape[3]
            or q2.dtype != q.dtype or k2.dtype != q.dtype):
        return ("the second pair must be q2 [B, T, H, D2], k2 [B, Tk, H2, "
                "D2] in q's dtype")
    H2, D2 = k2.shape[2], q2.shape[3]
    if Hkv % H2:
        return f"the second pair's {H2} key heads do not divide the {Hkv}"
    if _row_widths(D, Dv) is None:
        return (f"a second pair rides the caller's rows: head_dim {D} and "
                f"the values' {Dv} must be whole lane tiles")
    if D2 % 64 or D2 > 256:
        return (f"the second pair's width {D2} is not a multiple of 64 up "
                "to 256")
    bq, bk = _block_sizes(T, Tk)
    step = _dq_step_bytes(1, bq, bk, D, T // bq, Tk, q.dtype.itemsize, Dv,
                          _padded(D2))
    if 2 * step[0] + step[1] + step[2] > _MASKED_STEP_VMEM:
        return (f"one head's step with the second pair's keys resident needs "
                f"{2 * step[0] + step[1] + step[2]} bytes of VMEM, over "
                f"{_MASKED_STEP_VMEM}")
    return None


def _padded(d):
    """``d`` lanes as whole lane tiles."""
    return -(-d // _LANES) * _LANES


def _refusal(q, k, v, pair=None) -> Optional[str]:
    """Which test keeps the Pallas kernel off this call; None = it runs.
    ``pair``: a second query/key pair (:func:`flash_attention`)."""
    if (why := _pallas.off_chip()):
        return why
    if q.ndim != 4 or k.ndim != 4:
        return "q and k must be rank 4"
    B, T, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if (v.ndim != 4 or v.shape[:3] != k.shape[:3]
            or q.shape[0] != k.shape[0] or k.shape[3] != D):
        return ("v must match k but in its width, and k must match q in "
                "batch and head_dim")
    Dv = v.shape[3]
    if H % Hkv:
        return f"kv heads {Hkv} do not divide query heads {H}"
    if D % 64 or D > 256 or Dv % 64 or Dv > 256:
        return (f"head_dim {D} or the values' {Dv} is not a multiple of "
                "64 up to 256")
    bq, bk = _block_sizes(T, Tk)
    if T % bq or Tk % bk or bq % 128 or bk % 128:
        return (f"blocks ({bq}, {bk}) must divide the sequence lengths "
                f"({T}, {Tk}) and be multiples of 128")
    if (why := _pallas.dtype_refusal(q.dtype)):
        return why
    g = H // Hkv
    # fwd holds k+v [Tk, D + Dv]; dkv holds q+do of one query tile of the
    # group
    resident = max(Tk, g * bq) * (D + Dv) * q.dtype.itemsize
    if resident > _VMEM_BUDGET:
        return (f"resident buffers need {resident} bytes of VMEM, over "
                f"the {_VMEM_BUDGET} budget")
    return None if pair is None else _pair_refusal(q, k, v, pair)


def supported(q, k, v, causal: bool = True, mask=None, pair=None) -> bool:
    """True when the Pallas kernel can run this shape on this backend;
    the same shapes with or without ``causal`` or a ``mask``.  ``pair``:
    whether it runs with this second query/key pair as it is."""
    return _verdict("flash_attention", _refusal(q, k, v, pair), q, k, v,
                    *(pair or ()))


# ------------------------------------------------- packed (one-block) path
# The whole sequence in one block: no loop over kv blocks, no running
# maximum to correct, and a grid step takes a pack of (batch row, head)
# pairs.  Arrays are [B, T, H*D], cut on lane tiles of max(D, 128).

def _head_of(x, w, D):
    """Lane tile ``x`` with every head but its ``w``-th zeroed: a product
    contracted over the tile then sums that head's lanes alone, and one
    that keeps the tile's lanes is zero outside them."""
    if x.shape[1] == D:
        return x
    lane = lax.broadcasted_iota(jnp.int32, (1, x.shape[1]), 1)
    return jnp.where(lane // D == w, x, jnp.zeros_like(x))


def _add(acc, x):
    return x if acc is None else acc + x


def _scores(q, k, scale, causal):
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= rows, s, NEG_INF)
    return s


def _packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                       causal, D, g):
    bb, T, width = q_ref.shape
    L, per_tile = _lane_tile(D)

    def row(b, carry):
        for t in range(width // L):          # static: lane tiles, heads
            kv = (t // g) * L
            q = q_ref[b, :, t * L:(t + 1) * L]
            k, v = k_ref[b, :, kv:kv + L], v_ref[b, :, kv:kv + L]
            o = None
            for w in range(per_tile):
                vw = _head_of(v, w, D)
                s = _scores(q, _head_of(k, w, D), scale, causal)
                m = s.max(axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = p.sum(axis=-1, keepdims=True)
                o = _add(o, jnp.dot(p.astype(vw.dtype), vw,
                                    preferred_element_type=jnp.float32) / l)
                lse_ref[b, t * per_tile + w, 0, :] = (
                    m + jnp.log(l)).reshape(T)
            o_ref[b, :, t * L:(t + 1) * L] = o.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, bb, row, 0)


def _packed_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, *, scale, causal, D, g):
    bb, T, width = q_ref.shape
    L, per_tile = _lane_tile(D)

    def row(b, carry):
        for c in range(width // L // g):     # static: kv tiles, their group
            kv = slice(c * L, (c + 1) * L)
            k, v = k_ref[b, :, kv], v_ref[b, :, kv].astype(jnp.float32)
            dk = dv = None
            for t in range(c * g, (c + 1) * g):
                lanes = slice(t * L, (t + 1) * L)
                q = q_ref[b, :, lanes]
                do = do_ref[b, :, lanes].astype(jnp.float32)
                dq = None
                for w in range(per_tile):
                    h = t * per_tile + w
                    # dow's zeros keep dp to this head: v stays whole
                    qw, kw, dow = (_head_of(x, w, D) for x in (q, k, do))
                    lse = lse_ref[b, h, 0, :].reshape(T, 1)
                    delta = delta_ref[b, h, 0, :].reshape(T, 1)
                    p = jnp.exp(_scores(qw, kw, scale, causal) - lse)
                    dv = _add(dv, lax.dot_general(
                        p, dow, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                    dp = lax.dot_general(dow, v, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                    ds = p * (dp - delta) * scale
                    dq = _add(dq, jnp.dot(
                        ds.astype(kw.dtype), kw,
                        preferred_element_type=jnp.float32))
                    dk = _add(dk, lax.dot_general(
                        ds, qw.astype(jnp.float32), (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                dq_ref[b, :, lanes] = dq.astype(dq_ref.dtype)
            dk_ref[b, :, kv] = dk.astype(dk_ref.dtype)
            dv_ref[b, :, kv] = dv.astype(dv_ref.dtype)
        return carry

    lax.fori_loop(0, bb, row, 0)


def _packed_specs(q, k, D, pack):
    B, T, HD = q.shape
    H = HD // D
    g = H // (k.shape[2] // D)
    bb, hb = pack
    q_blk = pl.BlockSpec((bb, T, hb * D), lambda b, h: (b, 0, h))
    kv_blk = pl.BlockSpec((bb, k.shape[1], (hb // g) * D),
                          lambda b, h: (b, 0, h))
    row_blk = pl.BlockSpec((bb, hb, 1, T), lambda b, h: (b, h, 0, 0))
    return (B // bb, H // hb), q_blk, kv_blk, row_blk, g


def _packed_fwd(q, k, v, causal, scale, D, pack):
    """q [B,T,H*D], k/v [B,Tk,Hkv*D] → (out [B,T,H*D], lse [B,H,1,T])."""
    grid, q_blk, kv_blk, row_blk, g = _packed_specs(q, k, D, pack)
    B, T, HD = q.shape
    _count("fwd", "packed", "rows")
    return pl.pallas_call(
        functools.partial(_packed_fwd_kernel, scale=scale, causal=causal,
                          D=D, g=g),
        grid=grid,
        in_specs=[q_blk, kv_blk, kv_blk],
        out_specs=[q_blk, row_blk],
        out_shape=[
            _sds(q.shape, q.dtype, q, k, v),
            _sds((B, HD // D, 1, T), jnp.float32, q, k, v),
        ],
        interpret=_pallas.INTERPRET,
        name="hvd_flash_fwd",
    )(q, k, v)


def _packed_bwd(q, k, v, out, lse, do, dlse, causal, scale, D, pack):
    grid, q_blk, kv_blk, row_blk, g = _packed_specs(q, k, D, pack)
    B, T, HD = q.shape
    # delta as in the masked path (rowsum(dO * O), less the lse cotangent),
    # per head of the flat layout
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        B, T, HD // D, D).sum(-1).transpose(0, 2, 1)[:, :, None, :]
    delta = delta - dlse.astype(jnp.float32)
    _count("bwd", "packed", "rows")
    return pl.pallas_call(
        functools.partial(_packed_bwd_kernel, scale=scale, causal=causal,
                          D=D, g=g),
        grid=grid,
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, row_blk, row_blk],
        out_specs=[q_blk, kv_blk, kv_blk],
        out_shape=[
            _sds(q.shape, q.dtype, q, k, v, do),
            _sds(k.shape, k.dtype, q, k, v, do),
            _sds(v.shape, v.dtype, q, k, v, do),
        ],
        interpret=_pallas.INTERPRET,
        name="hvd_flash_bwd",
    )(q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _packed_attention_lse(q, k, v, causal, scale, D, pack):
    return _packed_fwd(q, k, v, causal, scale, D, pack)


def _named_residuals(out, lse):
    """The forward kernel's two results under their checkpoint names.
    A forward rule returns THESE as primal outputs and as residuals: a
    name put on what the public op returns would be another variable
    than the residual, and a remat'd caller would still rerun the
    kernel to make the residual again."""
    return (checkpoint_name(out, OUT_NAME), checkpoint_name(lse, LSE_NAME))


def _packed_attention_lse_fwd(q, k, v, causal, scale, D, pack):
    out, lse = _named_residuals(*_packed_fwd(q, k, v, causal, scale, D,
                                             pack))
    return (out, lse), (q, k, v, out, lse)


def _packed_attention_lse_bwd(causal, scale, D, pack, res, cotangents):
    do, dlse = cotangents
    q, k, v, out, lse = res
    return tuple(_packed_bwd(q, k, v, out, lse, do, dlse, causal, scale, D,
                             pack))


_packed_attention_lse.defvjp(_packed_attention_lse_fwd,
                             _packed_attention_lse_bwd)


# ------------------------------------------------- masked (ranges) path
# The mask as data: per query row (lo1, hi1, lo2, hi2), key c live when
# lo1 <= c < hi1 or lo2 <= c < hi2.  Tile classes: 0 dead, 1 mixed, 2 full.

def causal_ranges(T: int):
    """``[T, 4]``: row i sees keys ``[0, i + 1)``."""
    r = np.zeros((T, 4), np.int32)
    r[:, 1] = np.arange(T) + 1
    return r


def full_ranges(T: int, Tk: int):
    """``[T, 4]``: every row sees every key (every tile full)."""
    r = np.zeros((T, 4), np.int32)
    r[:, 1] = Tk
    return r


def window_ranges(T: int, window: int):
    """``[T, 4]``: row i sees keys ``[max(0, i - window + 1), i + 1)``."""
    r = causal_ranges(T)
    r[:, 0] = np.maximum(np.arange(T) - window + 1, 0)
    return r


def dense_mask(ranges, Tk: int):
    """Boolean ``[..., T, Tk]`` of the ranges (tests and plain paths)."""
    xp = np if isinstance(ranges, np.ndarray) else jnp
    cols = xp.arange(Tk)
    lo1, hi1, lo2, hi2 = (ranges[..., c:c + 1] for c in range(4))
    return (((cols >= lo1) & (cols < hi1))
            | ((cols >= lo2) & (cols < hi2)))


def tile_classes(ranges, bq: int, bk: int, Tk: int):
    """``[Bm, nq, nk]`` int32 classes of the (query tile, key tile) pairs
    of ``ranges [Bm, T, 4]``: 2 where every row has one range covering
    the whole key tile, 0 where no row's range meets it, else 1.  numpy
    in, numpy out (known where the call is built); a traced array gives
    a traced table."""
    xp = np if isinstance(ranges, np.ndarray) else jnp
    Bm, T, _ = ranges.shape
    c0 = (xp.arange(Tk // bk) * bk).astype(xp.int32)      # [nk]
    c1 = c0 + bk
    meets = covers = False
    for lo, hi in ((ranges[..., 0:1], ranges[..., 1:2]),
                   (ranges[..., 2:3], ranges[..., 3:4])):
        meets = meets | (xp.minimum(hi, c1) > xp.maximum(lo, c0))
        covers = covers | ((lo <= c0) & (hi >= c1))
    meets = meets.reshape(Bm, T // bq, bq, -1).any(axis=2)
    covers = covers.reshape(Bm, T // bq, bq, -1).all(axis=2)
    return xp.where(covers, 2, xp.where(meets, 1, 0)).astype(xp.int32)


class _Sub(NamedTuple):
    """The mixed tiles' ``sq x sk`` sub-tiles, ``[Bm, nq, nk]`` int32 words
    a tile, zero for a tile that is not mixed.  ``words``: the sub-tiles'
    classes at two bits each (query sub-tile ``r`` on key sub-tile ``c`` at
    bits ``2 * (r * sk + c)``).  ``spans``: for each band of query
    sub-tiles ``r`` (bits ``8 * r`` on) the span from its first live key
    sub-tile to its last: their number (3 bits; 0, no live one) and the
    first (2 bits).  ``grid``: ``(sq, sk)``."""
    words: object
    spans: object
    grid: tuple


def _sub_words(ranges, classes, bq: int, bk: int, Tk: int):
    """:class:`_Sub` of ``ranges [Bm, T, 4]`` whose tiles of ``bq x bk``
    have ``classes``, by :func:`tile_classes` at ``_SUB`` positions a side
    (numpy in, numpy out; traced, traced tables).  None where a tile is
    no more than one sub-tile, or the mask is known here and cuts no
    tile: such a call builds no table and walks no sub-tile."""
    xp = np if isinstance(ranges, np.ndarray) else jnp
    if (bq % _SUB or bk % _SUB or bq * bk == _SUB * _SUB
            or (xp is np and not (classes == 1).any())):
        return None
    sq, sk = bq // _SUB, bk // _SUB
    assert sq <= 4 and sk <= 4, (bq, bk, _SUB)      # the words' fields
    Bm, nq, nk = classes.shape
    fine = tile_classes(ranges, _SUB, _SUB, Tk).reshape(Bm, nq, sq, nk, sk)
    fine = fine.transpose(0, 1, 3, 2, 4)            # [Bm, nq, nk, sq, sk]
    live = fine >= 1
    first = xp.argmax(live, -1)
    last = sk - 1 - xp.argmax(live[..., ::-1], -1)
    fields = xp.where(live.any(-1), last - first + 1, 0) + (first << 3)

    def pack(values, bits):      # disjoint bits: the sum is the union
        n = values.shape[-1]
        shifts = (bits * xp.arange(n)).astype(xp.uint32)
        word = (values.astype(xp.uint32) << shifts).sum(-1, dtype=xp.uint32)
        return xp.where(classes == 1, word, xp.uint32(0)).view(xp.int32)

    return _Sub(pack(fine.reshape(Bm, nq, nk, sq * sk), 2), pack(fields, 8),
                (sq, sk))


def sub_codes(words, n: int):
    """``[..., n]`` classes of the ``n`` sub-tiles held in ``words``."""
    xp = np if isinstance(words, np.ndarray) else jnp
    return (words[..., None] >> (2 * xp.arange(n)).astype(words.dtype)) & 3


def _row_tables(classes, sub=None):
    """For ``hvd_flash_fwd`` / ``hvd_flash_dq``: each query tile's key
    tiles ordered full, mixed, dead, with the counts of the first and
    of the first two, flat int32 for SMEM; with ``sub`` (:class:`_Sub`)
    its ``spans`` as a fourth, by query tile and key tile."""
    xp = np if isinstance(classes, np.ndarray) else jnp
    nk = classes.shape[-1]
    key = (2 - classes) * nk + xp.arange(nk)
    idx = xp.argsort(key, axis=-1).astype(xp.int32)
    n_full = (classes == 2).sum(-1).astype(xp.int32)
    n_live = (classes >= 1).sum(-1).astype(xp.int32)
    tables = idx.reshape(-1), n_full.reshape(-1), n_live.reshape(-1)
    return tables if sub is None else tables + (sub.spans.reshape(-1),)


def _pair_table(classes, sub=None):
    """For ``hvd_flash_dkv``: ``(table, P)``, the (key tile, query tile)
    pairs to visit, key-tile major so that a key tile's steps are
    consecutive.  Flat int32 ``[Bm * P * 4]`` of (key tile, query tile,
    class, flags: 1 first of its key tile, 2 last), ``[Bm * P * 5]`` with
    ``sub`` (:class:`_Sub`): the pair's word of sub-tile classes
    (``words``) the fifth.  A key tile no query
    tile sees keeps one dead pair, which writes its zeros.  With classes
    known at build time ``P`` is the most any batch row needs; traced,
    every pair has a slot."""
    xp = np if isinstance(classes, np.ndarray) else jnp
    Bm, nq, nk = classes.shape
    cls = xp.swapaxes(classes, 1, 2).reshape(Bm, nk * nq)   # k-major
    jj = xp.repeat(xp.arange(nk), nq)[None].astype(xp.int32)
    ii = xp.tile(xp.arange(nq), nk)[None].astype(xp.int32)
    unseen = (cls.reshape(Bm, nk, nq) >= 1).sum(-1) == 0     # [Bm, nk]
    needed = (cls >= 1) | (xp.repeat(unseen, nq, axis=1) & (ii == 0))
    order = xp.argsort(xp.where(needed, 0, 1) * (nk * nq)
                       + xp.arange(nk * nq), axis=-1)
    n = needed.sum(-1)                                       # [Bm]
    P = int(n.max()) if xp is np else nk * nq
    slot = xp.minimum(xp.arange(P)[None], n[:, None] - 1)    # pads repeat
    take = lambda a: xp.take_along_axis(
        xp.broadcast_to(a, cls.shape), xp.take_along_axis(order, slot, 1), 1)
    j, i = take(jj), take(ii)
    real = xp.arange(P)[None] < n[:, None]
    c = xp.where(real, take(cls), 0)
    prev_j = xp.concatenate([xp.full((Bm, 1), -1, j.dtype), j[:, :-1]], 1)
    next_j = xp.concatenate([j[:, 1:], xp.full((Bm, 1), -1, j.dtype)], 1)
    last_real = xp.arange(P)[None] == n[:, None] - 1
    flags = (xp.where(real & (j != prev_j), 1, 0)
             + xp.where(real & ((j != next_j) | last_real), 2, 0))
    columns = [j, i, c, flags]
    if sub is not None:
        columns.append(take(xp.swapaxes(sub.words, 1, 2).reshape(cls.shape)))
    table = xp.stack(columns, -1).astype(xp.int32)
    return table.reshape(-1), P


def _in_ranges(cols, lo1, hi1, lo2, hi2):
    return ((cols >= lo1) & (cols < hi1)) | ((cols >= lo2) & (cols < hi2))


def _walk(word, sub, visit):
    """A mixed pair of ``hvd_flash_dkv`` by its live sub-tiles:
    ``visit(c, r)`` for key sub-tile ``c`` (traced: a loop) on query
    sub-tile ``r`` (static) of the ``sub = (sq, sk)`` that the two bits of
    ``word`` (SMEM; :class:`_Sub`'s ``words``) do not call dead; the
    caller unrolls its heads inside ``visit``."""
    sq, sk = sub

    def keys(c, carry):
        for r in range(sq):
            pl.when((word >> (2 * (r * sk + c))) & 3 != 0)(
                functools.partial(visit, c, r))
        return carry

    lax.fori_loop(0, sk, keys, 0)


def _walk_bands(word, sub, visit):
    """A mixed tile of ``hvd_flash_fwd`` / ``hvd_flash_dq`` by its bands of
    query sub-tiles, each on the span of its live key sub-tiles at once
    (what a visit costs beside its products, the rows' statistics and
    accumulators read and written, it then pays once a band, as a tile
    taken whole pays it): ``visit(r, first, n)`` for band ``r`` (traced: a
    loop) on ``n`` (static) key sub-tiles from the ``first`` on (traced),
    as ``word`` says (SMEM; :class:`_Sub`'s ``spans``); a band with no
    live sub-tile not at all."""
    sq, sk = sub

    def band(r, carry):
        field = (word >> (8 * r)) & 0xff
        for n in range(1, sk + 1):
            pl.when(field & 7 == n)(functools.partial(
                visit, r, (field >> 3) & 3, n))
        return carry

    lax.fori_loop(0, sq, band, 0)


def _key_tiles(tables, row, nk, bq, bk, sub, visit):
    """A query tile's live key tiles, from ``tables`` (:func:`_row_tables`
    in SMEM; the tile's entries are ``row``'s ``nk``): full tiles
    unmasked, then mixed tiles masked, whole or, with ``sub = (sq, sk)``,
    by their live sub-tiles (:func:`_walk_bands`).  ``visit(rows, col0,
    width, masked)`` takes the tile's query ``rows`` (a slice) on
    ``width`` keys from ``col0`` on.  No branch inside a loop body but
    :func:`_walk_bands`'s."""
    idx_ref, nfull_ref, nlive_ref, *sub_ref = tables

    def whole(n, carry, masked):
        j = idx_ref[row * nk + n]
        visit(slice(None), pl.multiple_of(j * bk, bk), bk, masked)
        return carry

    def by_sub_tiles(n, carry):
        j = idx_ref[row * nk + n]
        wq, wk = bq // sub[0], bk // sub[1]
        _walk_bands(sub_ref[0][row * nk + j], sub, lambda r, first, n: visit(
            pl.ds(pl.multiple_of(r * wq, wq), wq),
            pl.multiple_of(j * bk + first * wk, wk), n * wk, True))
        return carry

    lax.fori_loop(0, nfull_ref[row], functools.partial(whole, masked=False),
                  0)
    lax.fori_loop(nfull_ref[row], nlive_ref[row], by_sub_tiles if sub else
                  functools.partial(whole, masked=True), 0)


def _lanes(x, n):
    """``x [rows, 128]`` with every lane of a row alike, as ``[rows, n]``."""
    if n <= _LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // _LANES))


def _column(row):
    """``row [1, n]`` as a column alike in every lane, ``[n, 128]``: the
    row down the sublanes and one transpose, not ``n`` lane shuffles."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _spread_ranges(r_ref, rb_ref):
    """The rows' four range columns of ``r_ref [1, bq, 4]`` over the lanes
    of ``rb_ref [4, bq, 128]``: once a grid step, for every head and
    mixed tile of it."""
    for c in range(4):
        rb_ref[c] = jnp.broadcast_to(r_ref[0][:, c:c + 1], rb_ref.shape[1:])


def _rows_live(rb_ref, col0, bk, rows=slice(None)):
    """Boolean ``[rows, bk]``: the pairs of the tile's ``rows`` with the
    ``bk`` keys from ``col0`` on that the rows' spread ranges let through
    (the tile's columns are shifted, not the ranges)."""
    lo1, hi1, lo2, hi2 = (_lanes(rb_ref[c, rows], bk) for c in range(4))
    cols = lax.broadcasted_iota(jnp.int32, lo1.shape, 1) + col0
    return _in_ranges(cols, lo1, hi1, lo2, hi2)


def _head(ref, h, d, at=slice(None)):
    """Index of positions ``at`` of head ``h`` in a block of heads ``d``
    wide: ``[1, heads, positions, d]`` of the transposed layout, or
    ``[1, positions, heads * d]`` of the caller's, where a head is ``d``
    lanes from a 128 boundary, known where the kernel is built."""
    if len(ref.shape) == 4:
        return 0, h, at
    return 0, at, slice(h * d, (h + 1) * d)


def _mfwd_kernel(tables, q_ref, k_ref, v_ref, r_ref, o_ref, lse_ref, m_ref,
                 l_ref, acc_ref, rb_ref, *, scale, bk, nq, nk, per_batch,
                 sub, pair=None):
    """One query tile of ``hb`` query heads that share a kv head (static,
    unrolled: one head's softmax is scheduled under another's products).
    The running statistics live in VMEM, a head at a time in registers:
    ``m_ref`` the row maxima and ``l_ref`` the row sums as ``[hb, bq,
    128]``, the maximum alike in every lane, the sum in 128 partial sums
    (one a lane, added across the tile's column groups elementwise) that
    meet once, after the last tile; ``acc_ref [hb, bq, D]``.  ``rb_ref
    [4, bq, 128]`` holds the rows' ranges spread over the lanes once a
    step, for every head and mixed tile of it.  ``tables``: the query
    tiles' key tiles (:func:`_row_tables`); with ``sub = (sq, sk)`` a mixed
    tile is walked by its live sub-tiles, with None it is taken whole
    (:func:`_key_tiles`).  ``pair``: the second pair's ``(q2_ref, k2_ref)``,
    a query tile of the step's heads and their one key head's whole keys:
    its product joins each score tile."""
    hb, bq, Dv = acc_ref.shape
    D = k_ref.shape[-1]
    if pair:
        q2_ref, k2_ref = pair
        D2 = k2_ref.shape[-1]
    i = pl.program_id(2)
    row = (pl.program_id(0) * nq if per_batch else 0) + i
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    _spread_ranges(r_ref, rb_ref)

    def visit(rows, col0, width, masked):
        """The tile's query ``rows`` (a slice) on ``width`` keys from
        ``col0`` on.  Rows that see none of them keep what they have: with
        a maximum of their own ``p`` is 0, and what rows without one add
        is wiped by ``corr`` = 0 at their first live key."""
        at = pl.ds(col0, width)
        kj, vj = k_ref[_head(k_ref, 0, D, at)], v_ref[_head(v_ref, 0, Dv, at)]
        if pair:
            k2j = k2_ref[_head(k2_ref, 0, D2, at)]
        if masked:                  # one mask a visit, added by every head
            dead = jnp.where(_rows_live(rb_ref, col0, width, rows), 0.0,
                             NEG_INF)
        for h in range(hb):
            s = _scores(q_ref[_head(q_ref, h, D, rows)], kj, scale, False)
            if pair:
                s = s + _scores(q2_ref[_head(q2_ref, h, D2, rows)], k2j,
                                scale, False)
            if masked:
                s = s + dead
            m = m_ref[h, rows]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, width))
            corr = jnp.exp(m - m_new)
            m_ref[h, rows] = m_new
            l_ref[h, rows] = l_ref[h, rows] * corr + functools.reduce(
                jnp.add,
                (p[:, c:c + _LANES] for c in range(0, width, _LANES)))
            acc_ref[h, rows] = acc_ref[h, rows] * _lanes(corr, Dv) + jnp.dot(
                p.astype(vj.dtype), vj, preferred_element_type=jnp.float32)

    _key_tiles(tables, row, nk, bq, bk, sub, visit)
    for h in range(hb):
        l = l_ref[h].sum(axis=-1, keepdims=True)
        o_ref[_head(o_ref, h, Dv)] = (acc_ref[h] / l).astype(o_ref.dtype)
        # along the lanes for the row of lse: a transpose, not bq shuffles
        lse_ref[0, h, pl.ds(i, 1), :] = (m_ref[h] + jnp.log(l)).T[:1]


def _mdq_kernel(tables, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                r_ref, dq_ref, acc_ref, lse_b, delta_b, rb_ref, *, scale, bk,
                nq, nk, per_batch, sub, pair=None):
    """One query tile of ``hb`` query heads that share a kv head, as the
    forward takes them (static, unrolled: a key tile is loaded once for
    all of them and one head's elementwise work is scheduled under
    another's products), a mixed tile by its live sub-tiles as there
    (``tables``, ``sub``).  ``acc_ref [hb, bq, D]`` holds ``dq`` in float32
    until the last tile; ``lse_b`` and ``delta_b [hb, bq, 128]`` the heads'
    rows of ``lse`` and ``delta`` as columns alike in every lane and
    ``rb_ref [4, bq, 128]`` the rows' ranges over the lanes, each laid out
    once a step.  ``pair``: the second pair's ``(q2_ref, k2_ref, dq2_ref,
    acc2_ref)``, ``dq2`` accumulated as ``dq`` is."""
    (hb, bq, D), Dv = acc_ref.shape, v_ref.shape[-1]
    if pair:
        q2_ref, k2_ref, dq2_ref, acc2_ref = pair
        D2 = k2_ref.shape[-1]
        acc2_ref[...] = jnp.zeros(acc2_ref.shape, jnp.float32)
    i = pl.program_id(2)
    row = (pl.program_id(0) * nq if per_batch else 0) + i
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    for h in range(hb):
        lse_b[h] = _column(lse_ref[0, h, pl.ds(i, 1), :])
        delta_b[h] = _column(delta_ref[0, h, pl.ds(i, 1), :])
    _spread_ranges(r_ref, rb_ref)

    def visit(rows, col0, width, masked):
        """The tile's query ``rows`` (a slice) on ``width`` keys from
        ``col0`` on."""
        at = pl.ds(col0, width)
        kj, vj = k_ref[_head(k_ref, 0, D, at)], v_ref[_head(v_ref, 0, Dv, at)]
        if pair:
            k2j = k2_ref[_head(k2_ref, 0, D2, at)]
        if masked:                  # one mask a tile, added by every head
            dead = jnp.where(_rows_live(rb_ref, col0, width, rows), 0.0,
                             NEG_INF)
        for h in range(hb):
            s = _scores(q_ref[_head(q_ref, h, D, rows)], kj, scale, False)
            if pair:
                s = s + _scores(q2_ref[_head(q2_ref, h, D2, rows)], k2j,
                                scale, False)
            if masked:
                s = s + dead
            p = jnp.exp(s - _lanes(lse_b[h, rows], width))
            dp = lax.dot_general(do_ref[_head(do_ref, h, Dv, rows)], vj,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(delta_b[h, rows], width)) * scale
            acc_ref[h, rows] += jnp.dot(ds.astype(kj.dtype), kj,
                                        preferred_element_type=jnp.float32)
            if pair:
                acc2_ref[h, rows] += jnp.dot(
                    ds.astype(k2j.dtype), k2j,
                    preferred_element_type=jnp.float32)

    _key_tiles(tables, row, nk, bq, bk, sub, visit)
    for h in range(hb):
        dq_ref[_head(dq_ref, h, D)] = acc_ref[h].astype(dq_ref.dtype)
        if pair:
            dq2_ref[_head(dq2_ref, h, D2)] = acc2_ref[h].astype(
                dq2_ref.dtype)


def _mdkv_kernel(tbl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 r_ref, dk_ref, dv_ref, dk_acc, dv_acc, *st_ref, scale, P, g,
                 per_batch, sub, pair=None, dq=None):
    """One live (key tile, query tile) pair of a kv head's ``g`` query
    heads (static, unrolled).  The tile is formed keys by queries, ``S^T =
    K Q^T [bk, bq]``: a query's ``lse``, ``delta`` and ranges (``r_ref [1,
    4, bq]``, a row a bound) then lie along the lanes as they are stored
    and go down the sublanes for nothing, and ``dv = P^T do`` and ``dk =
    dS^T q`` are plain ``[bk, bq] x [bq, D]`` products.  With ``sub = (sq,
    sk)`` a mixed pair is walked by its live sub-tiles (the table's fifth
    column their classes), ``dk`` and ``dv`` accumulating by the key
    sub-tile's rows; with None it is taken whole.  The sub-tiles read the
    query tile's rows of ``lse`` and ``delta`` from ``st_ref [2, g, 1,
    bq]``, laid there once a mixed pair: Mosaic loads a row at a dynamic
    index whole, not one lane tile of it.  ``pair``: the second pair's
    ``(q2_ref, k2_ref, dk2_ref, dk2_acc)``; ``dk2_ref`` takes this kv head's
    part of the shared key head's gradient in float32, and the caller adds
    the parts of the heads that share it.  ``dq``: ``(dq_ref, dq2_ref,
    dq_acc, dq2_acc)`` of the one backward (``hvd_flash_dqkv``: a call with
    a pair whose step :func:`_dqkv_step_bytes` finds room for): the visit's
    ``dS^T`` turned once gives ``dq += dS k`` and ``dq2 += dS k2`` of its
    query rows, in ``dq_acc [g, T, D]`` and ``dq2_acc [g, T, D2]`` float32
    over all the kv head's pairs, zeroed at its first and cast out at its
    last into blocks that are the group's whole columns of the caller's
    rows, which the pipeline writes once a kv head."""
    (bk, D), Dv = k_ref.shape[-2:], v_ref.shape[-1]
    if pair:
        q2_ref, k2_ref, dk2_ref, dk2_acc = pair
        D2 = k2_ref.shape[-1]
    bq = r_ref.shape[-1]
    if dq:
        dq_ref, dq2_ref, dq_acc, dq2_acc = dq
        tiles = lambda n: pl.ds(pl.multiple_of(n * bq, bq), bq)

        @pl.when(pl.program_id(2) == 0)
        def _():
            def zero(n, carry):
                dq_acc[:, tiles(n)] = jnp.zeros((g, bq, D), jnp.float32)
                dq2_acc[:, tiles(n)] = jnp.zeros((g, bq, D2), jnp.float32)
                return carry

            lax.fori_loop(0, dq_acc.shape[1] // bq, zero, 0)
    width = 5 if sub else 4
    at = ((pl.program_id(0) * P if per_batch else 0)
          + pl.program_id(2)) * width
    j, i, cls, flags = (tbl_ref[at + c] for c in range(4))
    keys_by_queries = (((1,), (1,)), ((), ()))

    @pl.when(flags % 2 == 1)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if pair:
            dk2_acc[...] = jnp.zeros_like(dk2_acc)

    def whole(n, hq):
        return (lse_ref, delta_ref)[n][0, hq, pl.ds(i, 1), :]

    def visit(masked, keys=slice(None), key0=0, queries=slice(None),
              stat=whole):
        """The pair's ``keys`` (a slice from the tile's ``key0`` on) by its
        ``queries`` (a static slice); ``stat(0, head)`` the queries' row of
        ``lse``, ``stat(1, head)`` of ``delta``."""
        kb = k_ref[_head(k_ref, 0, D, keys)]
        vb = v_ref[_head(v_ref, 0, Dv, keys)]
        if pair:
            k2b = k2_ref[_head(k2_ref, 0, D2, keys)]
        if masked:
            bounds = [r_ref[0, c:c + 1, queries] for c in range(4)]
            at_key = lax.broadcasted_iota(
                jnp.int32, (kb.shape[0], bounds[0].shape[1]), 0)
            live = _in_ranges(at_key + (j * bk + key0), *bounds)
        dk = dv = dk2 = None
        for hq in range(g):           # static: the kv head's query heads
            qi = q_ref[_head(q_ref, hq, D, queries)]
            doi = do_ref[_head(do_ref, hq, Dv, queries)]
            s = _scores(kb, qi, scale, False)
            if pair:
                q2i = q2_ref[_head(q2_ref, hq, D2, queries)]
                s = s + _scores(k2b, q2i, scale, False)
            if masked:
                s = jnp.where(live, s, NEG_INF)
            p = jnp.exp(s - stat(0, hq))
            dv = _add(dv, jnp.dot(p.astype(doi.dtype), doi,
                                  preferred_element_type=jnp.float32))
            dp = lax.dot_general(vb, doi, keys_by_queries,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - stat(1, hq)) * scale
            dk = _add(dk, jnp.dot(ds.astype(qi.dtype), qi,
                                  preferred_element_type=jnp.float32))
            if pair:
                dk2 = _add(dk2, jnp.dot(ds.astype(q2i.dtype), q2i,
                                        preferred_element_type=jnp.float32))
            if dq:          # the queries' rows of the kv head's T
                first = queries.start or 0
                rows = pl.ds(pl.multiple_of(i * bq, bq) + first,
                             (queries.stop or bq) - first)
                dst = ds.astype(kb.dtype).T
                dq_acc[hq, rows] += jnp.dot(
                    dst, kb, preferred_element_type=jnp.float32)
                dq2_acc[hq, rows] += jnp.dot(
                    dst, k2b, preferred_element_type=jnp.float32)
        dk_acc[keys] += dk
        dv_acc[keys] += dv
        if pair:
            dk2_acc[keys] += dk2

    pl.when(cls == 2)(functools.partial(visit, False))
    if sub:
        @pl.when(cls == 1)
        def _():
            for hq in range(g):
                for n in range(2):
                    st_ref[0][n, hq] = whole(n, hq)
            wq, wk = bq // sub[0], bk // sub[1]

            def sub_tile(c, r):
                key0, queries = pl.multiple_of(c * wk, wk), slice(
                    r * wq, (r + 1) * wq)
                visit(True, pl.ds(key0, wk), key0, queries,
                      lambda n, hq: st_ref[0][n, hq, :, queries])

            _walk(tbl_ref[at + 4], sub, sub_tile)
    else:
        pl.when(cls == 1)(functools.partial(visit, True))

    @pl.when(flags >= 2)
    def _():
        dk_ref[_head(dk_ref, 0, D)] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[_head(dv_ref, 0, Dv)] = dv_acc[...].astype(dv_ref.dtype)
        if pair:
            dk2_ref[_head(dk2_ref, 0, D2)] = dk2_acc[...]

    if dq:
        @pl.when(pl.program_id(2) == P - 1)
        def _():
            def cast(n, carry):
                for hq in range(g):
                    dq_ref[_head(dq_ref, hq, D, tiles(n))] = dq_acc[
                        hq, tiles(n)].astype(dq_ref.dtype)
                    dq2_ref[_head(dq2_ref, hq, D2, tiles(n))] = dq2_acc[
                        hq, tiles(n)].astype(dq2_ref.dtype)
                return carry

            lax.fori_loop(0, dq_acc.shape[1] // bq, cast, 0)


def _mask_plan(mask, bq, bk, Tk):
    """(ranges [Bm, T, 4], tile classes, the mixed tiles' sub-tile classes
    (:func:`_sub_words`), whether the mask is one a batch row, the batch
    row's index into them) of a caller's mask."""
    ranges = mask if mask.ndim == 3 else mask[None]
    if not isinstance(ranges, np.ndarray):
        ranges = ranges.astype(jnp.int32)
    per_batch = ranges.shape[0] > 1
    classes = tile_classes(ranges, bq, bk, Tk)
    return (ranges, classes, _sub_words(ranges, classes, bq, bk, Tk),
            per_batch, (lambda b: b) if per_batch else (lambda b: 0))


def _tables_first(kernel, n, **static):
    """``kernel`` taking its first ``n`` refs, the tables in SMEM, as one
    tuple."""
    return lambda *refs, **pair: kernel(refs[:n], *refs[n:], **static, **pair)


def _pair_refs(kernel, pair, *groups, name="pair"):
    """``kernel`` for a call with a second ``pair``, whose refs come in
    ``groups`` of ``(how many, the last of them that are the pair's)``: the
    tables with the inputs, the outputs, the scratch.  The pair's are taken
    out and handed on as ``pair=``, in their order; without a pair the
    kernel as it is.  ``name``: another optional group's keyword (the one
    backward's ``dq=``), taken out the same way before the pair's."""
    if not pair:
        return kernel
    at, end = [], 0
    for size, last in groups:
        end += size
        at += range(end - last, end)
    return lambda *refs, **others: kernel(
        *(r for n, r in enumerate(refs) if n not in at),
        **{name: tuple(refs[n] for n in at)}, **others)


def _vmem(*block_bytes, scratch=0):
    """A masked kernel's VMEM limit: its blocks double-buffered, its
    scratch, and room for the fp32 tiles in flight."""
    return _pallas.params(
        vmem=2 * sum(block_bytes) + scratch + 24 * 1024 * 1024)


def _heads_shape(rows, B, heads, n, d):
    """``B`` batch rows of ``heads`` heads ``d`` wide by ``n`` positions:
    ``[B, heads, n, d]``, or with ``rows`` the caller's ``[B, n, heads *
    d]``."""
    return (B, n, heads * d) if rows else (B, heads, n, d)


def _heads_block(rows, heads, n, d, index):
    """Block of such an array, one batch row, at ``index(*grid) -> (batch,
    block of heads, block of positions)``; with ``rows`` the heads are
    the block's lanes."""
    def at(*grid):
        b, h, i = index(*grid)
        return (b, i, h) if rows else (b, h, i, 0)

    return pl.BlockSpec(_heads_shape(rows, 1, heads, n, d), at)


def _row_specs(rows, bq, D, Dv, Tk, nq, g, bm, hb=1, pair=None):
    """Block specs of the kernels that walk a query tile's key tiles
    (grid ``(B, H // hb, nq)``, three tables prefetched): a query tile of
    ``hb`` heads ``D`` wide (q, dq) and ``Dv`` wide (out, do), their kv
    head's whole keys and whole values, the heads' row statistics, the
    tile's ranges; with ``pair = (D2, g2)`` also a query tile ``D2`` wide
    and the whole keys of the second pair's key head, one to ``g2`` query
    heads."""
    tile = lambda d: _heads_block(rows, hb, bq, d,
                                  lambda b, h, i, *_: (b, h, i))
    whole = lambda d, g=g: _heads_block(
        rows, 1, Tk, d, lambda b, h, i, *_: (b, h * hb // g, 0))
    stats = pl.BlockSpec((1, hb, nq, bq), lambda b, h, i, *_: (b, h, 0, 0))
    rng = pl.BlockSpec((1, bq, 4), lambda b, h, i, *_: (bm(b), i, 0))
    specs = tile(D), tile(Dv), whole(D), whole(Dv), stats, rng
    return specs + (tile(pair[0]), whole(*pair)) if pair else specs


def _fwd_step_bytes(hb, bq, bk, D, nq, Tk, itemsize, Dv=None, D2=0):
    """VMEM bytes a masked forward grid step of ``hb`` heads holds:
    ``(blocks, scratch, tiles)``, the blocks the pipeline double-buffers
    (whole k and v, ``hb`` tiles of q and of out, their rows of lse, the
    ranges padded to a lane tile), the kernel's scratch (statistics,
    accumulators, the ranges over the lanes) and every head's ``[bq, bk]``
    scores and probabilities in fp32 and the latter cast.  ``Dv``: the
    values' width where it is not ``D``; ``D2``: a second pair's (its
    whole keys and ``hb`` tiles of its queries)."""
    Dv = D if Dv is None else Dv
    blocks = (Tk * (D + Dv + D2) * itemsize
              + hb * (bq * (D + Dv + D2) * itemsize + nq * bq * 4)
              + bq * _LANES * 4)
    scratch = (hb * bq * (2 * _LANES + Dv) + 4 * bq * _LANES) * 4
    return blocks, scratch, hb * bq * bk * (8 + itemsize)


def _dq_step_bytes(hb, bq, bk, D, nq, Tk, itemsize, Dv=None, D2=0):
    """The same for a ``dq`` grid step of ``hb`` heads: whole k and v,
    ``hb`` tiles of q, ``do`` and ``dq`` with their rows of lse and of
    delta, the ranges; the accumulators, the two statistics and the ranges
    over the lanes; and a head's two ``[bq, bk]`` fp32 tiles in flight
    (the probabilities take the scores' place and ``ds`` takes ``dp``'s)
    with ``ds`` cast; a second pair ``D2`` wide brings its whole keys, its
    queries, ``dq2`` and that accumulator."""
    Dv = D if Dv is None else Dv
    blocks = (Tk * (D + Dv + D2) * itemsize
              + hb * (bq * (2 * D + Dv + 2 * D2) * itemsize + 2 * nq * bq * 4)
              + bq * _LANES * 4)
    scratch = (hb * bq * (2 * _LANES + D + D2) + 4 * bq * _LANES) * 4
    return blocks, scratch, hb * bq * bk * (8 + itemsize)


def _dqkv_step_bytes(g, bq, bk, D, nq, Tk, itemsize, Dv=None, D2=0):
    """The same for a grid step of the one backward (``hvd_flash_dqkv``), a
    live pair of tiles of a kv head's ``g`` query heads: the blocks the
    pipeline double-buffers (the group's query tile of q, ``do`` and q2; a
    key tile of k, v and k2, of dk and dv and of dk2's float32 part; the
    group's whole rows of lse and of delta; the ranges, a row a bound,
    padded to 8 sublanes; **``dq`` and ``dq2`` of the group's whole ``nq x
    bq`` rows**, written once a kv head), the scratch (dk, dv and dk2 of a
    key tile, the sub-tiles' statistics, and **``dq`` and ``dq2`` of all
    the rows in float32**) and a head's tiles in flight (``S^T`` / ``P^T``
    and ``dP^T`` / ``dS^T`` in float32, ``P^T``, ``dS^T`` and ``dS`` cast).
    At the kanana cell's call (``g`` 1, 512 x 512 tiles, 128 + 128 + the
    pair's padded 128, 16,384 rows, bf16) 9.85 MB of blocks of which 8.39
    are ``dq`` and ``dq2``, 17.60 MB of scratch of which 16.78 are their
    accumulators, 3.67 MB of tiles: 40.96 MB a step."""
    Dv = D if Dv is None else Dv
    T = nq * bq
    blocks = ((g * bq + bk) * (D + Dv + D2) * itemsize
              + bk * ((D + Dv) * itemsize + D2 * 4)
              + 2 * g * T * 4 + 8 * bq * 4 + g * T * (D + D2) * itemsize)
    scratch = (bk * (D + Dv + D2) + 2 * g * 8 * bq + g * T * (D + D2)) * 4
    return blocks, scratch, g * bq * bk * (8 + 3 * itemsize)


def _one_backward(g, *shapes):
    """The bytes a step of the one backward holds where a call with a
    second pair takes it, else None: :func:`_dqkv_step_bytes` of ``g,
    *shapes``, the blocks twice, under ``_DQKV_STEP_VMEM``.  Latent
    attention's group is one head (16.8 MB of ``dq`` and ``dq2`` in float32
    at 16,384 rows); a longer sequence or a larger group under one rotary
    key keeps ``hvd_flash_dq`` + ``hvd_flash_dkv``."""
    blocks, scratch, tiles = _dqkv_step_bytes(g, *shapes)
    step = 2 * blocks + scratch + tiles
    return step if step <= _DQKV_STEP_VMEM else None


def _heads_a_step(step_bytes, g, *shapes):
    """Query heads a grid step of ``hvd_flash_fwd`` or ``hvd_flash_dq``
    takes: the most of one GQA group (a divisor of ``g``: they share the
    resident k and v) whose ``step_bytes(hb, *shapes)`` stay under
    ``_MASKED_STEP_VMEM``, one where not even two fit."""
    def fits(hb):
        blocks, scratch, tiles = step_bytes(hb, *shapes)
        return 2 * blocks + scratch + tiles <= _MASKED_STEP_VMEM

    return max(hb for hb in range(1, g + 1)
               if g % hb == 0 and (hb == 1 or fits(hb)))


# (g, bq, bk, D, nq, Tk, itemsize, Dv=None, D2=0) -> heads a step; dq never
# more than the forward, whose step holds less
_fwd_heads = functools.partial(_heads_a_step, _fwd_step_bytes)
_dq_heads = functools.partial(_heads_a_step, _dq_step_bytes)


def _masked_dims(q, k, v, widths):
    """``(B, H, Hkv, T, Tk, D, Dv)`` of a masked call's operands: ``[B, H,
    T, D]``, or with ``widths = (D, Dv)`` the caller's ``[B, T, H * D]``."""
    if widths is None:
        (B, H, T, D), (_, Hkv, Tk, Dv) = q.shape, v.shape
    else:
        (B, T, HD), (_, Tk, HkvDv), (D, Dv) = q.shape, v.shape, widths
        H, Hkv = HD // D, HkvDv // Dv
    return B, H, Hkv, T, Tk, D, Dv


def _pair_dims(pair, H):
    """``(D2, g2)`` of a second pair ``(q2 [B, T, H*D2], k2 [B, Tk,
    H2*D2])`` in the caller's rows: its width and the query heads a key
    head of it serves; ``(0, 0)`` of None."""
    if pair is None:
        return 0, 0
    D2 = pair[0].shape[2] // H
    return D2, H // (pair[1].shape[2] // D2)


def _masked_fwd(q, k, v, mask, scale, widths, pair=None):
    """q [B,H,T,D], k [B,Hkv,Tk,D], v [B,Hkv,Tk,Dv] → (out [B,H,T,Dv],
    lse [B,H,nq,bq]); with ``widths = (D, Dv)``, q [B,T,H*D], k
    [B,Tk,Hkv*D], v [B,Tk,Hkv*Dv] → out [B,T,H*Dv] and the same lse.
    ``pair``: the second pair in the caller's rows (:func:`_pair_dims`)."""
    B, H, Hkv, T, Tk, D, Dv = _masked_dims(q, k, v, widths)
    rows = widths is not None
    g = H // Hkv
    D2, g2 = _pair_dims(pair, H)
    bq, bk = _block_sizes(T, Tk)
    nq, nk = T // bq, Tk // bk
    hb = _fwd_heads(g, bq, bk, D, nq, Tk, q.dtype.itemsize, Dv, D2)
    ranges, classes, sub, per_batch, bm = _mask_plan(mask, bq, bk, Tk)
    tile, otile, whole, vwhole, stats, rng, *second = _row_specs(
        rows, bq, D, Dv, Tk, nq, g, bm, hb, pair and (D2, g2))
    _count("fwd", "paired" if pair else "masked", "rows" if rows else "heads")
    _count_tiles("fwd", classes, sub)
    blocks, scratch, _ = _fwd_step_bytes(hb, bq, bk, D, nq, Tk,
                                         q.dtype.itemsize, Dv, D2)
    tables = _row_tables(classes, sub)
    n = len(tables)
    in_specs = [tile, whole, vwhole, rng, *second]
    return pl.pallas_call(
        _pair_refs(_tables_first(
            _mfwd_kernel, n, scale=scale, bk=bk, nq=nq, nk=nk,
            per_batch=per_batch, sub=sub and sub.grid),
            pair, (n + len(in_specs), 2)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=(B, H // hb, nq),
            in_specs=in_specs,
            out_specs=[otile, stats],
            scratch_shapes=[pltpu.VMEM((hb, bq, _LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, _LANES), jnp.float32),
                            pltpu.VMEM((hb, bq, Dv), jnp.float32),
                            pltpu.VMEM((4, bq, _LANES), jnp.int32)]),
        out_shape=[
            _sds(_heads_shape(rows, B, H, T, Dv), q.dtype, q, k, v),
            _sds((B, H, nq, bq), jnp.float32, q, k, v),
        ],
        compiler_params=_vmem(blocks, scratch=scratch),
        interpret=_pallas.INTERPRET,
        name="hvd_flash_fwd",
    )(*tables, q, k, v, ranges, *(pair or ()))


def _masked_bwd(q, k, v, out, lse, do, mask, scale, dlse, widths, pair=None):
    """``(dq, dk, dv)`` in the operands' own layout (:func:`_masked_fwd`);
    with the second ``pair``, ``(dq, dk, dv, dq2, dk2)``: ``dk2`` is the sum
    over the kv heads that share a key head of it of the parts
    ``hvd_flash_dkv`` writes, one reduction in float32."""
    B, H, Hkv, T, Tk, D, Dv = _masked_dims(q, k, v, widths)
    rows = widths is not None
    g = H // Hkv
    D2, g2 = _pair_dims(pair, H)
    bq, bk = _block_sizes(T, Tk)
    nq, nk = T // bq, Tk // bk
    item = q.dtype.itemsize
    hb = _dq_heads(g, bq, bk, D, nq, Tk, item, Dv, D2)
    ranges, classes, sub, per_batch, bm = _mask_plan(mask, bq, bk, Tk)
    tile, otile, whole, vwhole, stats, rng, *second = _row_specs(
        rows, bq, D, Dv, Tk, nq, g, bm, hb, pair and (D2, g2))
    path = "paired" if pair else "masked"

    # delta_i = rowsum(dO * O) — cheap elementwise, stays in XLA.
    # When the caller differentiates through the exposed lse (ring-step
    # merging), its cotangent folds in exactly here: dlse/ds = p, so
    # ds = p·(dp − delta) + p·dlse = p·(dp − (delta − dlse)).
    lay = "bthd" if rows else "bhtd"     # rows: [B, T, H*Dv] by its heads
    heads = (B, T, H, Dv) if rows else (B, H, T, Dv)
    delta = jnp.einsum(f"{lay},{lay}->bht",
                       do.astype(jnp.float32).reshape(heads),
                       out.astype(jnp.float32).reshape(heads))
    delta = delta.reshape(B, H, nq, bq) - dlse.astype(jnp.float32)

    # the one backward: a second pair, and room for the kv head's dq
    one = pair and _one_backward(g, bq, bk, D, nq, Tk, item, Dv, D2)
    for kernel in ("dqkv",) if one else ("dq", "dkv"):
        _count(kernel, path, "rows" if rows else "heads")
        _count_tiles(kernel, classes, sub)
    blocks, scratch, _ = _dq_step_bytes(hb, bq, bk, D, nq, Tk, item, Dv, D2)
    tables = _row_tables(classes, sub)
    n = len(tables)
    dq_shape = _sds(_heads_shape(rows, B, H, T, D), q.dtype, q, k, v, do)
    in_specs = [tile, whole, vwhole, otile, stats, stats, rng, *second]
    scratch_shapes = ([pltpu.VMEM((hb, bq, D), jnp.float32),
                       pltpu.VMEM((hb, bq, _LANES), jnp.float32),
                       pltpu.VMEM((hb, bq, _LANES), jnp.float32),
                       pltpu.VMEM((4, bq, _LANES), jnp.int32)]
                      + ([pltpu.VMEM((hb, bq, D2), jnp.float32)] if pair else []))
    dq = None if one else pl.pallas_call(
        _pair_refs(_tables_first(
            _mdq_kernel, n, scale=scale, bk=bk, nq=nq, nk=nk,
            per_batch=per_batch, sub=sub and sub.grid),
            # q2 and k2 the last inputs, dq2 after dq, its accumulator last
            pair, (n + len(in_specs), 2), (2, 1), (len(scratch_shapes), 1)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=(B, H // hb, nq),
            in_specs=in_specs,
            out_specs=[tile, second[0]] if pair else tile,
            scratch_shapes=scratch_shapes),
        out_shape=[dq_shape, _sds(pair[0].shape, q.dtype, q, k, v, do)]
        if pair else dq_shape,
        compiler_params=_vmem(blocks, scratch=scratch),
        interpret=_pallas.INTERPRET,
        name="hvd_flash_dq",
    )(*tables, q, k, v, do, lse, delta, ranges, *(pair or ()))

    table, P = _pair_table(classes, sub)
    # table entry p of batch row b: key tile at [.. + 0], query tile at
    # [.. + 1]
    width = 5 if sub else 4
    at = (lambda b, p: (b * P + p) * width) if per_batch else (
        lambda b, p: p * width)
    q_blk = lambda d: _heads_block(
        rows, g, bq, d, lambda b, c, p, t: (b, c, t[at(b, p) + 1]))
    kv_blk = lambda d: _heads_block(
        rows, 1, bk, d, lambda b, c, p, t: (b, c, t[at(b, p)]))
    row_blk = pl.BlockSpec((1, g, nq, bq), lambda b, c, p, t: (b, c, 0, 0))
    if pair:
        # the key head of the second pair that kv head c's queries read
        k2_blk = _heads_block(
            rows, 1, bk, D2, lambda b, c, p, t: (b, c * g // g2, t[at(b, p)]))
    in_specs = [
        q_blk(D), kv_blk(D), kv_blk(Dv), q_blk(Dv), row_blk, row_blk,
        # the query tile's ranges, a row a bound: [Bm, 4, T]
        pl.BlockSpec((1, 4, bq),
                     lambda b, c, p, t: (bm(b), 0, t[at(b, p) + 1])),
    ] + ([q_blk(D2), k2_blk] if pair else [])
    out_specs = [kv_blk(D), kv_blk(Dv)] + ([kv_blk(D2)] if pair else [])
    scratch_shapes = ([pltpu.VMEM((bk, D), jnp.float32),
                       pltpu.VMEM((bk, Dv), jnp.float32)]
                      + ([pltpu.VMEM((2, g, 1, bq), jnp.float32)] if sub else [])
                      + ([pltpu.VMEM((bk, D2), jnp.float32)] if pair else []))
    out_shape = [
        _sds(_heads_shape(rows, B, Hkv, Tk, D), k.dtype, q, k, v, do),
        _sds(_heads_shape(rows, B, Hkv, Tk, Dv), v.dtype, q, k, v, do),
    ] + ([_sds(_heads_shape(rows, B, Hkv, Tk, D2), jnp.float32,
               q, k, v, do)] if pair else [])
    kernel = _pair_refs(functools.partial(
        _mdkv_kernel, scale=scale, P=P, g=g, per_batch=per_batch,
        sub=sub and sub.grid),
        # q2 and k2 the last inputs; this kv head's part of dk2 after dk
        # and dv; its accumulator the last scratch
        pair, (1 + len(in_specs), 2), (len(out_specs), 1),
        (len(scratch_shapes), 1))
    if one:
        # dq and dq2 the last results, the group's whole rows at an index
        # that stands still along P; their accumulators the last scratch
        rows_blk = lambda d: _heads_block(rows, g, T, d,
                                          lambda b, c, p, t: (b, c, 0))
        kernel = _pair_refs(
            kernel, True, (1 + len(in_specs), 0), (len(out_specs) + 2, 2),
            (len(scratch_shapes) + 2, 2), name="dq")
        out_specs += [rows_blk(D), rows_blk(D2)]
        out_shape += [dq_shape, _sds(pair[0].shape, q.dtype, q, k, v, do)]
        scratch_shapes += [pltpu.VMEM((g, T, D), jnp.float32),
                           pltpu.VMEM((g, T, D2), jnp.float32)]
        # what the step holds and 4 MiB for Mosaic's own, not the 24 of
        # ``_vmem``: XLA keeps a call's limit clear of its own fast-memory
        # buffers (PERF.md, question 27)
        limit = _pallas.params(vmem=one + 4 * 1024 * 1024)
    else:
        limit = _vmem(g * bq * (D + Dv + D2) * item,
                      2 * bk * (D + Dv) * item + bk * D2 * (item + 4),
                      2 * g * T * 4, 8 * bq * 4,
                      scratch=bk * (D + Dv + D2) * 4)
    dk, dv, *others = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, Hkv, P),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=limit,
        interpret=_pallas.INTERPRET,
        name="hvd_flash_dqkv" if one else "hvd_flash_dkv",
    )(table, q, k, v, do, lse, delta, ranges.transpose(0, 2, 1),
      *(pair or ()))
    if not pair:
        return dq, dk, dv
    dk2, dq, dq2 = others if one else others + list(dq)
    dk2 = dk2.reshape(B, Tk, -1, g2 // g, D2).sum(3).astype(k.dtype)
    return dq, dk, dv, dq2, dk2.reshape(pair[1].shape)


class _StaticMask:
    """A mask known where the call is built, hashable so that it rides a
    ``custom_vjp`` as a static argument and stays numpy in both rules."""

    def __init__(self, ranges):
        self.ranges = np.ascontiguousarray(ranges, np.int32)
        self._hash = hash((self.ranges.shape, self.ranges.tobytes()))

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return (isinstance(other, _StaticMask)
                and self.ranges.shape == other.ranges.shape
                and bool((self.ranges == other.ranges).all()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _masked_attention_lse(q, k, v, mask, static, scale, widths):
    """``mask``: a traced mask, or None with ``static`` a _StaticMask;
    ``widths``: None, or ``(D, Dv)`` of operands in the caller's layout
    (:func:`_masked_fwd`)."""
    return _masked_fwd(q, k, v, static.ranges if static else mask, scale,
                       widths)


def _masked_attention_lse_fwd(q, k, v, mask, static, scale, widths):
    out, lse = _named_residuals(
        *_masked_attention_lse(q, k, v, mask, static, scale, widths))
    return (out, lse), (q, k, v, mask, out, lse)


def _masked_attention_lse_bwd(static, scale, widths, res, cotangents):
    do, dlse = cotangents
    q, k, v, mask, out, lse = res
    dq, dk, dv = _masked_bwd(
        q, k, v, out, lse, do, static.ranges if static else mask, scale,
        dlse, widths)
    return dq, dk, dv, None


_masked_attention_lse.defvjp(_masked_attention_lse_fwd,
                             _masked_attention_lse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _paired_attention_lse(q, k, v, q2, k2, mask, static, scale, widths):
    """:func:`_masked_attention_lse` with the second pair ``(q2 [B, T,
    H*D2], k2 [B, Tk, H2*D2])``, whole lane tiles a head, in the caller's
    rows."""
    return _masked_fwd(q, k, v, static.ranges if static else mask, scale,
                       widths, (q2, k2))


def _paired_attention_lse_fwd(q, k, v, q2, k2, mask, static, scale, widths):
    out, lse = _named_residuals(
        *_paired_attention_lse(q, k, v, q2, k2, mask, static, scale, widths))
    return (out, lse), (q, k, v, q2, k2, mask, out, lse)


def _paired_attention_lse_bwd(static, scale, widths, res, cotangents):
    do, dlse = cotangents
    q, k, v, q2, k2, mask, out, lse = res
    return _masked_bwd(
        q, k, v, out, lse, do, static.ranges if static else mask, scale,
        dlse, widths, (q2, k2)) + (None,)


_paired_attention_lse.defvjp(_paired_attention_lse_fwd,
                             _paired_attention_lse_bwd)


# ------------------------------------------------------------- public op
# The GQA group in _mdkv_kernel's q block assumes query heads of one kv
# group are contiguous (head h ↔ kv head h // g; in the caller's layout
# the group is g * D adjacent lanes), matching
# jnp.repeat(k, g, axis=head) semantics used across the framework.
# Each custom_vjp serves both entry points: the plain path is the lse path
# with a zero lse cotangent (folded into delta as a cheap subtract).

def _attention_lse(q, k, v, causal, sm_scale, mask=None, pair=None):
    """Both public entry points: ``(out [B,T,H,D], lse [B,H,T])`` by the
    packed path where there is no ``mask`` and :func:`_pack` takes these
    shapes, else by the masked path; with a second ``pair`` by the masked
    kernels built with it."""
    scale = float(sm_scale if sm_scale is not None else
                  (q.shape[-1] + (pair[0].shape[-1] if pair else 0)) ** -0.5)
    B, T, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if mask is None:
        pack = ((1, 1) if v.shape[-1] != D or pair else
                _pack(B, H, Hkv, T, Tk, D, q.dtype.itemsize))
        if pack != (1, 1):
            out, lse = _packed_attention_lse(
                q.reshape(B, T, H * D), k.reshape(B, Tk, Hkv * D),
                v.reshape(B, Tk, Hkv * D), bool(causal), scale, D, pack)
            return out.reshape(B, T, H, D), lse.reshape(B, H, T)
        mask = causal_ranges(T) if causal else full_ranges(T, Tk)
    static = _StaticMask(mask) if isinstance(mask, np.ndarray) else None
    Dv = v.shape[-1]
    widths = _row_widths(D, Dv)
    if pair:
        # a head of the second pair as whole lane tiles, zeros after its
        # own columns (module docstring, "a second pair")
        D2 = _padded(pair[0].shape[-1])
        q2, k2 = (jnp.pad(x, ((0, 0),) * 3 + ((0, D2 - x.shape[-1]),))
                  .reshape(x.shape[0], x.shape[1], -1) for x in pair)
        out, lse = _paired_attention_lse(
            q.reshape(B, T, H * D), k.reshape(B, Tk, Hkv * D),
            v.reshape(B, Tk, Hkv * Dv), q2, k2, None if static else mask,
            static, scale, widths)
        return out.reshape(B, T, H, Dv), lse.reshape(B, H, T)
    if widths:
        operands = (q.reshape(B, T, H * D), k.reshape(B, Tk, Hkv * D),
                    v.reshape(B, Tk, Hkv * Dv))
    else:
        operands = tuple(x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = _masked_attention_lse(*operands, None if static else mask,
                                     static, scale, widths)
    out = out.reshape(B, T, H, Dv) if widths else out.transpose(0, 2, 1, 3)
    return out, lse.reshape(B, H, T)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None, mask=None, pair=None):
    """Fused exact attention.  ``q [B,T,H,D]``, ``k [B,Tk,Hkv,D]``, ``v
    [B,Tk,Hkv,Dv]``; ``out [B,T,H,Dv]``.
    ``mask``: the ranges each query row sees (module docstring); given
    one, ``causal`` is not looked at.  ``pair``: a second query/key pair
    ``(q2 [B,T,H,D2], k2 [B,Tk,H2,D2])``, ``H2 | Hkv``, whose product is
    added to ``q k^T`` before the softmax (module docstring, "a second
    pair"); the default scale is then ``(D + D2) ** -0.5``."""
    return _attention_lse(q, k, v, causal, sm_scale, mask, pair)[0]


def flash_attention_lse(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None, mask=None):
    """Fused attention returning ``(out, lse)`` for tile merging.

    ``out [B,T,H,D]``, ``lse [B,H,T]`` (logsumexp of the masked scores per
    query row).  The ring-attention path merges per-step tiles computed by
    this kernel into its online-softmax accumulator; gradients flow
    through both outputs (the lse cotangent folds into the backward
    kernels' delta term).
    """
    return _attention_lse(q, k, v, causal, sm_scale, mask)
