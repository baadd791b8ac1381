"""Overlapped gradient dispatch: per-bucket collectives inside backprop.

ROADMAP item 3 (arXiv:2305.06942 fused computation-collective ops;
OptiReduce arXiv:2310.06993 on why the cross-host hop hurts most): the
non-overlapped in-jit path runs ``jax.value_and_grad`` to completion and
only then issues the fused per-bucket reductions, so every DCN
round-trip is pure exposed latency.  The models drive their layer
stacks with ``lax.scan`` — the backward pass therefore materializes
gradients one layer at a time, in reverse layer order, with the whole
remaining backprop still to run.  This module taps those gradients *as
they materialize*:

* :func:`grad_tap` — a ``custom_vjp`` identity the models apply to the
  per-layer parameter slice inside the scan body (and to the non-scanned
  leaves at the top of the loss).  Forward is exactly identity; the
  backward rule buckets the cotangent with the SAME ``plan_fusion``
  planner as every other path and dispatches each bucket's ``psum`` /
  ``psum_scatter`` right there — **inside the backward scan**, where XLA
  overlaps the transfer with the remaining backward compute.
* :func:`overlapped_backprop` — the trace-time context that arms the
  taps with a ``DistributedGradientTransform(overlap=True)``'s plan.
  Outside the context every tap is literally ``return tree`` (zero
  jaxpr impact: existing schedule snapshots stay byte-identical).
* the layer-aware plan — :class:`OverlapLayout` expands stacked
  ``[L, ...]`` leaves (the ``lax.scan`` xs under the ``"layers"``
  subtree) into per-layer :class:`~..ops.fusion.EntrySig` entries whose
  ``layer`` key keeps buckets from spanning layers, and carries the
  explicit reverse-layer :class:`~..ops.fusion.DispatchSchedule`.  The
  boundary path (taps not armed — the A/B baseline, and the safety net
  when a user forgets the context) executes the *identical* plan after
  backprop, so overlapped vs non-overlapped steps land on bit-identical
  weights — including under ``sharded_update`` and quantized wire
  formats, where bucket/block partitioning decides the bits.

Composition rules:

* ``sharded_update``: the tap fires the per-bucket ``psum_scatter`` in
  the backward scan and returns the cotangent with this worker's tile
  written into an otherwise-zero buffer (a ``custom_vjp`` cotangent must
  match the primal's shape); the transform carves the tiles back out at
  the step boundary — zero extra wire — runs the 1/N inner update, and
  the updates **allgather stays at the step boundary**.
* ``wire_format``: each early-dispatched bucket uses the block-scaled
  quantized staging (``quantized_allreduce_p`` / ``_sum_scatter_p``)
  WITHOUT error feedback — the residual is per-step optimizer state the
  backward pass cannot thread — and the transform's error-feedback
  residual is untouched (stays ``None``).  EQuARX measures int8 block
  scaling at near-zero quality cost even feedback-free; prefer the
  non-overlapped path when the residual matters more than the overlap.
* ``backward_passes_per_step > 1``: every tap collective is gated on
  the accumulation boundary (``lax.cond`` on a replicated predicate the
  context computes from ``state.count``), so intermediate micro-steps
  move ZERO gradient bytes; the boundary step reduces the accumulated
  (k-1)/k of the gradient mass at the step boundary and only the final
  backprop's share overlaps.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import weakref
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import metrics as _metrics
from .. import tracing as _tracing
from ..parallel.vma import as_varying
from ..runtime import ReduceOp

logger = logging.getLogger("horovod_tpu")

_m_buckets = _metrics.counter(
    "hvd_overlap_buckets_dispatched_total",
    "Fusion buckets staged for overlapped dispatch (trace-time: counted "
    "when a grad tap or the boundary fallback stages its collectives)",
    labels=("phase",))


# ---------------------------------------------------------------------------
# plan: which transform's dispatch the taps execute
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OverlapPlan:
    """The static dispatch recipe of one ``overlap=True`` transform.

    Built by ``DistributedGradientTransform`` and shared (same object)
    between its ``update_fn`` and the taps armed by
    :func:`overlapped_backprop` — one planner configuration, so the
    in-backprop and at-boundary executions of the plan are the same
    reviewable schedule.
    """
    axis_name: str
    op: str
    threshold_bytes: Optional[int]
    prescale: float
    postscale: float
    sharded: bool
    fmt: Any                      # compression.WireFormat or None
    k: int                        # backward_passes_per_step
    layers_key: str = "layers"
    # mesh-axis-aware dispatch (ISSUE 14): the transform's SpecPlan
    # (distributed.make_spec_plan) — per-leaf canonical PartitionSpecs
    # plus the model axes.  None = the 1-D replicated plan.
    spec_plan: Any = None
    # trace-time handshake: taps that fired since update_fn last looked
    # (Python counter, never traced), plus the gate predicate the
    # context armed them with (a tracer from the SAME trace update_fn
    # runs in, or None for unconditional dispatch)
    _fired: int = 0
    _fire: Any = None

    def consume_fired(self):
        """(tap count, gate predicate) since the last consume."""
        n, self._fired = self._fired, 0
        fire, self._fire = self._fire, None
        return n, fire

    def tap_specs(self):
        """Canonical spec lookup for TAP-level leaf names (None when the
        plan is not spec-aware).

        A tap sees SUB-trees of the params: the per-layer slice of the
        ``layers_key`` subtree (leaf paths lose the ``['layers']``
        prefix and the leading scan dim — specs shift down one
        dimension) and the root rest-dict (paths unchanged).  This
        merges both into one name->spec dict; a collision between a
        stripped layer path and a root path with DIFFERENT specs is
        ambiguous and raises (rename the leaf)."""
        if self.spec_plan is None:
            return None
        from ..ops.fusion import spec_shift
        prefix = f"['{self.layers_key}']"
        merged = {}
        for name, spec in self.spec_plan.by_name.items():
            if name.startswith(prefix):
                key, val = name[len(prefix):], spec_shift(spec)
            else:
                key, val = name, spec
            if key in merged and merged[key] != val:
                raise ValueError(
                    f"overlap + param_specs: tap-level leaf name "
                    f"{key} is ambiguous — a root leaf and a "
                    f"{self.layers_key!r} stack leaf share it with "
                    f"different specs ({merged[key]} vs {val}); "
                    f"rename one of the leaves")
            merged[key] = val
        return merged


#: transform update_fn -> OverlapPlan (weak: dies with the transform).
_TRANSFORMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def register_transform(update_fn, plan: OverlapPlan) -> None:
    _TRANSFORMS[update_fn] = plan


def plan_for(tx) -> OverlapPlan:
    """The :class:`OverlapPlan` of a transform built with
    ``overlap=True`` (raises for any other optax transformation)."""
    plan = _TRANSFORMS.get(getattr(tx, "update", None))
    if plan is None:
        raise ValueError(
            "overlapped_backprop() needs a DistributedGradientTransform/"
            "DistributedOptimizer built with overlap=True (or "
            "HOROVOD_OVERLAP=1) — this transformation has no overlap "
            "dispatch plan")
    return plan


class _ActiveDispatch:
    """Trace-time armed state while inside ``overlapped_backprop``."""

    def __init__(self, plan: OverlapPlan, fire):
        self.plan = plan
        self.fire = fire          # traced bool (k>1 gate) or None
        self.fired = 0            # taps traced under this context


_ACTIVE: Optional[_ActiveDispatch] = None


def active() -> bool:
    """True while an ``overlapped_backprop`` context is armed (trace
    time).  Models use this to keep the tap call sites zero-cost —
    outside a context :func:`grad_tap` returns its argument unchanged,
    so existing jaxprs (and schedule snapshots) are untouched."""
    return _ACTIVE is not None


@contextlib.contextmanager
def overlapped_backprop(tx, count=None, fire=None):
    """Arm the model-side grad taps with ``tx``'s dispatch plan.

    Wrap the ``jax.value_and_grad`` (or ``jax.grad``) call of the step::

        with hvd.overlapped_backprop(tx):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, state = tx.update(grads, state, params)

    With ``backward_passes_per_step > 1`` pass ``count=state.count`` so
    the taps gate on the accumulation boundary (they must move zero
    bytes on intermediate micro-steps); with ``k == 1`` taps fire
    unconditionally.  ``fire`` (k == 1 only) is an explicit traced
    boolean gate: the taps dispatch when it is true and the transform
    runs the identical plan at the boundary when it is false — ONE
    compiled program whose two branches are the overlapped and the
    non-overlapped schedule, which is what makes an A/B bit-exact (two
    separately compiled programs differ by fusion ulps; see
    tools/bench_overlap.py).  The context is trace-time only (a Python
    context manager around tracing) — it does not survive into the
    compiled program except as the collectives it placed there.

    Coverage contract: once ANY tap fires in a trace, ``update_fn``
    treats the whole gradient tree as pre-reduced — every parameter
    must be covered by exactly one tap (the bundled models tap the
    scanned stack per layer and everything else via ``tap_root``).  A
    custom model that taps only part of its tree leaves the rest
    unreduced; tap everything or nothing.  And the context must be
    followed by ``tx.update`` in the SAME traced step function: the
    fired-taps handshake is consumed there, so an armed backprop whose
    trace never reaches ``tx.update`` leaves it pending (arming a new
    context discards any unconsumed leftover, but a context-less
    ``tx.update`` in between would mistake its raw gradients for
    tapped ones).
    """
    global _ACTIVE
    plan = plan_for(tx)
    if _ACTIVE is not None:
        raise RuntimeError(
            "overlapped_backprop contexts do not nest: one backward "
            "pass has one dispatch plan")
    if plan.k > 1:
        if fire is not None:
            raise ValueError(
                "overlapped_backprop: with backward_passes_per_step > 1 "
                "the gate is the accumulation boundary — pass "
                "count=state.count, not an explicit fire")
        if count is None:
            raise ValueError(
                f"overlapped_backprop: backward_passes_per_step="
                f"{plan.k} gates the tap dispatch on the accumulation "
                f"boundary — pass count=state.count (the _DistState "
                f"counter) so the gate predicate matches the "
                f"transform's")
        fire = (count + 1) % plan.k == 0
    if plan._fired:
        # an earlier armed trace never reached tx.update (its
        # handshake was never consumed) — a new context supersedes it;
        # carrying it over would poison this trace's update with a
        # stale count (and a dead fire tracer)
        logger.warning(
            "overlapped_backprop: discarding an unconsumed tap "
            "handshake from a previous armed trace — arm the context "
            "and call tx.update in the SAME traced step function")
        plan.consume_fired()
    token = _ActiveDispatch(plan, fire)
    _ACTIVE = token
    try:
        yield token
    except BaseException:
        # the trace failed mid-backprop: do NOT commit the handshake —
        # a stale fired count would make the next (context-less) trace
        # treat raw gradients as pre-reduced, and a stale fire gate is
        # a dead tracer from the failed trace
        _ACTIVE = None
        raise
    _ACTIVE = None
    plan._fired += token.fired
    plan._fire = token.fire
    if token.fired == 0:
        logger.warning(
            "overlapped_backprop: no grad taps fired inside the "
            "context — the model's backward pass has no tap sites "
            "(models.llama/models.bert tap their scanned layers; "
            "custom models must call optim.overlap.grad_tap), so "
            "the reduction will run un-overlapped at the step "
            "boundary")


# ---------------------------------------------------------------------------
# layer-aware layout: stacked [L, ...] leaves -> per-layer plan entries
# ---------------------------------------------------------------------------

class OverlapEntry(NamedTuple):
    leaf_pos: int                 # index into the path-sorted leaves
    layer: int                    # -1 = whole leaf (no layer identity)


class OverlapLayout(NamedTuple):
    """Static layer-aware plan of one gradient tree.

    Mirrors ``distributed.ShardedLayout`` but over per-layer entries:
    every stacked leaf under ``layers_key`` contributes one entry per
    layer (``layer`` rides the EntrySig bucket key, so buckets never
    span layers), the rest one whole-leaf entry at ``layer=-1``.
    ``dispatch`` is the explicit reverse-layer dispatch order the
    backward scan realizes structurally and the boundary path executes
    explicitly.
    """
    treedef: Any
    order: Tuple[int, ...]                 # _tree_leaves_sorted permutation
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    entries: Tuple[OverlapEntry, ...]
    entry_shapes: Tuple[Tuple[int, ...], ...]
    buckets: Tuple[Any, ...]               # ops.fusion.BucketLayout
    dispatch: Any                          # ops.fusion.DispatchSchedule
    bucket_wire: Tuple[str, ...]           # wire format name per bucket
    bucket_spec: Tuple[str, ...] = ()      # canonical spec per bucket

    def fingerprint(self) -> Tuple:
        """Static identity for grads-vs-params layout validation."""
        return (self.entries, self.entry_shapes, self.buckets,
                self.bucket_spec)


def _is_layered(keystr: str, leaf, layers_key: str) -> bool:
    return (keystr.startswith(f"['{layers_key}']")
            and getattr(leaf, "ndim", 0) >= 1)


def build_layout(tree, plan: OverlapPlan, shards: int,
                 force_root: bool = False) -> Tuple[list, OverlapLayout]:
    """Plan ``tree`` for layer-aware dispatch.

    ``shards`` is the mesh-axis size (1 when the buckets will be
    full-width allreduced rather than reduce-scattered).  With
    ``force_root`` every leaf is a single ``layer=-1`` entry — the shape
    a per-layer tap tree has (inside the scan body each leaf IS one
    layer's slice).  Returns ``(path_sorted_leaves, layout)``.
    """
    from ..compression import quantizable
    from ..ops.fusion import (EntrySig, plan_bucket_layouts, plan_dispatch,
                              plan_fusion, spec_shift)
    from .distributed import _resolve_threshold, _tree_leaves_sorted
    leaves, names, order = _tree_leaves_sorted(tree)
    threshold = _resolve_threshold(plan.threshold_bytes)
    n_layers = None
    entries = []
    sigs = []
    # spec resolution: tap sub-trees (force_root) use tap-level names,
    # the boundary full tree uses full paths with stacked leaves'
    # per-layer entries carrying the dim-shifted spec (so the tap plan
    # and the boundary plan bucket IDENTICALLY — one schedule)
    spec_of = (None if plan.spec_plan is None
               else (plan.tap_specs() if force_root
                     else plan.spec_plan.by_name))

    def _leaf_spec(pos, layered):
        if spec_of is None:
            return "replicated"
        spec = spec_of.get(names[pos])
        if spec is None:
            raise ValueError(
                f"overlap + param_specs: no spec entry for leaf "
                f"{names[pos]} — the spec tree must be congruent with "
                f"the param tree (every leaf needs a PartitionSpec, "
                f"None for replicated)")
        return spec_shift(spec) if layered else spec

    def add(pos, layer, shape, spec="replicated"):
        leaf = leaves[pos]
        entries.append(OverlapEntry(leaf_pos=pos, layer=layer))
        sigs.append(EntrySig(
            name=names[pos], op_type="allreduce", reduce_op=str(plan.op),
            dtype=str(leaf.dtype), shape=tuple(shape), process_set_id=0,
            stacked=False, prescale=plan.prescale,
            postscale=plan.postscale,
            wire_format=(plan.fmt.name if plan.fmt is not None
                         and quantizable(leaf.dtype) else "none"),
            layer=layer, spec=spec))

    for pos, leaf in enumerate(leaves):
        if not force_root and _is_layered(names[pos], leaf,
                                          plan.layers_key):
            if n_layers is None:
                n_layers = int(leaf.shape[0])
            elif int(leaf.shape[0]) != n_layers:
                raise ValueError(
                    f"overlap: stacked leaves under "
                    f"{plan.layers_key!r} disagree on the layer count "
                    f"({n_layers} vs {leaf.shape[0]} at {names[pos]}) — "
                    f"the scanned stack must share one leading dim")
            spec = _leaf_spec(pos, layered=True)
            for layer in range(n_layers):
                add(pos, layer, leaf.shape[1:], spec=spec)
        else:
            add(pos, -1, leaf.shape, spec=_leaf_spec(pos, layered=False))
    buckets = plan_fusion(sigs, threshold)
    align = plan.fmt.block_size if plan.fmt is not None else 1
    layouts = plan_bucket_layouts(sigs, buckets, max(shards, 1),
                                  align=align)
    return leaves, OverlapLayout(
        treedef=jax.tree_util.tree_structure(tree), order=tuple(order),
        leaf_shapes=tuple(tuple(l.shape) for l in leaves),
        entries=tuple(entries),
        entry_shapes=tuple(s.shape for s in sigs),
        buckets=tuple(layouts),
        dispatch=plan_dispatch(sigs, buckets),
        # mixed formats/specs never fuse (both are in bucket_key), so
        # the first entry speaks for its whole bucket
        bucket_wire=tuple(sigs[b[0]].wire_format for b in buckets),
        bucket_spec=tuple(sigs[b[0]].spec for b in buckets))


def _entry_flat(leaves, layout: OverlapLayout, i: int):
    e = layout.entries[i]
    leaf = leaves[e.leaf_pos]
    return (leaf if e.layer < 0 else leaf[e.layer]).reshape(-1)


def _bucket_buf(leaves, layout: OverlapLayout, bucket_id: int):
    bl = layout.buckets[bucket_id]
    parts = [_entry_flat(leaves, layout, i) for i in bl.indices]
    buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if bl.padded_numel != bl.numel:
        buf = jnp.pad(buf, (0, bl.padded_numel - bl.numel))
    return buf


def _assemble(pieces, layout: OverlapLayout):
    """Per-entry flat pieces -> the full pytree (stack layered leaves)."""
    from .distributed import _restore_order
    by_leaf = [None] * len(layout.leaf_shapes)
    for i, piece in enumerate(pieces):
        e = layout.entries[i]
        shaped = piece.reshape(layout.entry_shapes[i])
        if e.layer < 0:
            by_leaf[e.leaf_pos] = shaped
        else:
            if by_leaf[e.leaf_pos] is None:
                by_leaf[e.leaf_pos] = [None] * \
                    layout.leaf_shapes[e.leaf_pos][0]
            by_leaf[e.leaf_pos][e.layer] = shaped
    out = [jnp.stack(x) if isinstance(x, list) else x for x in by_leaf]
    return jax.tree_util.tree_unflatten(
        layout.treedef, _restore_order(out, list(layout.order)))


def _split_entries(red, layout: OverlapLayout, bucket_id: int, pieces):
    bl = layout.buckets[bucket_id]
    off = 0
    for i, sz in zip(bl.indices, bl.sizes):
        pieces[i] = lax.slice_in_dim(red, off, off + sz)
        off += sz


# ---------------------------------------------------------------------------
# plan execution (shared by the taps and the boundary fallback)
# ---------------------------------------------------------------------------

def reduce_full(tree, plan: OverlapPlan, force_root: bool = False):
    """Full-width reduction of ``tree`` under the layer-aware plan, in
    explicit dispatch order — value-identical to the taps' in-backprop
    dispatch (same buckets, same staging, same scale order)."""
    t_stage = _tracing.now() if _tracing.ACTIVE else 0.0
    leaves, layout = build_layout(tree, plan, shards=1,
                                  force_root=force_root)
    if not leaves:
        return tree
    sp = plan.spec_plan
    global_n = sp.global_size() if sp is not None else None
    pieces = [None] * len(layout.entries)
    for bucket_id in layout.dispatch.order:
        with jax.named_scope(f"hvd_bucket{bucket_id}"):
            buf = _bucket_buf(leaves, layout, bucket_id)
            if plan.prescale != 1.0:
                buf = buf * jnp.asarray(plan.prescale, buf.dtype)
            # spec-aware: the bucket reduces over (data + model axes)
            # minus its spec's axes — a model-sharded bucket's
            # cotangent is the locally-owned shard, pre-reduced over
            # the model axes by the model's gather-transpose
            if sp is not None:
                r_axes = sp.reduce_axes(layout.bucket_spec[bucket_id]
                                        if layout.bucket_spec
                                        else "replicated")
            else:
                r_axes = (plan.axis_name,)
            if plan.fmt is not None \
                    and layout.bucket_wire[bucket_id] != "none" \
                    and plan.axis_name in r_axes:
                from ..ops.collectives import quantized_allreduce_p
                m_axes = tuple(a for a in r_axes if a != plan.axis_name)
                if m_axes:
                    # replicated bucket on a multi-axis mesh: the
                    # model hop runs full-width, only the data (DCN)
                    # hop quantizes
                    buf = lax.psum(buf, m_axes)
                red, _ = quantized_allreduce_p(buf, plan.axis_name,
                                               plan.fmt, op=plan.op,
                                               denom=global_n)
            else:
                red = lax.psum(buf, r_axes) if r_axes else buf
                if plan.op == ReduceOp.AVERAGE:
                    red = red / (lax.axis_size(plan.axis_name)
                                 if global_n is None else global_n)
            if plan.postscale != 1.0:
                red = red * jnp.asarray(plan.postscale, red.dtype)
            _split_entries(red, layout, bucket_id, pieces)
    if _metrics.ACTIVE:
        _m_buckets.inc(len(layout.buckets),
                       phase="bwd" if active() else "boundary")
    if _tracing.ACTIVE:
        # TRACE-TIME span (round=-1: never on a runtime round's
        # critical path — the dispatch itself runs inside the compiled
        # program): records when and how the overlap plan staged its
        # buckets, the in-jit analog of the engine's dispatch spans
        _tracing.span("overlap", "reduce_full", t_stage, _tracing.now(),
                      round=-1, phase="bwd" if active() else "boundary",
                      buckets=len(layout.buckets))
    return _assemble(pieces, layout)


def scatter_tiles(tree, plan: OverlapPlan, force_root: bool = False,
                  layout: Optional[OverlapLayout] = None):
    """Reduce-scatter ``tree`` under the layer-aware plan: one tile per
    bucket (plan order), plus the layout.  The sharded-update half of
    :func:`reduce_full` — same buckets, ``psum_scatter`` (or the
    quantized sum-scatter staging) instead of ``psum``.  Pass a
    prebuilt ``layout`` to skip re-planning (it must come from this
    plan over a same-shaped tree)."""
    t_stage = _tracing.now() if _tracing.ACTIVE else 0.0
    if layout is None:
        leaves, layout = build_layout(tree, plan,
                                      shards=lax.axis_size(plan.axis_name),
                                      force_root=force_root)
    else:
        from .distributed import _tree_leaves_sorted
        leaves, _names, _order = _tree_leaves_sorted(tree)
    sp = plan.spec_plan
    global_n = sp.global_size() if sp is not None else None
    tiles = [None] * len(layout.buckets)
    for bucket_id in layout.dispatch.order:
        with jax.named_scope(f"hvd_bucket{bucket_id}"):
            buf = _bucket_buf(leaves, layout, bucket_id)
            if plan.prescale != 1.0:
                buf = buf * jnp.asarray(plan.prescale, buf.dtype)
            if sp is not None:
                # replicated buckets psum their model hop first; a
                # model-sharded bucket's buffer is the local shard and
                # only the data-axis scatter remains (a spec naming
                # the data axis itself is refused at transform build)
                m_axes = tuple(
                    a for a in sp.reduce_axes(
                        layout.bucket_spec[bucket_id]
                        if layout.bucket_spec else "replicated")
                    if a != plan.axis_name)
                if m_axes:
                    buf = lax.psum(buf, m_axes)
            if plan.fmt is not None \
                    and layout.bucket_wire[bucket_id] != "none":
                from ..ops.collectives import quantized_sum_scatter_p
                tile, _ = quantized_sum_scatter_p(
                    buf.astype(jnp.float32), plan.axis_name, plan.fmt)
                tile = tile.astype(buf.dtype)
            else:
                tile = lax.psum_scatter(buf, plan.axis_name,
                                        scatter_dimension=0, tiled=True)
            if plan.op == ReduceOp.AVERAGE:
                tile = tile / (lax.axis_size(plan.axis_name)
                               if global_n is None else global_n)
            if plan.postscale != 1.0:
                tile = tile * jnp.asarray(plan.postscale, tile.dtype)
            tiles[bucket_id] = tile
    if _metrics.ACTIVE:
        _m_buckets.inc(len(layout.buckets),
                       phase="bwd" if active() else "boundary")
    if _tracing.ACTIVE:
        # trace-time overlap staging span (see reduce_full)
        _tracing.span("overlap", "scatter_tiles", t_stage,
                      _tracing.now(), round=-1,
                      phase="bwd" if active() else "boundary",
                      buckets=len(layout.buckets))
    return tuple(tiles), layout


def scatter_place(tree, plan: OverlapPlan, force_root: bool = False):
    """Reduce-scatter, with each tile written back into an
    otherwise-zero buffer of the bucket's full (padded) size and split
    to the tree's shapes — the form a ``custom_vjp`` cotangent must
    take (primal-shaped).  ``carve_tiles`` recovers the tiles exactly;
    the zero regions are never read."""
    tiles, layout = scatter_tiles(tree, plan, force_root=force_root)
    idx = lax.axis_index(plan.axis_name)
    pieces = [None] * len(layout.entries)
    for bucket_id, (bl, tile) in enumerate(zip(layout.buckets, tiles)):
        full = jnp.zeros((bl.padded_numel,), tile.dtype)
        full = lax.dynamic_update_slice_in_dim(
            full, tile, idx * bl.shard_numel, 0)
        _split_entries(full, layout, bucket_id, pieces)
    return _assemble(pieces, layout)


def carve_tiles(tree, plan: OverlapPlan, layout: Optional[OverlapLayout]
                = None):
    """This worker's per-bucket tiles of ``tree`` (no collectives):
    flatten each bucket under the layout and slice
    ``[idx*shard : (idx+1)*shard]``.  Applied to tap-placed gradients it
    recovers exactly the reduce-scattered tiles; applied to (replicated)
    params it carves the tile the 1/N inner update runs against."""
    if layout is None:
        leaves, layout = build_layout(tree, plan,
                                      shards=lax.axis_size(plan.axis_name))
    else:
        from .distributed import _tree_leaves_sorted
        leaves, _names, _order = _tree_leaves_sorted(tree)
    idx = lax.axis_index(plan.axis_name)
    tiles = []
    for bucket_id, bl in enumerate(layout.buckets):
        buf = _bucket_buf(leaves, layout, bucket_id)
        tiles.append(lax.dynamic_slice_in_dim(
            buf, idx * bl.shard_numel, bl.shard_numel))
    return tuple(tiles), layout


def gather_updates(tiles, layout: OverlapLayout, plan: OverlapPlan):
    """Rebuild the full updates tree from per-bucket tiles: ONE tiled
    full-width ``all_gather`` per bucket at the step boundary (the
    overlapped mode never early-dispatches the updates gather — they do
    not exist until the inner update ran)."""
    if len(tiles) != len(layout.buckets):
        raise ValueError(
            f"got {len(tiles)} tile(s) for a layout of "
            f"{len(layout.buckets)} bucket(s) — tiles and layout come "
            f"from different plans")
    pieces = [None] * len(layout.entries)
    for bucket_id, (bl, tile) in enumerate(zip(layout.buckets, tiles)):
        with jax.named_scope(f"hvd_bucket{bucket_id}"):
            full = lax.all_gather(tile, plan.axis_name, axis=0,
                                  tiled=True)
            _split_entries(full, layout, bucket_id, pieces)
    return _assemble(pieces, layout)


# ---------------------------------------------------------------------------
# the grad tap
# ---------------------------------------------------------------------------

def _tap_dispatch(ct_tree, plan: OverlapPlan):
    """The backward-side dispatch of one tap's cotangent tree (a
    per-layer slice inside the backward scan, or the root leaves at the
    end of backprop)."""
    if plan.sharded:
        return scatter_place(ct_tree, plan, force_root=True)
    return reduce_full(ct_tree, plan, force_root=True)


def grad_tap(tree):
    """Identity on the forward pass; inside an armed
    :func:`overlapped_backprop` context the backward rule dispatches the
    cotangent's fusion buckets immediately — see the module docstring.
    Models call this on the per-layer parameter slice inside their
    ``lax.scan`` body and on the non-scanned leaves at the top of the
    loss (:func:`tap_root`); outside a context it returns ``tree``
    unchanged (no custom_vjp node, no jaxpr change)."""
    token = _ACTIVE
    if token is None or not jax.tree_util.tree_leaves(tree):
        return tree
    plan = token.plan
    token.fired += 1

    if token.fire is None:
        @jax.custom_vjp
        def tap(t):
            return t

        def fwd(t):
            return t, None

        def bwd(_res, ct):
            return (_tap_dispatch(ct, plan),)

        tap.defvjp(fwd, bwd)
        return tap(tree)

    # k>1: gate every collective on the accumulation boundary.  The
    # predicate is replicated (the step counter is), so every replica
    # takes the same branch and the dispatch schedule stays consistent.
    @jax.custom_vjp
    def gated_tap(fire, t):
        return t

    def gfwd(fire, t):
        return t, fire

    def gbwd(fire, ct):
        red = lax.cond(
            fire,
            lambda c: as_varying(_tap_dispatch(c, plan), plan.axis_name),
            lambda c: c, ct)
        # fire is boolean: its cotangent is the zero of float0
        return (np.zeros((), dtype=jax.dtypes.float0), red)

    gated_tap.defvjp(gfwd, gbwd)
    return gated_tap(token.fire, tree)


def tap_root(params, layers_key: Optional[str] = None):
    """Tap every non-scanned top-level leaf of ``params`` as ONE tap.

    The scanned stack (under ``layers_key``, default: the armed plan's
    ``layers_key`` so the exclusion always matches the transform's
    ``overlap_layers``) is tapped per layer inside the scan body;
    everything else (embeddings, final norms, heads) is tapped together
    here so the root leaves fuse into the same buckets the boundary
    plan gives them — and because the tap wraps the VALUE, every use
    (e.g. a tied embedding appearing in both the lookup and the loss
    head) contributes to one cotangent before the dispatch fires.
    No-op outside an armed context; inside one, ``params`` must be a
    dict (a silent pass-through would leave the root gradients
    unreduced while ``update_fn`` treats the whole tree as tapped —
    replica divergence, not graceful degradation).
    """
    if _ACTIVE is None:
        return params
    if not isinstance(params, dict):
        raise TypeError(
            f"tap_root needs a dict param tree to split the scanned "
            f"stack from the root leaves, got {type(params).__name__}: "
            f"tap the non-scanned leaves explicitly with grad_tap "
            f"(every leaf must be covered by exactly one tap, or its "
            f"gradient is never reduced)")
    if layers_key is None:
        layers_key = _ACTIVE.plan.layers_key
    rest = {k: v for k, v in params.items() if k != layers_key}
    if not rest:
        return params
    tapped = grad_tap(rest)
    merged = dict(params)
    merged.update(tapped)
    return merged
