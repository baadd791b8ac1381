"""Distributed optimizer: fused cross-worker gradient reduction for optax.

Reference parity: ``horovod/torch/optimizer.py`` ``DistributedOptimizer``
(SURVEY.md §3.3) — per-parameter gradient hooks fire async allreduces which
are fusion-buffered by the background loop, then ``synchronize()`` blocks
before ``step()``; supports ``backward_passes_per_step`` (local gradient
accumulation), compression, prescale/postscale, Adasum, and process sets.

TPU redesign: the training step is one compiled SPMD program, so gradient
reduction belongs *inside* the program where XLA can overlap it with the
backward pass.  ``DistributedOptimizer`` is an optax gradient
transformation: when used inside a jit/shard_map step over the worker mesh
(``axis_name=...``), gradients are deterministically bucketed by dtype up
to the fusion threshold, each bucket is flattened/concatenated and reduced
with ONE ``psum`` over ICI, then split back — the fusion buffer as a
compiler construct.  Outside jit it falls back to the eager engine's
grouped allreduce, preserving the reference's async-hook semantics.

ZeRO-style sharded update (``sharded_update=True`` /
``HOROVOD_SHARDED_UPDATE``, arXiv:2004.13336 "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training"): instead of
materializing the FULL reduced gradient and full optimizer state on every
worker, each bucket is **reduce-scattered** (same total bytes on the wire
as a tree allreduce), the inner optax update runs on this worker's 1/N
tile against 1/N-sized moment state, and ONE **allgather** per bucket
rebuilds the updated flat buffer.  Per-chip optimizer compute and state
drop N×; params stay replicated (ZeRO stage "weight update sharding").
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import chaos as _chaos
from .. import runtime
from ..compression import Compression, resolve_wire_format
from ..parallel.vma import as_varying
from ..runtime import ReduceOp


def _tree_leaves_sorted(tree):
    """Leaves in deterministic path-sorted order (the controller's total
    order on tensor names, applied at trace time).

    Returns ``(leaves, names, order)`` where ``order[pos]`` is the
    ``tree_leaves`` index of the ``pos``-th sorted leaf: the permutation
    from the single path walk, which ``_restore_order`` inverts instead
    of re-walking and re-sorting the paths (this runs per recompile)."""
    keyed = jax.tree_util.tree_leaves_with_path(tree)
    order = sorted(range(len(keyed)),
                   key=lambda i: jax.tree_util.keystr(keyed[i][0]))
    return ([keyed[i][1] for i in order],
            [jax.tree_util.keystr(keyed[i][0]) for i in order],
            order)


def fused_reduce_tree(grads, axis_name: str, op: str = ReduceOp.AVERAGE,
                      threshold_bytes: Optional[int] = None,
                      compression=Compression.none,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      wire_format=None, residual=None, health=None,
                      spec_plan=None):
    """Reduce a gradient pytree across ``axis_name`` with bucket fusion.

    The in-jit analog of the reference's fusion buffer: leaves are bucketed
    by dtype in deterministic order up to ``threshold_bytes``
    (HOROVOD_FUSION_THRESHOLD), each bucket reduced with one ``psum``.

    The buckets come from the SAME planner the eager engine uses
    (``ops/fusion.py`` ``plan_fusion``) — one bucketing algorithm, one
    cross-process ordering contract — and each bucket's collective is
    traced under a ``jax.named_scope("hvd_bucket<i>")`` so the static
    schedule extractor (``tools/hvdsched``, ``analysis/schedule.py``) can
    attribute every ``psum`` in the jaxpr to its fusion bucket.

    ``wire_format`` (a name or :class:`~..compression.WireFormat`)
    switches every bucket from the full-width psum to the block-scaled
    quantized staging (``ops.collectives.quantized_allreduce_p``):
    quantize → exchange tiles + scales → dequantize-accumulate in fp32.
    ``residual`` is the grads-shaped error-feedback tree (this worker's
    carried quantization error, fp32; None = zeros); when a wire format
    is active the return value becomes ``(reduced_tree, new_residual)``.

    ``health`` is an optional :class:`~..health.taps.HealthTaps`
    context: each bucket's LOCAL (pre-reduction) flat buffer feeds the
    numerics tap (l2 / max-abs / nonfinite — attribution needs the
    contributor, not the smeared post-psum result), and the new
    error-feedback residual feeds the drift check.  Independently, the
    ``collective.corrupt`` chaos site (guarded on ``chaos.ACTIVE``) may
    bake a chosen rank's NaN/scale corruption into a chosen bucket —
    the deterministic fault every health verdict is tested against.

    ``spec_plan`` (a :class:`SpecPlan`) makes the reduction
    mesh-axis-aware (ISSUE 14): each leaf's canonical PartitionSpec
    rides its EntrySig — differently-sharded leaves never fuse — and a
    bucket reduces over ``(data_axis,) + model_axes`` MINUS its spec's
    axes (a model-sharded leaf's gradient arrives pre-reduced over its
    spec axes via the model's gather-transpose, and is the locally-
    owned shard: no full-width buffer is ever materialized here).
    ``op=Average`` divides by the GLOBAL batch degree — the batch
    shards over data and model axes alike.  With a ``wire_format`` only
    the DATA-axis (DCN) hop quantizes; any model-axis hop of a
    replicated bucket runs full-width first (those buckets hold the
    small unsharded leaves).
    """
    threshold_bytes = _resolve_threshold(threshold_bytes)
    fmt = resolve_wire_format(wire_format)
    leaves, _names, order = _tree_leaves_sorted(grads)
    if not leaves:
        # an empty gradient pytree has nothing to reduce on ANY op path;
        # return it unchanged rather than handing None to a collective
        return grads if fmt is None else (grads, residual)
    treedef = jax.tree_util.tree_structure(grads)

    if spec_plan is not None and op not in (ReduceOp.AVERAGE,
                                            ReduceOp.SUM):
        raise ValueError(
            f"spec-aware reduction (param_specs) supports op=Average/"
            f"Sum, got {op!r}: the per-bucket axis-set factoring relies "
            f"on sum linearity")
    if op == ReduceOp.ADASUM:
        if fmt is not None:
            raise ValueError(
                "wire_format quantization is not supported with "
                "op=Adasum: the recursive pairwise dot products operate "
                "on the exact local gradients and are not expressible as "
                "a quantize-exchange-accumulate staging — use "
                "op=Average/Sum with a wire format, or Adasum full-width")
        if compression not in (None, Compression.none):
            raise ValueError(
                "compression is not supported with op=Adasum: the "
                "recursive pairwise dot products operate on the exact "
                "local gradients, and silently skipping the compressor "
                "would diverge from the psum path's wire format — use "
                "op=Average/Sum with compression, or Adasum uncompressed")
        from ..ops.adasum import adasum_p
        dorder = sorted(range(len(leaves)),
                        key=lambda i: (str(leaves[i].dtype), i))
        flat_all = jnp.concatenate([leaves[i].reshape(-1) for i in dorder])
        red = adasum_p(flat_all * prescale_factor if prescale_factor != 1.0
                       else flat_all, axis_name)
        out = [None] * len(leaves)
        off = 0
        for i in dorder:
            sz = leaves[i].size
            out[i] = red[off:off + sz].reshape(leaves[i].shape)
            off += sz
        if postscale_factor != 1.0:
            out = [o * postscale_factor for o in out]
        return jax.tree_util.tree_unflatten(
            treedef, _restore_order(out, order))

    if fmt is not None and compression not in (None, Compression.none):
        raise ValueError(
            "wire_format and compression are two definitions of the same "
            "wire: pick the block-scaled quantized format OR the cast "
            "compressor, not both")

    specs = (spec_plan.specs_for(_names) if spec_plan is not None
             else None)
    buckets, _sigs = _plan_buckets(leaves, _names, op, prescale_factor,
                                   postscale_factor, threshold_bytes,
                                   wire_format=fmt.name if fmt else "none",
                                   specs=specs)
    global_n = spec_plan.global_size() if spec_plan is not None else None

    res_leaves = _residual_leaves(residual, leaves) if fmt is not None \
        else None
    out = [None] * len(leaves)
    new_res = [None] * len(leaves) if fmt is not None else None
    for bucket_id, bucket in enumerate(buckets):
        with jax.named_scope(f"hvd_bucket{bucket_id}"):
            parts = [leaves[i].reshape(-1) for i in bucket]
            buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            if _chaos.ACTIVE:
                from ..health.taps import chaos_corrupt
                buf = chaos_corrupt(buf, axis_name, bucket_id,
                                    _names[bucket[0]])
            if health is not None:
                health.observe_bucket(bucket_id, _names[bucket[0]], buf)
            if prescale_factor != 1.0:
                buf = buf * jnp.asarray(prescale_factor, buf.dtype)
            # the bucket's reduce-axis set: everything in the default
            # path; under a spec plan the data axis + the model axes
            # its (shared) spec does NOT already shard over
            if spec_plan is not None:
                r_axes = spec_plan.reduce_axes(_sigs[bucket[0]].spec)
            else:
                r_axes = (axis_name,)
            quantize = (fmt is not None
                        and _sigs[bucket[0]].wire_format != "none"
                        and axis_name in r_axes)
            if quantize:
                from ..ops.collectives import quantized_allreduce_p
                rparts = [res_leaves[i].reshape(-1) for i in bucket]
                rbuf = (jnp.concatenate(rparts) if len(rparts) > 1
                        else rparts[0])
                m_axes = tuple(a for a in r_axes if a != axis_name)
                if m_axes:
                    # replicated bucket on a multi-axis mesh: the
                    # model-axis hop runs full-width (these buckets
                    # hold the small unsharded leaves); only the
                    # data (DCN) hop quantizes
                    buf = jax.lax.psum(buf, m_axes)
                red, nres = quantized_allreduce_p(
                    buf, axis_name, fmt, op=op, residual=rbuf,
                    error_feedback=True, denom=global_n)
                if health is not None:
                    health.observe_residual(bucket_id, nres)
            else:
                wire, ctx = compression.compress(buf)
                red = jax.lax.psum(wire, r_axes) if r_axes else wire
                red = compression.decompress(red, ctx)
                if op == ReduceOp.AVERAGE:
                    red = red / (jax.lax.axis_size(axis_name)
                                 if global_n is None else global_n)
                # a bucket whose spec shards over the data axis itself
                # (1-D FSDP) arrived fully reduced: r_axes is empty, no
                # collective ran, only the Average normalization
                # applies.  nres=None carries any residual through
                # unchanged below — nothing was quantized.
                nres = None
            if postscale_factor != 1.0:
                red = red * jnp.asarray(postscale_factor, red.dtype)
            off = 0
            for i in bucket:
                sz = leaves[i].size
                out[i] = jax.lax.slice_in_dim(red, off, off + sz).reshape(
                    leaves[i].shape)
                if new_res is not None:
                    # non-quantizable buckets under a quantized transform
                    # carry their (zero) residual through unchanged
                    new_res[i] = (jax.lax.slice_in_dim(
                        nres, off, off + sz).reshape(leaves[i].shape)
                        if nres is not None else res_leaves[i])
                off += sz
    # out is in path-sorted leaf order; restore original leaf order
    reduced = jax.tree_util.tree_unflatten(
        treedef, _restore_order(out, order))
    if fmt is None:
        return reduced
    return reduced, jax.tree_util.tree_unflatten(
        treedef, _restore_order(new_res, order))


def _residual_leaves(residual, leaves):
    """Path-sorted fp32 error-feedback leaves aligned with ``leaves``
    (None → zeros: the first quantized step starts with no carried
    error)."""
    if residual is None:
        return [jnp.zeros(l.shape, jnp.float32) for l in leaves]
    r_leaves, _names, _order = _tree_leaves_sorted(residual)
    if len(r_leaves) != len(leaves):
        raise ValueError(
            f"error-feedback residual tree has {len(r_leaves)} leaves "
            f"for {len(leaves)} gradient leaves — the residual must be "
            f"carried from the previous step's return of the same tree")
    return r_leaves


class SpecPlan(NamedTuple):
    """Static mesh-axis plan of one spec-aware transform (ISSUE 14).

    ``by_name`` maps a leaf's path keystr to its canonical PartitionSpec
    fingerprint (``ops.fusion.canonicalize_spec``); ``model_axes`` are
    the parameter-sharding mesh axes beside the data axis.  The plan is
    pure trace-time metadata: the contract it encodes is that a leaf's
    gradient arrives PRE-reduced over every axis its spec shards over
    (the model's gather-transpose collectives did that) and partial
    over the rest, so a bucket's reduction runs over
    ``(data_axis,) + model_axes`` minus its spec's axes — and an
    ``op=Average`` divides by the GLOBAL batch degree (the product of
    all axis sizes: the batch shards over data and model axes alike).
    """
    by_name: Any                       # dict keystr -> canonical spec
    model_axes: Tuple[str, ...]
    data_axis: str

    def specs_for(self, names):
        """Canonical spec per path-sorted gradient leaf name."""
        out = []
        for n in names:
            spec = self.by_name.get(n)
            if spec is None:
                raise ValueError(
                    f"param_specs has no entry for gradient leaf {n}: "
                    f"the spec tree must be congruent with the "
                    f"gradient/param pytree (every leaf needs a "
                    f"PartitionSpec, None for replicated)")
            out.append(spec)
        return out

    def reduce_axes(self, spec: str) -> Tuple[str, ...]:
        """The axes a bucket with canonical ``spec`` reduces over."""
        from ..ops.fusion import spec_axes
        shard = set(spec_axes(spec))
        return tuple(a for a in (self.data_axis,) + self.model_axes
                     if a not in shard)

    def global_size(self) -> int:
        """Trace-time global batch degree (prod of all axis sizes)."""
        n = 1
        for a in (self.data_axis,) + self.model_axes:
            n *= jax.lax.axis_size(a)
        return n


def make_spec_plan(param_specs, data_axis: str,
                   model_axes=None) -> SpecPlan:
    """Canonicalize a param PartitionSpec pytree into a :class:`SpecPlan`.

    ``model_axes`` defaults to the union of axes the specs name plus the
    validated ``HOROVOD_MODEL_AXES`` config (sorted by name — a
    deterministic cross-process order), minus the data axis.  The data
    axis may appear in a spec (an FSDP leaf sharded over the data axis
    itself arrives fully reduced — its bucket runs no collective), but
    never in ``model_axes``.
    """
    from jax.sharding import PartitionSpec as P
    from ..ops.fusion import canonicalize_spec, spec_axes
    keyed = jax.tree_util.tree_leaves_with_path(
        param_specs,
        is_leaf=lambda x: x is None or isinstance(x, (P, str, tuple)))
    by_name = {jax.tree_util.keystr(k): canonicalize_spec(v)
               for k, v in keyed}
    if model_axes is None:
        axes = set()
        for spec in by_name.values():
            axes.update(spec_axes(spec))
        import os
        cfg = runtime._state().config
        cfg_axes = (cfg.model_axes if cfg is not None
                    else os.environ.get("HOROVOD_MODEL_AXES", ""))
        axes.update(a.strip() for a in cfg_axes.split(",") if a.strip())
        axes.discard(data_axis)
        model_axes = tuple(sorted(axes))
    else:
        model_axes = tuple(model_axes)
        if data_axis in model_axes:
            raise ValueError(
                f"model_axes {model_axes} must not contain the data "
                f"axis {data_axis!r}: the data axis is the one the "
                f"transform itself reduces over")
    return SpecPlan(by_name=by_name, model_axes=model_axes,
                    data_axis=data_axis)


def _restore_order(sorted_leaves, order):
    """Invert the ``_tree_leaves_sorted`` permutation back to
    ``tree_leaves`` order (no second path walk)."""
    out = [None] * len(order)
    for pos, i in enumerate(order):
        out[i] = sorted_leaves[pos]
    return out


def _resolve_threshold(threshold_bytes: Optional[int]) -> int:
    if threshold_bytes is not None:
        return threshold_bytes
    cfg = runtime._state().config
    return (cfg.fusion_threshold_bytes if cfg is not None
            else 64 * 1024 * 1024)


def _plan_buckets(leaves, names, op, prescale_factor, postscale_factor,
                  threshold_bytes, wire_format: str = "none",
                  tail_policy: str = "strict", specs=None):
    """One planner for both worlds: leaves become EntrySigs (name = the
    sorted pytree path, the controller's total order) and the eager
    engine's ``plan_fusion`` decides the buckets.  Within one dtype the
    path-sorted leaf order IS the planner's name order, so this is the
    plan every process computes.  ``specs`` (canonical PartitionSpec
    fingerprints aligned with ``leaves``; None = all replicated) rides
    each EntrySig so differently-sharded leaves never fuse — a bucket
    reduces over ONE axis set."""
    from ..compression import quantizable
    from ..ops.fusion import EntrySig, plan_fusion
    sigs = [EntrySig(name=names[i], op_type="allreduce",
                     reduce_op=str(op), dtype=str(leaves[i].dtype),
                     shape=tuple(leaves[i].shape), process_set_id=0,
                     stacked=False, prescale=prescale_factor,
                     postscale=postscale_factor,
                     wire_format=(wire_format if quantizable(leaves[i].dtype)
                                  else "none"),
                     tail_policy=tail_policy,
                     spec=("replicated" if specs is None else specs[i]))
            for i in range(len(leaves))]
    return plan_fusion(sigs, threshold_bytes), sigs


def fused_tail_reduce_tree(grads, cross_axis: str, local_axis: str,
                           op: str = ReduceOp.AVERAGE,
                           threshold_bytes: Optional[int] = None,
                           tail_policy: str = "strict",
                           present=None, tail_state=None,
                           max_staleness: int = 0, wire_format=None,
                           health=None):
    """Hierarchical tail-tolerant fused reduce of a gradient pytree over
    a ``(cross, local)`` mesh factoring (ISSUE 11 / ROADMAP item 2,
    OptiReduce arXiv:2310.06993).

    Buckets come from the SAME ``plan_fusion`` planner as every other
    reduce path (``tail_policy`` rides each :class:`EntrySig`, so the
    plan is the one peers negotiate) and each bucket runs
    :func:`~..ops.collectives.hierarchical_allreduce_p` under its
    ``hvd_bucket<i>`` scope: psum_scatter over ``local_axis`` (ICI),
    the tail-tolerant DCN stage over ``cross_axis``
    (:func:`~..ops.collectives.tail_allreduce_p` for non-strict
    policies), all-gather over ``local_axis``.

    ``present`` is the round's participation mask (fp32
    ``[axis_size(cross_axis)]``; None = all present).  Under ``stale``
    the per-bucket state threads through ``tail_state`` — a tuple of
    ``(prev, staleness)`` per bucket, None to start from zeros — and
    the return value is ``(reduced_tree, new_tail_state)``; other
    policies return ``(reduced_tree, None)``.
    """
    from ..ops.collectives import hierarchical_allreduce_p
    from ..ops.fusion import pad_to_multiple
    threshold_bytes = _resolve_threshold(threshold_bytes)
    leaves, names, order = _tree_leaves_sorted(grads)
    if not leaves:
        return grads, None
    treedef = jax.tree_util.tree_structure(grads)
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"fused_tail_reduce_tree supports op=Sum/Average, got {op!r}")
    buckets, _sigs = _plan_buckets(leaves, names, op, 1.0, 1.0,
                                   threshold_bytes,
                                   tail_policy=tail_policy)
    G = jax.lax.axis_size(cross_axis)
    L = jax.lax.axis_size(local_axis)
    if present is None:
        present = jnp.ones((G,), jnp.float32)
    stale = tail_policy == "stale"
    if stale and tail_state is not None and len(tail_state) != len(buckets):
        raise ValueError(
            f"tail_state carries {len(tail_state)} bucket states for a "
            f"{len(buckets)}-bucket plan — thread the state returned by "
            f"the previous step (same tree, same threshold)")
    out = [None] * len(leaves)
    new_state = [] if stale else None
    for bucket_id, bucket in enumerate(buckets):
        with jax.named_scope(f"hvd_bucket{bucket_id}"):
            parts = [leaves[i].reshape(-1) for i in bucket]
            buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            if _chaos.ACTIVE:
                from ..health.taps import chaos_corrupt
                # the tail reduce's worker identity is the flattened
                # (cross, local) device order — corrupt targets rank on
                # the cross axis (the DCN hop the tail policy rewrites)
                buf = chaos_corrupt(buf, cross_axis, bucket_id,
                                    names[bucket[0]])
            if health is not None:
                health.observe_bucket(bucket_id, names[bucket[0]], buf)
            state_i = None
            if stale:
                if tail_state is not None:
                    state_i = tail_state[bucket_id]
                else:
                    chunk_len = pad_to_multiple(buf.shape[0], L) // L
                    state_i = (jnp.zeros((G, chunk_len), buf.dtype),
                               jnp.zeros((G,), jnp.int32))
            red = hierarchical_allreduce_p(
                buf, cross_axis, local_axis, op=op,
                wire_format=wire_format, tail_policy=tail_policy,
                tail_present=present, tail_state=state_i,
                tail_max_staleness=max_staleness)
            if stale:
                red, st = red
                new_state.append(st)
                if health is not None:
                    # st[1]: int32 [n_groups] substitution counters —
                    # a counter AT the cap means that group's staleness
                    # budget is spent (the saturation verdict)
                    health.observe_staleness(bucket_id,
                                             names[bucket[0]], st[1],
                                             max_staleness)
            off = 0
            for i in bucket:
                sz = leaves[i].size
                out[i] = jax.lax.slice_in_dim(red, off, off + sz).reshape(
                    leaves[i].shape)
                off += sz
    reduced = jax.tree_util.tree_unflatten(
        treedef, _restore_order(out, order))
    return reduced, (tuple(new_state) if stale else None)


# ---------------------------------------------------------------------------
# ZeRO-style sharded update: reduce-scatter → 1/N update → allgather
# ---------------------------------------------------------------------------

class ShardedLayout(NamedTuple):
    """Trace-time slice metadata for reassembling reduce-scattered buckets.

    Everything here is static Python data (no arrays): the pytree
    structure, the path-sort permutation, per-leaf shapes, and each
    planned bucket's padded flat-buffer layout (``ops.fusion
    BucketLayout``).  ``all_gather_sharded_tree`` needs exactly this to
    rebuild the full pytree from per-worker 1/N tiles."""
    treedef: Any
    order: Tuple[int, ...]                 # _tree_leaves_sorted permutation
    shapes: Tuple[Tuple[int, ...], ...]    # leaf shapes, path-sorted order
    buckets: Tuple[Any, ...]               # ops.fusion.BucketLayout per bucket


def _sharded_layout(tree, axis_size: int, op, prescale_factor,
                    postscale_factor, threshold_bytes, align: int = 1,
                    spec_plan=None):
    """Plan the bucket/padding layout of ``tree`` for an ``axis_size``-way
    reduce-scatter — the SAME ``plan_fusion`` buckets as the replicated
    path (one cross-process ordering contract), plus per-bucket padding
    to a multiple of ``axis_size`` (times ``align``: the quantized wire
    needs block-aligned shards so per-block scales route with their
    blocks).  Returns ``(sorted_leaves, sorted_names, layout)`` so
    callers reuse the single path walk.

    Under a ``spec_plan`` the buckets are additionally keyed by each
    leaf's canonical PartitionSpec (mixed-spec buckets never form), and
    the per-bucket layouts tile each bucket's LOCAL (per-model-shard)
    flat size over the data axis — ZeRO within each model-shard group,
    so per-chip state is ``total/(model x data)``.

    Returns ``(sorted_leaves, sorted_names, sorted_specs, layout)``;
    ``sorted_specs`` is None without a spec plan — callers reuse it
    instead of re-resolving per leaf."""
    from ..ops.fusion import plan_bucket_layouts
    leaves, names, order = _tree_leaves_sorted(tree)
    specs = (spec_plan.specs_for(names) if spec_plan is not None
             else None)
    buckets, sigs = _plan_buckets(leaves, names, op, prescale_factor,
                                  postscale_factor, threshold_bytes,
                                  specs=specs)
    return leaves, names, specs, ShardedLayout(
        treedef=jax.tree_util.tree_structure(tree), order=tuple(order),
        shapes=tuple(tuple(l.shape) for l in leaves),
        buckets=tuple(plan_bucket_layouts(sigs, buckets, axis_size,
                                          align=align)))


def _bucket_flat(leaves, bl):
    """Concatenate a bucket's (path-sorted) leaves into one flat buffer,
    zero-padded to the reduce-scatter-divisible size."""
    parts = [leaves[i].reshape(-1) for i in bl.indices]
    buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if bl.padded_numel != bl.numel:
        buf = jnp.pad(buf, (0, bl.padded_numel - bl.numel))
    return buf


def _my_tile(buf, shard_numel: int, axis_name: str):
    """This worker's 1/N tile of a padded flat bucket buffer."""
    idx = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(buf, idx * shard_numel, shard_numel)


def _tiles_from_leaves(leaves, layout: ShardedLayout, axis_name: str):
    """Per-bucket 1/N tiles of already-path-sorted leaves."""
    return tuple(_my_tile(_bucket_flat(leaves, bl), bl.shard_numel,
                          axis_name)
                 for bl in layout.buckets)


def shard_tree_like(tree, layout: ShardedLayout, axis_name: str):
    """Carve ``tree`` (e.g. the replicated params) into this worker's
    per-bucket flat tiles under an existing ``ShardedLayout`` — the
    layout the sharded optimizer state lives on."""
    leaves, _names, _order = _tree_leaves_sorted(tree)
    return _tiles_from_leaves(leaves, layout, axis_name)


def fused_reduce_scatter_tree(grads, axis_name: str,
                              op: str = ReduceOp.AVERAGE,
                              threshold_bytes: Optional[int] = None,
                              compression=Compression.none,
                              prescale_factor: float = 1.0,
                              postscale_factor: float = 1.0,
                              wire_format=None, residual=None,
                              health=None, spec_plan=None):
    """Reduce-scatter a gradient pytree: each worker keeps 1/N per bucket.

    The sharded-update half of ``fused_reduce_tree``: the SAME
    ``plan_fusion`` buckets in the same ``hvd_bucket<i>`` named scopes,
    but each padded flat buffer is reduced with ``psum_scatter`` instead
    of ``psum`` — same total collective bytes as a tree allreduce, and no
    worker ever materializes the full reduced gradient.

    Returns ``(shards, layout)``: ``shards`` is a tuple with one flat
    1/N-sized array per planned bucket (this worker's tile, fully scaled
    and averaged), ``layout`` is the static slice metadata
    ``all_gather_sharded_tree`` / ``shard_tree_like`` consume.

    ``wire_format`` quantizes the gradient reduce-scatter (block-scaled
    tiles + scales, fp32 accumulation) with error feedback: ``residual``
    is the grads-shaped carried-error tree (None = zeros) and the return
    becomes ``(shards, layout, new_residual)``.  Bucket padding grows to
    a multiple of ``n * block_size`` so tiles stay block-aligned — the
    sharded state layout therefore depends on the wire format.  The
    updates all-gather (``all_gather_sharded_tree``) stays full-width:
    it carries optimizer OUTPUT, which has no error-feedback state to
    absorb quantization bias.

    ``spec_plan`` (a :class:`SpecPlan`) composes ZeRO with a model-
    sharded mesh (ISSUE 14): each bucket's flat buffer is the LOCAL
    model shard, tiled over the DATA axis *within* this model-shard
    group — per-chip optimizer state is ``total/(model x data)`` — a
    model-sharded bucket's ``psum_scatter`` runs over the data axis
    alone (its gradient is already reduced over the model axes), and a
    replicated bucket psums over the model axes first.  With a
    ``wire_format`` the error-feedback residual is shaped like the
    (local) gradient shard.  A spec naming the data axis itself is
    refused: such a gradient arrives fully reduced AND sharded, so
    there is no axis left to scatter over — use the plain spec-aware
    reduction (``sharded_update=False``) for those leaves.
    """
    if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
        raise ValueError(
            f"fused_reduce_scatter_tree supports op=Average/Sum, got "
            f"{op!r}: Adasum and min/max reductions are not expressible "
            f"as a reduce-scatter of bucket tiles")
    threshold_bytes = _resolve_threshold(threshold_bytes)
    fmt = resolve_wire_format(wire_format)
    if fmt is not None and compression not in (None, Compression.none):
        raise ValueError(
            "wire_format and compression are two definitions of the same "
            "wire: pick the block-scaled quantized format OR the cast "
            "compressor, not both")
    if not jax.tree_util.tree_leaves(grads):
        empty = ((), ShardedLayout(
            treedef=jax.tree_util.tree_structure(grads), order=(),
            shapes=(), buckets=()))
        return empty if fmt is None else empty + (residual,)
    n = jax.lax.axis_size(axis_name)
    # names ride the single path walk: a chaos rule matching name=
    # must not be silently inert under sharded_update, and verdicts
    # carry the same tensor names as the other fused paths
    leaves, names, specs, layout = _sharded_layout(
        grads, n, op, prescale_factor, postscale_factor,
        threshold_bytes, align=fmt.block_size if fmt else 1,
        spec_plan=spec_plan)
    if specs is not None:
        # validate only the leaves actually present in THIS tree (a
        # data-axis spec on an unused spec-tree entry is not an error
        # here; the transform-build guard covers the configured case)
        from ..ops.fusion import spec_axes
        for nm, spec in zip(names, specs):
            if axis_name in spec_axes(spec):
                raise ValueError(
                    f"sharded_update with param_specs: leaf {nm} is "
                    f"sharded over the data axis {axis_name!r} itself — "
                    f"its gradient arrives fully reduced and sharded, "
                    f"leaving no axis to reduce-scatter over; use "
                    f"sharded_update=False for spec trees naming the "
                    f"data axis")
    global_n = spec_plan.global_size() if spec_plan is not None else n
    res_leaves = _residual_leaves(residual, leaves) if fmt is not None \
        else None
    new_res = [None] * len(leaves) if fmt is not None else None
    shards = []
    for bucket_id, bl in enumerate(layout.buckets):
        with jax.named_scope(f"hvd_bucket{bucket_id}"):
            buf = _bucket_flat(leaves, bl)
            nm = names[bl.indices[0]]
            if _chaos.ACTIVE:
                from ..health.taps import chaos_corrupt
                buf = chaos_corrupt(buf, axis_name, bucket_id, nm)
            if health is not None:
                health.observe_bucket(bucket_id, nm, buf)
            if prescale_factor != 1.0:
                buf = buf * jnp.asarray(prescale_factor, buf.dtype)
            if specs is not None:
                # a replicated bucket's model-axis hop runs first (its
                # members are the small unsharded leaves); a model-
                # sharded bucket's gradient is already reduced over its
                # spec axes, so only the data-axis scatter remains
                m_axes = tuple(
                    a for a in spec_plan.reduce_axes(
                        specs[bl.indices[0]]) if a != axis_name)
                if m_axes:
                    buf = jax.lax.psum(buf, m_axes)
            if fmt is not None:
                from ..ops.collectives import quantized_sum_scatter_p
                rbuf = _bucket_flat(res_leaves, bl).astype(jnp.float32)
                tile, nres = quantized_sum_scatter_p(
                    buf.astype(jnp.float32) + rbuf, axis_name, fmt,
                    error_feedback=True)
                if health is not None:
                    health.observe_residual(bucket_id, nres)
                tile = tile.astype(buf.dtype)
                off = 0
                for i in bl.indices:
                    sz = leaves[i].size
                    new_res[i] = jax.lax.slice_in_dim(
                        nres, off, off + sz).reshape(leaves[i].shape)
                    off += sz
            else:
                wire, ctx = compression.compress(buf)
                tile = jax.lax.psum_scatter(
                    wire, axis_name, scatter_dimension=0, tiled=True)
                tile = compression.decompress(tile, ctx)
            if op == ReduceOp.AVERAGE:
                tile = tile / global_n
            if postscale_factor != 1.0:
                tile = tile * jnp.asarray(postscale_factor, tile.dtype)
            shards.append(tile)
    if fmt is None:
        return tuple(shards), layout
    return tuple(shards), layout, jax.tree_util.tree_unflatten(
        layout.treedef, _restore_order(new_res, list(layout.order)))


def sharded_tile_layout(tree, shards: int, op: str = ReduceOp.AVERAGE,
                        threshold_bytes: Optional[int] = None,
                        align: int = 1, spec_plan=None) -> ShardedLayout:
    """The ZeRO bucket/tile layout of ``tree`` tiled ``shards``-way —
    pure trace-free plan metadata (``tree`` may hold
    ``ShapeDtypeStruct`` leaves; nothing is materialized).  Callers
    price per-chip sharded optimizer state EXACTLY from
    ``layout.buckets[i].shard_numel`` (tools/bench_fsdp.py,
    tools/rehearse_8b.py) instead of re-deriving the planner's padding
    arithmetic."""
    _leaves, _names, _specs, layout = _sharded_layout(
        tree, shards, op, 1.0, 1.0, _resolve_threshold(threshold_bytes),
        align=align, spec_plan=spec_plan)
    return layout


def all_gather_sharded_tree(shards, layout: ShardedLayout, axis_name: str):
    """Rebuild the full (replicated) pytree from per-worker bucket tiles:
    ONE tiled ``all_gather`` per bucket, then unpad/split/unflatten."""
    if len(shards) != len(layout.buckets):
        raise ValueError(
            f"got {len(shards)} shard(s) for a layout of "
            f"{len(layout.buckets)} bucket(s) — the shards and the "
            f"layout come from different plans (e.g. a stale layout "
            f"after a fusion-threshold change)")
    out = [None] * len(layout.shapes)
    for bucket_id, (bl, tile) in enumerate(zip(layout.buckets, shards)):
        with jax.named_scope(f"hvd_bucket{bucket_id}"):
            full = jax.lax.all_gather(tile, axis_name, axis=0, tiled=True)
            off = 0
            for i, sz in zip(bl.indices, bl.sizes):
                out[i] = jax.lax.slice_in_dim(full, off, off + sz).reshape(
                    layout.shapes[i])
                off += sz
    return jax.tree_util.tree_unflatten(
        layout.treedef, _restore_order(out, list(layout.order)))


def _sharded_update_default() -> bool:
    """Env/config default for ``sharded_update`` (HOROVOD_SHARDED_UPDATE)."""
    cfg = runtime._state().config
    if cfg is not None:
        return cfg.sharded_update
    from ..config import _env_bool
    return _env_bool("HOROVOD_SHARDED_UPDATE", False)


def _overlap_default() -> bool:
    """Env/config default for ``overlap`` (HOROVOD_OVERLAP)."""
    cfg = runtime._state().config
    if cfg is not None:
        return cfg.overlap
    from ..config import _env_bool
    return _env_bool("HOROVOD_OVERLAP", False)


def _health_taps_default() -> bool:
    """Env/config default for ``health`` (HOROVOD_HEALTH_TAPS, vetoed
    by the HOROVOD_HEALTH master switch): the in-jit numerics taps +
    divergence sentinel are a schedule property like sharded_update,
    so they are an opt-in — an explicit ``health=True`` on the
    transform wins over the env either way (the pinned
    ``health_distopt_step`` schedule entry must not flip with it)."""
    cfg = runtime._state().config
    if cfg is not None:
        return cfg.health and cfg.health_taps
    from .. import health as _h
    return _h.taps_default()


def _health_check_every_default() -> int:
    """Env/config default for the divergence-sentinel cadence
    (HOROVOD_HEALTH_CHECK_EVERY, steps)."""
    cfg = runtime._state().config
    if cfg is not None:
        return cfg.health_check_every
    from .. import health as _h
    return _h.check_every()


def _sentinel_bucket_flats(target, plan_like, op, prescale_factor,
                           postscale_factor, threshold_bytes):
    """``(bucket_id, name, flat_buf)`` per fusion bucket of ``target``,
    bucketed by the plan of ``plan_like`` (the GRADIENT tree): the
    sentinel's checksum attribution must line up with the numerics
    taps' bucket ids, and planning from the target itself would split
    differently under mixed precision (fp32 params vs bf16 grads —
    byte thresholds see 2x the sizes).  Both trees share one
    structure, so the path-sorted leaf indices coincide."""
    t_leaves, _t_names, _order = _tree_leaves_sorted(target)
    p_leaves, p_names, _p_order = _tree_leaves_sorted(plan_like)
    buckets, _sigs = _plan_buckets(p_leaves, p_names, op,
                                   prescale_factor, postscale_factor,
                                   threshold_bytes)
    out = []
    for bucket_id, bucket in enumerate(buckets):
        parts = [t_leaves[i].reshape(-1) for i in bucket]
        out.append((bucket_id, p_names[bucket[0]],
                    jnp.concatenate(parts) if len(parts) > 1
                    else parts[0]))
    return out


def _wire_format_default():
    """Env/config default for ``wire_format`` (HOROVOD_COMPRESSION +
    HOROVOD_COMPRESSION_BLOCK_SIZE): the quantized wire the operator
    opted into for the whole job.

    HOROVOD_COMPRESSION_DCN_ONLY is deliberately NOT consulted here: it
    is an eager-dispatch placement policy for a path with no error-
    feedback state.  The in-jit transform carries this worker's
    quantization error in ``_DistState.residual``, which is exactly what
    makes quantizing its whole bucketed reduction safe (EQuARX's
    regime); pass ``wire_format="none"`` to opt a transform out."""
    cfg = runtime._state().config
    if cfg is not None:
        return cfg.compression, cfg.compression_block_size
    import os
    return (os.environ.get("HOROVOD_COMPRESSION", "none") or "none",
            int(os.environ.get("HOROVOD_COMPRESSION_BLOCK_SIZE", 0) or 0)
            or None)


class _DistState(NamedTuple):
    inner: Any
    acc: Any
    count: jnp.ndarray
    # grads-shaped fp32 error-feedback tree carried by the quantized wire
    # formats (this worker's accumulated quantization error; None when no
    # wire format is active) — varying over the worker axis, like ``acc``
    residual: Any = None


def _deliver_recovery_snapshot(names, step, rank, *leaves):
    """Host side of the recovery snapshot tap (``jax.debug.callback``
    target): route the boundary payload to the installed
    :class:`~horovod_tpu.elastic.recovery.RecoveryAgent` (each filters
    by rank)."""
    from ..elastic import recovery as _recovery
    payload = {n: np.asarray(a) for n, a in zip(names, leaves)}
    _recovery.deliver_boundary(int(step), int(rank), payload)


def recovery_payload(state: _DistState) -> Dict[str, np.ndarray]:
    """The ``{name: array}`` snapshot the recovery tap emits for
    ``state``: the inner optimizer leaves (this worker's ZeRO tiles
    under ``sharded_update``), the error-feedback residual, and the
    step counter.  The accumulator is excluded — it is zero at every
    boundary by construction.  Host-side twin of the in-jit tap, for
    tests and direct callers."""
    out = {"count": np.asarray(state.count)}
    for i, leaf in enumerate(jax.tree_util.tree_leaves(state.inner)):
        out[f"inner/{i}"] = np.asarray(leaf)
    residual = getattr(state, "residual", None)
    if residual is not None:
        for i, leaf in enumerate(jax.tree_util.tree_leaves(residual)):
            out[f"residual/{i}"] = np.asarray(leaf)
    return out


def restore_dist_state(state: _DistState, payload) -> _DistState:
    """Rebuild a ``_DistState`` from a recovered snapshot payload.

    ``state`` is a freshly initialized state of the SAME transform on
    the SAME params (the rejoining worker re-runs ``init_fn``); its
    leaves define the expected shapes/dtypes, and the restore is
    bit-exact — a shape or dtype mismatch (e.g. a re-form that resized
    the fleet and changed the tile layout) raises instead of casting.
    """
    def _rebuild(tree, prefix):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out = []
        for i, leaf in enumerate(leaves):
            arr = payload.get(f"{prefix}/{i}")
            if arr is None:
                raise ValueError(
                    f"recovered payload is missing {prefix}/{i} — "
                    f"snapshot taken by a different transform "
                    f"configuration?")
            arr = np.asarray(arr)
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = np.dtype(getattr(leaf, "dtype", arr.dtype))
            if tuple(arr.shape) != shape or arr.dtype != dtype:
                raise ValueError(
                    f"recovered {prefix}/{i} is {arr.dtype}{arr.shape}, "
                    f"expected {dtype}{shape} — the tile layout changed "
                    f"(e.g. the fleet was resized); checkpointless "
                    f"recovery covers replacement-at-same-size re-forms "
                    f"only")
            out.append(jnp.asarray(arr))
        return jax.tree_util.tree_unflatten(treedef, out)

    new_inner = _rebuild(state.inner, "inner")
    residual = getattr(state, "residual", None)
    new_res = (_rebuild(residual, "residual")
               if residual is not None else None)
    if "count" not in payload:
        raise ValueError("recovered payload is missing the step counter")
    count = jnp.asarray(np.asarray(payload["count"]),
                        dtype=jnp.asarray(state.count).dtype)
    return _DistState(inner=new_inner, acc=state.acc, count=count,
                      residual=new_res)


def DistributedGradientTransform(
        inner: Optional[optax.GradientTransformation] = None,
        op: str = ReduceOp.AVERAGE,
        axis_name: Optional[str] = None,
        backward_passes_per_step: int = 1,
        compression=Compression.none,
        prescale_factor: float = 1.0,
        postscale_factor: float = 1.0,
        threshold_bytes: Optional[int] = None,
        process_set=None,
        sharded_update: Optional[bool] = None,
        wire_format: Optional[str] = None,
        wire_block_size: Optional[int] = None,
        overlap: Optional[bool] = None,
        overlap_layers: str = "layers",
        health: Optional[bool] = None,
        health_check_every: Optional[int] = None,
        param_specs=None,
        model_axes: Optional[Tuple[str, ...]] = None,
        recovery=None
        ) -> optax.GradientTransformation:
    """optax transformation that cross-worker-reduces gradients.

    ``axis_name`` given → in-jit path (fused psum over the mesh axis; use
    inside ``shard_map``/``pjit`` steps).  ``axis_name=None`` → eager path
    through the background engine (grouped allreduce, async + fused), for
    non-jit callers, matching the reference's per-parameter hook behavior.

    With ``backward_passes_per_step > 1``, gradients accumulate locally and
    the (single) reduction fires every k-th step; intermediate steps emit
    zero updates (reference: optimizer.py backward_passes_per_step).

    ``sharded_update=True`` (default from ``HOROVOD_SHARDED_UPDATE``;
    in-jit path only) switches each bucket from
    psum → full update to **reduce-scatter → 1/N update → allgather**
    (ZeRO-style, arXiv:2004.13336): ``init_fn`` initializes the inner
    optimizer state on this worker's flat bucket tiles, so per-chip
    optimizer-state bytes are ``total/N + padding`` — composing with the
    bf16 moments of ``optim.precision.adamw_lp``.  Params stay
    replicated; the allgathered updates apply as usual.  Because the
    state is per-worker, ``init_fn`` must run INSIDE the mapped program
    (like the ``backward_passes_per_step`` accumulator) and the state
    crosses shard_map boundaries with
    ``state_partition_specs(..., sharded_update=True)``.

    ``wire_format`` ("int8", "fp8_e4m3", "fp8_e5m2"; default from
    ``HOROVOD_COMPRESSION``, "none" disables; in-jit path only) switches
    each bucket to the block-scaled quantized staging with **error
    feedback**: this worker's quantization error is carried in
    ``_DistState.residual`` (grads-shaped, fp32, varying over the worker
    axis — ``state_partition_specs`` shards it like the accumulator) and
    added back before the next quantization, so the compressed updates
    converge to the full-width trajectory instead of accumulating bias.
    Composes with ``sharded_update`` (the gradient reduce-scatter is
    quantized; the updates all-gather stays full-width) and with
    ``backward_passes_per_step`` (the boundary reduction quantizes the
    accumulated mean).

    ``overlap=True`` (default from ``HOROVOD_OVERLAP``; in-jit only,
    Average/Sum only) switches to **overlapped dispatch** (ROADMAP item
    3, arXiv:2305.06942): the fusion plan becomes layer-aware (buckets
    never span layers of the scanned stack under ``overlap_layers``,
    and the plan carries an explicit reverse-layer dispatch schedule),
    and when the step's backward pass runs under
    :func:`~horovod_tpu.optim.overlap.overlapped_backprop`, each
    bucket's ``psum`` (or ``psum_scatter`` under ``sharded_update``)
    fires inside the backward scan the moment its layer's gradients
    materialize — hiding DCN latency behind the remaining backprop
    compute.  Without the context (or for models without tap sites) the
    same layer-aware plan runs at the step boundary, landing on
    bit-identical weights.  With a ``wire_format`` the early-dispatched
    buckets quantize WITHOUT error feedback (the residual is per-step
    state the backward pass cannot thread; ``_DistState.residual``
    stays untouched at ``None``).  With ``backward_passes_per_step > 1``
    the taps gate on the accumulation boundary — pass
    ``count=state.count`` to ``overlapped_backprop``.

    ``param_specs`` (a pytree of PartitionSpecs congruent with the
    params; default: the ``param_specs`` of the innermost active
    :class:`~horovod_tpu.parallel.mesh.ParallelMesh` context) makes the
    whole gradient plane **mesh-axis-aware** (ISSUE 14 / ROADMAP item
    3): the mesh factors into the data axis (``axis_name``) times the
    model axes (``model_axes``; default: the axes the specs name plus
    ``HOROVOD_MODEL_AXES``), each leaf's canonical spec rides its
    EntrySig and the negotiation token (field 12) so differently-
    sharded leaves never fuse and every process agrees which axes each
    bucket reduces over.  A model-sharded leaf's gradient arrives as
    the locally-owned shard, pre-reduced over its spec axes (the
    model's gather-transpose collectives), so its bucket psums over
    the DATA axis only — never materializing the full-width gradient;
    replicated buckets reduce over data + model axes.  ``op=Average``
    divides by the global batch degree.  Composes with
    ``sharded_update`` (ZeRO tiles over the data axis *within* each
    model-shard group: per-chip state is ``total/(model x data)``),
    ``wire_format`` (only the data/DCN hop quantizes; residuals are
    shaped like the shard) and ``overlap`` (the taps dispatch the
    spec-aware plan).  Not composed with ``health`` yet (the sentinel's
    checksum gather assumes one replication group) — that pairing
    raises, naming itself.

    ``health=True`` (default from ``HOROVOD_HEALTH_TAPS``, vetoed by
    ``HOROVOD_HEALTH=0``; in-jit only) arms the **training-health
    numerics taps** (docs/observability.md "Training health"): each
    fused bucket's local pre-reduction buffer feeds per-bucket l2 /
    max-abs / nonfinite stats (plus the error-feedback residual norm
    under a wire format, and staleness counters under a stale tail
    policy) to the host :class:`~..health.evaluate.HealthEvaluator`
    via ``jax.debug.callback``, and every
    ``health_check_every``-th step (``HOROVOD_HEALTH_CHECK_EVERY``) a
    **divergence sentinel** allgathers per-bucket param/update +
    opt-state checksums across the axis so a silently desynced replica
    is convicted with (worker, bucket, step) attribution.  An explicit
    ``health=`` wins over the env (the pinned ``health_distopt_step``
    hvdsched entry relies on this).  Under ``sharded_update`` the
    opt-state checksum is skipped — the state is 1/N per worker by
    design.  Not supported with ``overlap`` (the in-backward dispatched
    buckets never materialize a boundary buffer to tap).

    ``recovery`` (a
    :class:`~horovod_tpu.elastic.recovery.RecoveryAgent`; explicit
    opt-in only — deliberately no env default here, so compiled
    schedules are untouched unless a caller arms the plane) attaches
    the **checkpointless-recovery snapshot tap**: at every accumulation
    boundary whose ordinal lands on the agent's cadence, one
    ``jax.debug.callback`` delivers this worker's per-worker state (the
    ZeRO shard tiles or replicated inner state, the error-feedback
    residual, the step counter) to the agent, which frames and pushes
    it to its redundancy peer (docs/elastic.md "Checkpointless
    recovery").  Off-cadence boundaries pay one traced predicate.  The
    in-flight accumulator is NOT snapshotted — it is zero at every
    boundary by construction.  Not supported with ``overlap`` (the
    boundary state never materializes in one place to tap).
    """
    if inner is None:
        inner = optax.identity()
    k = backward_passes_per_step
    if param_specs is None and axis_name is not None:
        # the ParallelMesh context is the no-plumbing path: a step
        # built inside `with pmesh.with_param_specs(specs):` gets the
        # spec tree without threading it through every call site
        from ..parallel.mesh import current_mesh
        _m = current_mesh()
        if _m is not None and _m.param_specs is not None:
            param_specs = _m.param_specs
    spec_plan = None
    if param_specs is not None:
        if axis_name is None:
            raise ValueError(
                "param_specs requires axis_name: the mesh-axis-aware "
                "reduction factors the in-jit mesh into data x model "
                "axes; the eager engine's arrays are full-width "
                "(spec='replicated') by construction")
        if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
            raise ValueError(
                f"param_specs supports op=Average/Sum, got {op!r}")
        spec_plan = make_spec_plan(param_specs, axis_name, model_axes)
    if sharded_update and axis_name is None:
        raise ValueError(
            "sharded_update=True requires axis_name: the reduce-scatter "
            "rewrite exists only on the in-jit path (the eager engine "
            "has no mesh axis to scatter over)")
    sharded = (bool(sharded_update) if sharded_update is not None
               else axis_name is not None and _sharded_update_default())
    if sharded and op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
        raise ValueError(
            f"sharded_update supports op=Average/Sum, got {op!r}")
    if sharded and spec_plan is not None:
        from ..ops.fusion import spec_axes
        for _nm, _spec in sorted(spec_plan.by_name.items()):
            if axis_name in spec_axes(_spec):
                raise ValueError(
                    f"sharded_update with param_specs: leaf {_nm} is "
                    f"sharded over the data axis {axis_name!r} itself — "
                    f"its gradient arrives fully reduced and sharded, "
                    f"leaving no axis to ZeRO-tile over; use "
                    f"sharded_update=False for spec trees naming the "
                    f"data axis")
    if wire_format is not None and wire_format != "none" \
            and axis_name is None:
        raise ValueError(
            "wire_format requires axis_name: the quantized staging is an "
            "in-jit schedule rewrite; the eager path's wire format is the "
            "engine's negotiated HOROVOD_COMPRESSION setting")
    if wire_format is None and axis_name is not None:
        env_fmt, env_block = _wire_format_default()
        fmt = resolve_wire_format(env_fmt,
                                  wire_block_size or env_block or None)
    else:
        fmt = (resolve_wire_format(wire_format, wire_block_size)
               if axis_name is not None else None)
    if fmt is not None:
        if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
            raise ValueError(
                f"wire_format quantization supports op=Average/Sum, got "
                f"{op!r}: Adasum operates on exact local gradients and "
                f"min/max are not expressible as a quantize-accumulate "
                f"staging")
        if compression not in (None, Compression.none):
            raise ValueError(
                "wire_format and compression are two definitions of the "
                "same wire: pick the block-scaled quantized format OR "
                "the cast compressor, not both")

    if overlap and axis_name is None:
        raise ValueError(
            "overlap=True requires axis_name: overlapped dispatch "
            "places per-bucket collectives inside the compiled backward "
            "pass (the eager engine already overlaps via its background "
            "loop)")
    ov_enabled = (bool(overlap) if overlap is not None
                  else axis_name is not None and _overlap_default())
    _ov_plan = None
    if ov_enabled:
        if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
            raise ValueError(
                f"overlap supports op=Average/Sum, got {op!r}: Adasum's "
                f"recursive pairwise reduction needs every gradient at "
                f"once and cannot dispatch per-layer")
        if compression not in (None, Compression.none):
            raise ValueError(
                "overlap does not support the cast compressor: use "
                "wire_format for a quantized wire (feedback-free under "
                "overlap) or no compression")
        from . import overlap as _ov
        _ov_plan = _ov.OverlapPlan(
            axis_name=axis_name, op=op, threshold_bytes=threshold_bytes,
            prescale=prescale_factor, postscale=postscale_factor,
            sharded=sharded, fmt=fmt, k=k, layers_key=overlap_layers,
            spec_plan=spec_plan)

    if health and axis_name is None:
        raise ValueError(
            "health=True requires axis_name: the numerics taps live in "
            "the in-jit fused buffers and the divergence sentinel needs "
            "a mapped axis to gather checksums over (the eager engine "
            "has its own dispatch taps, on by default under "
            "HOROVOD_HEALTH)")
    if health and _ov_plan is not None:
        raise ValueError(
            "health=True is not supported with overlap=True: the "
            "overlapped buckets dispatch inside the backward scan and "
            "never materialize a boundary buffer to tap — use the "
            "trace/metrics plane for overlapped steps, or disable one")
    if health and spec_plan is not None:
        raise ValueError(
            "health=True is not supported with param_specs yet: the "
            "divergence sentinel's checksum gather assumes ONE "
            "replication group, but a model-sharded leaf's checksums "
            "legitimately differ across model shards — disable the "
            "in-jit taps for spec-aware steps (the eager engine taps "
            "and the trace/metrics plane still cover them)")
    hl_enabled = (bool(health) if health is not None
                  else (axis_name is not None and _ov_plan is None
                        and spec_plan is None
                        and _health_taps_default()))
    hl_every = 1
    if hl_enabled:
        hl_every = (int(health_check_every)
                    if health_check_every is not None
                    else _health_check_every_default())
        if hl_every < 1:
            raise ValueError(
                f"health_check_every must be >= 1, got {hl_every}")

    if recovery is not None and _ov_plan is not None:
        raise ValueError(
            "recovery is not supported with overlap=True: overlapped "
            "steps dispatch buckets inside the backward scan and never "
            "materialize the boundary state in one place to snapshot — "
            "disable one of the two")
    rc_every = max(int(getattr(recovery, "every", 1)), 1) \
        if recovery is not None else 1

    def _emit_recovery(boundary_ord, count, new_inner, new_res):
        """Cadence-gated boundary snapshot tap (HealthTaps pattern):
        the host transfer happens only inside the cadence branch;
        off-cadence boundaries pay one predicate."""
        names = ["count"]
        leaves = [count]
        for i, leaf in enumerate(jax.tree_util.tree_leaves(new_inner)):
            names.append(f"inner/{i}")
            leaves.append(leaf)
        if new_res is not None:
            for i, leaf in enumerate(jax.tree_util.tree_leaves(new_res)):
                names.append(f"residual/{i}")
                leaves.append(leaf)
        rank = (jax.lax.axis_index(axis_name) if axis_name is not None
                else jnp.int32(0))

        def fire(_):
            jax.debug.callback(
                functools.partial(_deliver_recovery_snapshot,
                                  tuple(names)),
                boundary_ord, rank, *leaves)
            return jnp.int32(0)

        jax.lax.cond(boundary_ord % rc_every == 0, fire,
                     lambda _: jnp.int32(0), jnp.int32(0))

    def reduce_grads(grads, health=None):
        if axis_name is not None:
            return fused_reduce_tree(
                grads, axis_name, op=op, threshold_bytes=threshold_bytes,
                compression=compression, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor, health=health,
                spec_plan=spec_plan)
        from .. import api
        leaves, names, order = _tree_leaves_sorted(grads)
        wires, ctxs = [], []
        for leaf in leaves:
            w, c = compression.compress(leaf)
            wires.append(w)
            ctxs.append(c)
        red = api.grouped_allreduce(
            wires, op=op, name="distopt",
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set=process_set)
        red = [compression.decompress(r, c) for r, c in zip(red, ctxs)]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(grads), _restore_order(red, order))

    # init-time layout fingerprints (static trace metadata, not traced
    # state): let _step validate the gradient-planned layout even when
    # update() is called without params.  Empty when init_fn never ran
    # in this transform's lifetime (e.g. state restored from checkpoint
    # into a fresh transform); more than one distinct entry means the
    # transform was reused across different models, so a params-less
    # update can't know which layout its state came from — validation
    # is then params-based only (no false positives either way).
    _init_fingerprints = set()

    def _step(grads, inner_state, params, residual, taps=None):
        """One reduced optimizer step → (full-size updates, new inner,
        new error-feedback residual).  ``taps`` is the per-update
        health context (numerics taps inside the fused reduce, then
        the divergence sentinel + one batched host delivery here)."""
        if sharded:
            if fmt is not None:
                shards, layout, new_res = fused_reduce_scatter_tree(
                    grads, axis_name, op=op,
                    threshold_bytes=threshold_bytes,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    wire_format=fmt, residual=residual, health=taps,
                    spec_plan=spec_plan)
            else:
                shards, layout = fused_reduce_scatter_tree(
                    grads, axis_name, op=op,
                    threshold_bytes=threshold_bytes,
                    compression=compression,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor, health=taps,
                    spec_plan=spec_plan)
                new_res = residual
            # init_fn planned the state layout from PARAMS; the gradient
            # layout above must be the same plan, or the 1/N state tiles
            # won't line up with the grad shards — fail with the cause
            # instead of a deep optax mismatch
            p_shards = None
            if params is not None:
                p_leaves, _p_names, _p_specs, p_layout = _sharded_layout(
                    params, jax.lax.axis_size(axis_name), op, prescale_factor,
                    postscale_factor, _resolve_threshold(threshold_bytes),
                    align=fmt.block_size if fmt else 1,
                    spec_plan=spec_plan)
                expected = (p_layout.shapes, p_layout.buckets)
            else:
                p_leaves = None
                expected = (next(iter(_init_fingerprints))
                            if len(_init_fingerprints) == 1 else None)
            if (expected is not None
                    and expected != (layout.shapes, layout.buckets)):
                raise ValueError(
                    "sharded_update requires gradients and params to "
                    "share one bucket layout, but they plan differently "
                    "(dtype or structure divergence between the gradient "
                    "tree and the param tree — e.g. a cast-to-bf16 "
                    "transform chained before this one); use the "
                    "replicated path or align the dtypes")
            if p_leaves is not None:
                p_shards = _tiles_from_leaves(p_leaves, layout, axis_name)
            upd_shards, new_inner = inner.update(
                shards, inner_state, p_shards)
            updates = all_gather_sharded_tree(upd_shards, layout, axis_name)
            if taps is not None:
                # sharded mode: the inner state is 1/N per worker BY
                # DESIGN — only the replicated params/updates can be
                # checksummed for desync.  Thunk: the flats build only
                # inside the cadence branch (off-cadence steps pay one
                # predicate, never the flatten+checksum reductions)
                taps.sentinel(lambda: _sentinel_bucket_flats(
                    params if params is not None else updates, grads,
                    op, prescale_factor, postscale_factor,
                    _resolve_threshold(threshold_bytes)))
                taps.emit()
            return updates, new_inner, new_res
        if fmt is not None:
            reduced, new_res = fused_reduce_tree(
                grads, axis_name, op=op, threshold_bytes=threshold_bytes,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                wire_format=fmt, residual=residual, health=taps,
                spec_plan=spec_plan)
        else:
            reduced = reduce_grads(grads, health=taps)
            new_res = residual
        updates, new_inner = inner.update(reduced, inner_state, params)
        if taps is not None:
            # thunk: flats/checksums build only inside the cadence
            # branch (see HealthTaps.sentinel — closure-captured
            # arrays would be evaluated on every step)
            taps.sentinel(lambda: _sentinel_bucket_flats(
                params if params is not None else updates, grads, op,
                prescale_factor, postscale_factor,
                _resolve_threshold(threshold_bytes)),
                opt_state=new_inner)
            taps.emit()
        return updates, new_inner, new_res

    def _ov_step(grads, inner_state, params, fired, extra_acc=None,
                 fire=None):
        """One overlapped optimizer step (layer-aware plan).

        ``fired``: taps were armed in this trace, so ``grads`` arrive
        pre-reduced (sharded: tile-placed) from the backward scan —
        otherwise the identical plan runs here at the boundary.
        ``fire``: the context's explicit runtime gate — when set, BOTH
        paths are traced under one ``lax.cond`` (grads are reduced iff
        the taps fired at runtime), making overlapped-vs-boundary a
        same-program A/B.  ``extra_acc`` (``backward_passes_per_step >
        1`` boundary): the accumulated raw local gradients of the k-1
        intermediate micro-steps, reduced here and folded in as
        ``(R(extra_acc) + grads) / k`` — linearity of Sum/Average makes
        that the reduction of the accumulated mean.
        """
        from . import overlap as _ov
        if sharded:
            if fired:
                if fire is not None:
                    # plan once; both cond branches reuse the layout
                    _leaves, layout = _ov.build_layout(
                        grads, _ov_plan, shards=jax.lax.axis_size(axis_name))
                    tiles = jax.lax.cond(
                        fire,
                        lambda g: _ov.carve_tiles(g, _ov_plan,
                                                  layout)[0],
                        lambda g: _ov.scatter_tiles(g, _ov_plan,
                                                    layout=layout)[0],
                        grads)
                else:
                    tiles, layout = _ov.carve_tiles(grads, _ov_plan)
            else:
                tiles, layout = _ov.scatter_tiles(grads, _ov_plan)
            if extra_acc is not None:
                acc_tiles, _ = _ov.scatter_tiles(extra_acc, _ov_plan)
                tiles = tuple((a + t) / k
                              for a, t in zip(acc_tiles, tiles))
            if params is not None:
                p_tiles, p_layout = _ov.carve_tiles(params, _ov_plan)
                expected = p_layout.fingerprint()
            else:
                p_tiles = None
                expected = (next(iter(_init_fingerprints))
                            if len(_init_fingerprints) == 1 else None)
            if expected is not None and expected != layout.fingerprint():
                raise ValueError(
                    "overlap + sharded_update requires gradients and "
                    "params to share one layer-aware bucket layout, but "
                    "they plan differently (dtype or structure "
                    "divergence between the gradient tree and the param "
                    "tree — e.g. a cast-to-bf16 transform chained "
                    "before this one); use the replicated path or align "
                    "the dtypes")
            upd_tiles, new_inner = inner.update(tiles, inner_state,
                                                p_tiles)
            updates = _ov.gather_updates(upd_tiles, layout, _ov_plan)
            return updates, new_inner
        if fired and fire is not None:
            reduced = jax.lax.cond(
                fire,
                lambda g: as_varying(g, axis_name),
                lambda g: as_varying(_ov.reduce_full(g, _ov_plan),
                                        axis_name),
                grads)
        else:
            reduced = grads if fired else _ov.reduce_full(grads, _ov_plan)
        if extra_acc is not None:
            racc = _ov.reduce_full(extra_acc, _ov_plan)
            reduced = jax.tree_util.tree_map(
                lambda a, g: (a + g) / k, racc, reduced)
        updates, new_inner = inner.update(reduced, inner_state, params)
        return updates, new_inner

    def init_fn(params):
        acc = (jax.tree_util.tree_map(jnp.zeros_like, params) if k > 1
               else None)
        # the error-feedback residual starts at zero: no carried error
        # before the first quantized reduction.  Overlapped dispatch is
        # feedback-free (the backward pass cannot thread per-step
        # state), so its residual stays None — untouched.
        residual = (jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if fmt is not None and _ov_plan is None else None)
        if sharded:
            try:
                n = jax.lax.axis_size(axis_name)
            except NameError as exc:
                raise ValueError(
                    f"sharded_update=True: init must run INSIDE the "
                    f"mapped program (shard_map/pmap over axis_name="
                    f"{axis_name!r}) because the optimizer state is this "
                    f"worker's 1/N bucket tiles — wrap opt.init in the "
                    f"mesh program and carry the state with "
                    f"state_partition_specs(..., sharded_update=True). "
                    f"(sharded mode may have been enabled by "
                    f"HOROVOD_SHARDED_UPDATE=1)") from exc
            if _ov_plan is not None:
                # layer-aware layout: the state tiles must line up with
                # the per-layer buckets the backward-scan taps scatter
                from . import overlap as _ov
                p_tiles, layout = _ov.carve_tiles(params, _ov_plan)
                _init_fingerprints.add(layout.fingerprint())
                inner_state = inner.init(p_tiles)
            else:
                _leaves, _lnames, _lspecs, layout = _sharded_layout(
                    params, n, op, prescale_factor, postscale_factor,
                    _resolve_threshold(threshold_bytes),
                    align=fmt.block_size if fmt else 1,
                    spec_plan=spec_plan)
                _init_fingerprints.add((layout.shapes, layout.buckets))
                inner_state = inner.init(
                    shard_tree_like(params, layout, axis_name))
        else:
            inner_state = inner.init(params)
        return _DistState(inner=inner_state, acc=acc,
                          count=jnp.zeros([], jnp.int32),
                          residual=residual)

    def update_fn(grads, state, params=None):
        if _ov_plan is not None:
            # overlapped dispatch: a trace-time handshake with the
            # overlapped_backprop context tells us whether the model's
            # taps already staged the reductions inside the backward
            # pass (fired) or the identical layer-aware plan must run
            # here at the boundary — both land on the same weights
            n_fired, fire = _ov_plan.consume_fired()
            fired = n_fired > 0
            if k == 1:
                updates, new_inner = _ov_step(grads, state.inner,
                                              params, fired, fire=fire)
                return updates, _DistState(new_inner, state.acc,
                                           state.count, state.residual)
            count = state.count + 1
            is_boundary = count % k == 0

            def _zeros(tree):
                return jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), tree)

            def ov_do_step(args):
                acc_prev, g, inner_state = args
                updates, new_inner = _ov_step(g, inner_state, params,
                                              fired, extra_acc=acc_prev)
                return (updates,
                        as_varying(_zeros(acc_prev), axis_name),
                        new_inner)

            def ov_skip_step(args):
                acc_prev, g, inner_state = args
                return (_zeros(g), jax.tree_util.tree_map(
                    lambda a, b: a + b, acc_prev, g), inner_state)

            updates, acc, new_inner = jax.lax.cond(
                is_boundary, ov_do_step, ov_skip_step,
                (state.acc, grads, state.inner))
            return updates, _DistState(new_inner, acc, count,
                                       state.residual)
        residual = getattr(state, "residual", None)
        if k == 1:
            if hl_enabled or recovery is not None:
                # the sentinel/recovery cadence needs a step counter:
                # with either tap armed, count advances every update
                # (k == 1 has no boundary arithmetic to disturb)
                count = state.count + 1
                taps = None
                if hl_enabled:
                    from ..health.taps import HealthTaps
                    taps = HealthTaps(axis_name, count, hl_every)
                updates, new_inner, new_res = _step(
                    grads, state.inner, params, residual, taps=taps)
                if recovery is not None:
                    _emit_recovery(count, count, new_inner, new_res)
                return updates, _DistState(new_inner, state.acc, count,
                                           new_res)
            updates, new_inner, new_res = _step(grads, state.inner,
                                                params, residual)
            return updates, _DistState(new_inner, state.acc, state.count,
                                       new_res)
        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.acc, grads)
        count = state.count + 1
        is_boundary = count % k == 0

        def _fresh_zeros(tree):
            # constants are replicated under shard_map VMA tracking,
            # keeping cond branch output types aligned
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), tree)

        def do_step(args):
            acc, inner_state, residual = args
            mean_acc = jax.tree_util.tree_map(lambda a: a / k, acc)
            taps = None
            if hl_enabled:
                # taps under the boundary cond: intermediate micro-
                # steps observe nothing (their gradients only
                # accumulate locally).  The sentinel cadence divides
                # the BOUNDARY ordinal (count // k), not the raw
                # micro-step counter — gating on count would alias
                # the cadence against k (k=32 at the default
                # check_every=32 would gather at EVERY boundary)
                from ..health.taps import HealthTaps
                taps = HealthTaps(axis_name, count, hl_every,
                                  cadence_step=count // k)
            updates, new_inner, new_res = _step(mean_acc, inner_state,
                                                params, residual,
                                                taps=taps)
            if recovery is not None:
                # like the sentinel, the snapshot cadence divides the
                # BOUNDARY ordinal, not the raw micro-step counter
                _emit_recovery(count // k, count, new_inner, new_res)
            return (updates, as_varying(_fresh_zeros(acc), axis_name), new_inner,
                    new_res)

        def skip_step(args):
            acc, inner_state, residual = args
            return _fresh_zeros(acc), acc, inner_state, residual

        if axis_name is not None:
            updates, acc, new_inner, new_res = jax.lax.cond(
                is_boundary, do_step, skip_step,
                (acc, state.inner, residual))
        else:
            # eager path: python control flow is fine
            if bool(is_boundary):
                updates, acc, new_inner, new_res = do_step(
                    (acc, state.inner, residual))
            else:
                updates, acc, new_inner, new_res = skip_step(
                    (acc, state.inner, residual))
        return updates, _DistState(new_inner, acc, count, new_res)

    if _ov_plan is not None:
        from . import overlap as _ov
        _ov.register_transform(update_fn, _ov_plan)
    return optax.GradientTransformation(init_fn, update_fn)


def state_partition_specs(state: _DistState, axis_name: str,
                          sharded_update: bool = False):
    """PartitionSpecs for a ``_DistState`` crossing shard_map boundaries.

    With ``backward_passes_per_step > 1`` the gradient accumulator holds
    *local* (per-worker, un-reduced) gradients, so it is varying over the
    worker axis and must be sharded over it; the inner optimizer state and
    counter are replicated.  Use these as in/out specs when the optimizer
    state is carried across separate shard_map'd step calls.

    With ``sharded_update=True`` the inner state lives on the flat
    bucket-tile layout: every non-scalar inner leaf is this worker's 1/N
    tile (varying over the worker axis → sharded spec), while scalar
    leaves (step counters) stay replicated.

    The quantized-wire error-feedback ``residual`` is this worker's own
    accumulated quantization error — per-worker data exactly like the
    ``backward_passes_per_step`` accumulator, so it is varying over the
    worker axis and shards over it.
    """
    from jax.sharding import PartitionSpec as P
    if sharded_update:
        inner = jax.tree_util.tree_map(
            lambda leaf: P(axis_name) if getattr(leaf, "ndim", 0) else P(),
            state.inner)
    else:
        inner = jax.tree_util.tree_map(lambda _: P(), state.inner)
    acc = (None if state.acc is None else
           jax.tree_util.tree_map(lambda _: P(axis_name), state.acc))
    residual = getattr(state, "residual", None)
    residual = (None if residual is None else
                jax.tree_util.tree_map(lambda _: P(axis_name), residual))
    return _DistState(inner=inner, acc=acc, count=P(), residual=residual)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         named_parameters=None,  # accepted for API parity
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         op: str = ReduceOp.AVERAGE,
                         gradient_predivide_factor: float = 1.0,
                         axis_name: Optional[str] = None,
                         threshold_bytes: Optional[int] = None,
                         process_set=None,
                         sharded_update: Optional[bool] = None,
                         wire_format: Optional[str] = None,
                         wire_block_size: Optional[int] = None,
                         overlap: Optional[bool] = None,
                         overlap_layers: str = "layers",
                         health: Optional[bool] = None,
                         health_check_every: Optional[int] = None,
                         param_specs=None,
                         model_axes: Optional[Tuple[str, ...]] = None
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer with distributed gradient reduction.

    Mirrors the reference's ``hvd.DistributedOptimizer`` signature
    (``named_parameters`` is accepted and ignored: pytree paths name the
    tensors).  ``gradient_predivide_factor`` splits the averaging between a
    pre-scale (1/f before the sum) and post-scale (f/n after), exactly as
    the reference does to control overflow in low-precision wires.
    """
    prescale, postscale = 1.0, 1.0
    if gradient_predivide_factor != 1.0:
        if op != ReduceOp.AVERAGE:
            raise ValueError(
                "gradient_predivide_factor requires op=Average")
        prescale = 1.0 / gradient_predivide_factor
        postscale = gradient_predivide_factor
    return DistributedGradientTransform(
        inner=optimizer, op=op, axis_name=axis_name,
        backward_passes_per_step=backward_passes_per_step,
        compression=compression, prescale_factor=prescale,
        postscale_factor=postscale, threshold_bytes=threshold_bytes,
        process_set=process_set, sharded_update=sharded_update,
        wire_format=wire_format, wire_block_size=wire_block_size,
        overlap=overlap, overlap_layers=overlap_layers,
        health=health, health_check_every=health_check_every,
        param_specs=param_specs, model_axes=model_axes)


def broadcast_parameters(params, root_rank: int = 0, process_set=None):
    """Broadcast a parameter pytree from ``root_rank`` to all workers.

    Reference: ``horovod/torch/functions.py`` broadcast_parameters — called
    once after init so every worker starts from identical weights.  Under a
    single controller, params are already one logical (replicated) array; a
    cross-process sync is performed when multiple processes exist.
    """
    from .. import api
    return jax.tree_util.tree_map(
        lambda p: api.broadcast(p, root_rank, process_set=process_set),
        params)


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              process_set=None):
    """Reference: broadcast_optimizer_state (state-pytree walk + bcast)."""
    from .. import api

    def bcast_leaf(leaf):
        if hasattr(leaf, "dtype"):
            return api.broadcast(leaf, root_rank, process_set=process_set)
        return leaf

    return jax.tree_util.tree_map(bcast_leaf, opt_state)
