"""Compiled collective kernels over a worker mesh.

Reference parity: this is the data plane — the TPU-native replacement for
``horovod/common/ops/nccl_operations.cc`` / ``mpi_operations.cc`` /
``gloo_operations.cc`` (SURVEY.md §2.1, L0).  Instead of hand-driving NCCL
streams, every collective is a jit-compiled ``shard_map`` program over the
process set's mesh; XLA schedules the transfers over ICI/DCN.  The
reference's fusion buffer (``MemcpyInFusionBuffer`` → one ``ncclAllReduce``
→ ``MemcpyOutFusionBuffer``) becomes flatten–concat–one ``psum``–split
inside a single XLA program, which XLA lowers to one fused all-reduce.

Tensor semantics on an SPMD substrate
-------------------------------------
The reference's contract is "every worker contributes a same-shaped tensor;
all receive the reduction".  Under a single controller there are two ways a
per-worker contribution can exist, and both are supported:

* **stacked**: an array of shape ``[num_workers, ...]`` sharded over the
  worker axis — shard *i* is worker *i*'s contribution.  This is the real
  communication path; it is what rank-dependent-input tests exercise.
* **replicated**: an ordinary (unsharded or replicated) array — every worker
  holds the same value, so the reduction is computed without communication
  (``sum = x * n``), exactly as the math demands.

Compiled kernels are cached per (process set, op, signature); the first call
pays XLA compilation, steady-state calls are dispatch-only — the analog of
the reference's response-cache steady state.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import chaos as _chaos
from .. import metrics as _metrics
from .. import tracing as _tracing
from ..compression import (WireFormat, dequantize_blocks, quantize_blocks,
                           resolve_wire_format)
from ..runtime import ReduceOp

#: Negotiated straggler-tolerance policies for the DCN stage of a
#: hierarchical reduce (OptiReduce, arXiv:2310.06993 — tail latency, not
#: the mean, governs cloud allreduce throughput):
#:
#: * ``strict``  — today's behavior: the cross-group psum waits for every
#:   host, one straggler stalls the fused bucket.
#: * ``bounded`` — the DCN stage proceeds at HOROVOD_TAIL_DEADLINE_MS
#:   with the k contributions that arrived, applying an n/k scale
#:   correction so the expected reduction is unbiased.
#: * ``stale``   — a missing host's previous-round chunk is substituted
#:   (bounded staleness), with a per-bucket per-host staleness counter
#:   capped by HOROVOD_TAIL_MAX_STALENESS: a host at the cap is waited
#:   out (strict for that host) until it contributes fresh data again.
TAIL_POLICIES = ("strict", "bounded", "stale")

_m_tail_rounds = _metrics.counter(
    "hvd_tail_rounds_total",
    "DCN tail rounds of the hierarchical reduce, by effective policy",
    labels=("policy",))

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# quantized collective staging (block-scaled int8/fp8 wire formats)
# ---------------------------------------------------------------------------
# A plain psum of a quantized payload overflows immediately (two int8
# summands already exceed the lane), so a quantized reduction is a
# SCHEDULE REWRITE, not a cast: quantize blocks -> exchange quantized
# tiles + their fp32 scales (reduce-scatter staged as a tiled all_to_all,
# all-gather staged as a tiled all_gather) -> dequantize and accumulate
# in fp32.  Every worker applies the same dequantized tiles (its own tile
# included, AS QUANTIZED), so replicas stay bit-identical.  EQuARX
# (arXiv:2506.17615) is the XLA-resident precedent.


def quantized_sum_scatter_p(flat, axis_name: str, fmt: WireFormat,
                            error_feedback: bool = False):
    """Reduce-scatter of a quantized 1-D buffer, fp32 accumulation.

    ``flat`` is this worker's fp32 contribution, with
    ``len(flat) % (n * fmt.block_size) == 0`` (callers pad; zero padding
    quantizes exactly).  Each worker receives every peer's quantized tile
    for its 1/n slice and accumulates them in fp32 — the wire carries
    1-byte lanes plus one fp32 scale per block, never a full-width
    gradient.  Returns ``(tile_sum, residual)`` where ``tile_sum`` is the
    fp32 SUM tile of length ``len(flat)//n`` and ``residual`` is this
    worker's local quantization error (``error_feedback=True``) or None.
    """
    n = lax.axis_size(axis_name)
    q, s = quantize_blocks(flat, fmt)
    residual = None
    if error_feedback:
        residual = flat.astype(jnp.float32) - dequantize_blocks(q, s, fmt)
    qx = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                        tiled=True)
    sx = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                        tiled=True)
    deq = dequantize_blocks(qx, sx, fmt).reshape(n, -1)
    return jnp.sum(deq, axis=0), residual


def quantized_all_gather_p(tile, axis_name: str, fmt: WireFormat):
    """All-gather of a quantized 1-D tile: every worker receives the same
    quantized payloads (its own included), so the dequantized full buffer
    is bit-identical on every replica.  ``len(tile)`` must be a multiple
    of ``fmt.block_size``.  Gather-side quantization is round-to-nearest
    without feedback: the value quantized is the already-reduced tile,
    identical everywhere, so there is no per-worker error to carry."""
    q, s = quantize_blocks(tile, fmt)
    qg = lax.all_gather(q, axis_name, tiled=True)
    sg = lax.all_gather(s, axis_name, tiled=True)
    return dequantize_blocks(qg, sg, fmt)


def quantized_allreduce_p(x, axis_name: str, fmt: WireFormat,
                          op: str = ReduceOp.SUM, residual=None,
                          error_feedback: bool = False,
                          denom: Optional[int] = None):
    """Drop-in for ``psum``(+average) with a quantized wire: RS + AG
    staging, fp32 accumulation, any input shape (padded internally to a
    multiple of ``n * fmt.block_size``).

    ``residual`` (optional, same shape as ``x``, fp32) is this worker's
    carried error-feedback term: it is added to the contribution before
    quantization, and with ``error_feedback=True`` the new residual
    (``contribution - dequantized(quantized(contribution))``) is
    returned.  Returns ``(reduced, new_residual_or_None)``; ``reduced``
    has ``x``'s shape and dtype.

    ``denom`` overrides the Average divisor (default: the axis size) —
    the spec-aware gradient plane divides by the GLOBAL batch degree of
    a multi-axis mesh while reducing over the data axis alone.  The
    division happens on the scattered tile, BEFORE the gather-side
    quantization, so the averaged values ride the wire (same staging as
    the default path, just a different constant).
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"quantized allreduce supports op=Sum/Average, got {op!r}")
    n = lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    total = flat.shape[0]
    if residual is not None:
        flat = flat + residual.reshape(-1).astype(jnp.float32)
    pad = (-total) % (n * fmt.block_size)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    tile, new_res = quantized_sum_scatter_p(
        flat, axis_name, fmt, error_feedback=error_feedback)
    if op == ReduceOp.AVERAGE:
        tile = tile / (n if denom is None else denom)
    red = quantized_all_gather_p(tile, axis_name, fmt)
    if pad:
        red = red[:total]
        if new_res is not None:
            new_res = new_res[:total]
    if new_res is not None:
        new_res = new_res.reshape(shape)
    return red.reshape(shape).astype(dtype), new_res


# ---------------------------------------------------------------------------
# tail-tolerant DCN reduce (deadline-bounded / bounded-staleness policies)
# ---------------------------------------------------------------------------
# An XLA collective always completes — the *deadline* lives in the eager
# runtime gate (tail_round below), which decides per round which hosts'
# contributions count and feeds the compiled program a participation
# mask.  The compiled side here is the policy arithmetic: masked sum
# with n/k scale correction (bounded), or per-host substitution from
# the previous round's gathered contributions (stale).  The mask is
# agreed with a pmin over the mesh axes first — the membership-agreement
# round a real tail-tolerant transport (OptiReduce) must run, and the
# reason replicas can never diverge on which contributions were summed.


def tail_allreduce_p(chunk, cross_axis: str, tail_policy: str = "strict",
                     present=None, prev=None, staleness=None,
                     max_staleness: int = 0, wire_format=None,
                     agree_axes: Tuple[str, ...] = ()):
    """Tail-tolerant SUM reduce of a 1-D ``chunk`` over ``cross_axis``
    (the DCN hop of a hierarchical reduce).

    ``present`` is the round's participation mask (shape
    ``[axis_size(cross_axis)]``, 1.0 = arrived by the deadline) — a
    *runtime input*, so strict/bounded A/B runs as one compiled program.
    It is hardened with ``lax.pmin`` over ``cross_axis`` and
    ``agree_axes`` before use: every replica sums exactly the commonly
    agreed contributions (a host counts only if EVERY replica has it).

    * ``strict``: plain (or quantized) psum — byte-identical to the
      pre-tail schedule; ``present`` is ignored.
    * ``bounded``: ``psum(chunk * m) * n/k`` with the scale correction
      gated by ``where(k == n)`` — an all-ones mask is bit-identical to
      strict (×1.0 and the skipped correction are exact).
    * ``stale``: the chunk crosses DCN as an ``all_gather`` (the
      transpose-allreduce shape tail-tolerant transports use: per-host
      contributions must be addressable to substitute one), missing
      hosts take their slot from ``prev`` (the previous round's agreed
      per-host contributions, ``[n, len(chunk)]``), and ``staleness``
      (int32 ``[n]``) counts consecutive substitutions per host —
      a host at ``max_staleness`` is forced present (waited out).

    Returns ``(reduced, new_prev, new_staleness)``; the state outputs
    are None except under ``stale``.
    """
    if tail_policy not in TAIL_POLICIES:
        raise ValueError(
            f"tail_policy must be one of {TAIL_POLICIES}, got "
            f"{tail_policy!r}")
    fmt = resolve_wire_format(wire_format)
    n = lax.axis_size(cross_axis)
    if tail_policy == "strict":
        if fmt is not None:
            red, _ = quantized_allreduce_p(chunk, cross_axis, fmt,
                                           op=ReduceOp.SUM)
        else:
            red = lax.psum(chunk, cross_axis)
        return red, None, None
    if present is None:
        raise ValueError(
            f"tail_policy={tail_policy!r} needs a participation mask "
            f"(present=[{n}] floats; all-ones = no deadline fired)")
    m = jnp.asarray(present).astype(jnp.float32)
    # membership agreement: the conservative intersection across every
    # replica of the mesh — the collective the tail schedule ADDS
    for ax in (cross_axis,) + tuple(agree_axes):
        m = lax.pmin(m, ax)
    if tail_policy == "bounded":
        own = m[lax.axis_index(cross_axis)]
        contrib = chunk * own.astype(chunk.dtype)
        if fmt is not None:
            red, _ = quantized_allreduce_p(contrib, cross_axis, fmt,
                                           op=ReduceOp.SUM)
        else:
            red = lax.psum(contrib, cross_axis)
        k = jnp.sum(m)
        # n/k scale correction for the k contributors present; gated so
        # a full round never pays a (×1.0) rounding step
        corrected = red * (n / jnp.maximum(k, 1.0)).astype(red.dtype)
        return jnp.where(k >= n, red, corrected), None, None
    # stale
    if prev is None or staleness is None:
        raise ValueError(
            "tail_policy='stale' carries per-bucket state: pass prev "
            f"([{n}, len(chunk)] previous-round contributions) and "
            f"staleness (int32 [{n}]) — zeros on the first round")
    if max_staleness >= 0:
        # cap: a host substituted max_staleness consecutive rounds must
        # be waited out — its CURRENT contribution is used (the eager
        # gate enforces the matching wait on the wall clock)
        m = jnp.where(staleness >= max_staleness, jnp.float32(1.0), m)
    if fmt is not None:
        pad = (-chunk.shape[0]) % fmt.block_size
        padded = (jnp.concatenate([chunk, jnp.zeros((pad,), chunk.dtype)])
                  if pad else chunk)
        q, s = quantize_blocks(padded, fmt)
        qg = lax.all_gather(q, cross_axis, tiled=False)
        sg = lax.all_gather(s, cross_axis, tiled=False)
        gathered = dequantize_blocks(
            qg.reshape(-1), sg.reshape(-1), fmt).reshape(n, -1)
        if pad:
            gathered = gathered[:, :chunk.shape[0]]
        gathered = gathered.astype(chunk.dtype)
    else:
        gathered = lax.all_gather(chunk, cross_axis, tiled=False)
    eff = jnp.where((m > 0)[:, None], gathered, prev.astype(chunk.dtype))
    red = jnp.sum(eff, axis=0)
    new_staleness = jnp.where(m > 0, 0, staleness + 1).astype(
        staleness.dtype)
    return red, eff, new_staleness


def is_stacked(x, ps) -> bool:
    """True when ``x`` carries per-worker contributions in dim 0.

    Detection: leading dim equals the process-set size AND the array is
    sharded over the process-set axis in dim 0.
    """
    if not hasattr(x, "ndim") or x.ndim == 0:
        return False
    if x.shape[0] != ps.size():
        return False
    sharding = getattr(x, "sharding", None)
    if isinstance(sharding, NamedSharding):
        spec = sharding.spec
        return len(spec) > 0 and spec[0] == ps.axis
    return False


def spans_processes(ps) -> bool:
    """True when the process set's mesh includes devices of other processes
    (the collective must ride DCN/ICI across hosts).  Cached per set."""
    return ps.spans_processes


def stack_on_workers(values: Sequence, ps=None):
    """Build a stacked per-worker array: ``values[i]`` becomes worker *i*'s
    contribution.  TPU-native helper for the reference's rank-dependent-input
    idiom (each rank constructs its own tensor).

    Multi-process: every process must call this with the same ``values``
    (the SPMD contract); each materializes only its addressable shards.
    """
    from .. import runtime
    ps = ps or runtime._get_global_process_set()
    vals = [np.asarray(v) for v in values]
    if len(vals) != ps.size():
        raise ValueError(
            f"need one value per worker ({ps.size()}), got {len(vals)}")
    arr = np.stack(vals)
    sharding = NamedSharding(ps.mesh, P(ps.axis))
    if not spans_processes(ps):
        return jax.device_put(jnp.asarray(arr), sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def lift_to_workers(x, ps):
    """Lift this process's local array to a stacked per-worker global array.

    The eager multi-process contribution path (reference: each rank's
    tensor in EnqueueTensorAllreduce): every chip this process drives
    contributes ``x``; peer processes' chips contribute their own values.
    All processes must lift the same (name, shape, dtype) in the same
    cycle — the property the cross-process controller negotiates.
    """
    x = np.asarray(x)
    n = ps.size()
    sharding = NamedSharding(ps.mesh, P(ps.axis))

    def cb(idx):
        rows = len(range(*idx[0].indices(n)))
        return np.broadcast_to(x, (rows,) + x.shape)

    return jax.make_array_from_callback((n,) + x.shape, sharding, cb)


def worker_values(fn, ps=None):
    """``worker_values(lambda r: ...)`` → stacked array of per-worker values."""
    from .. import runtime
    ps = ps or runtime._get_global_process_set()
    return stack_on_workers([fn(r) for r in range(ps.size())], ps)


def _reduce_shard(x, axis_name: str, op: str, n: int):
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.ADASUM):
        r = lax.psum(x, axis_name)
        if op == ReduceOp.AVERAGE:
            r = r / n if jnp.issubdtype(x.dtype, jnp.floating) else r // n
        return r
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    if op == ReduceOp.PRODUCT:
        # No lax.pprod: gather then reduce locally (log-depth on ICI).
        return jnp.prod(lax.all_gather(x, axis_name), axis=0)
    raise ValueError(f"unsupported reduce op: {op}")


_SUMMABLE = (ReduceOp.SUM, ReduceOp.AVERAGE)


# ---------------------------------------------------------------------------
# compiled kernel factories (cached)
# ---------------------------------------------------------------------------
# Cache key includes mesh identity via (ps_id, mesh devices tuple) — process
# sets can be removed and re-created with the same id.


@functools.lru_cache(maxsize=1024)
def _stacked_allreduce_fn(mesh_key, axis, op, n, shapes, dtypes,
                          has_prescale, has_postscale, fuse,
                          wire_format="none", wire_block=0):
    """Fused allreduce of stacked arrays: one psum per bucket.

    ``shapes``/``dtypes`` describe each array *without* the leading worker
    dim.  Returns a jitted fn ``f(prescale, postscale, *arrays) -> tuple``.
    ``wire_format != "none"`` replaces the fused psum with the quantized
    RS+AG staging (``quantized_allreduce_p``) — only reachable when
    HOROVOD_COMPRESSION_DCN_ONLY is off, since a flat mesh has no
    separate DCN stage to restrict to.
    """
    mesh = _MESHES[mesh_key]
    fmt = resolve_wire_format(wire_format, wire_block or None)

    def shard_fn(prescale, postscale, *xs):
        # each shard arrives as [1, ...]; drop the worker dim
        locals_ = [x[0] for x in xs]
        if has_prescale:
            locals_ = [x * prescale.astype(x.dtype) for x in locals_]
        if fuse and op in _SUMMABLE and (len(locals_) > 1
                                         or fmt is not None):
            # fusion buffer: flatten-concat → ONE psum → split (SURVEY §5.8)
            sizes = [int(np.prod(s)) if s else 1 for s in shapes]
            flat = (jnp.concatenate([x.reshape(-1) for x in locals_])
                    if len(locals_) > 1 else locals_[0].reshape(-1))
            if fmt is not None:
                red, _ = quantized_allreduce_p(flat, axis, fmt, op=op)
            else:
                red = lax.psum(flat, axis)
                if op == ReduceOp.AVERAGE:
                    red = red / n
            outs = []
            offset = 0
            for s, sz in zip(shapes, sizes):
                outs.append(red[offset:offset + sz].reshape(s))
                offset += sz
        else:
            outs = [_reduce_shard(x, axis, op, n) for x in locals_]
        if has_postscale:
            outs = [x * postscale.astype(x.dtype) for x in outs]
        return tuple(outs)

    in_specs = (P(), P()) + tuple(P(axis) for _ in shapes)
    out_specs = tuple(P() for _ in shapes)
    f = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    return jax.jit(f)


@functools.lru_cache(maxsize=1024)
def _replicated_allreduce_fn(mesh_key, op, n, nshapes,
                             has_prescale, has_postscale):
    """Allreduce when every worker holds the same value: pure math, no comm.

    sum = x*n, average = x, min/max = x, product = x**n.  Matches the
    reference's semantics bit-for-bit cheaper than moving bytes over ICI.
    """

    def f(prescale, postscale, *xs):
        outs = []
        for x in xs:
            y = x * prescale.astype(x.dtype) if has_prescale else x
            if op == ReduceOp.SUM:
                y = y * jnp.asarray(n, dtype=y.dtype)
            elif op == ReduceOp.PRODUCT:
                y = y ** n
            # AVERAGE / MIN / MAX / ADASUM of n identical values = identity
            if has_postscale:
                y = y * postscale.astype(y.dtype)
            outs.append(y)
        return tuple(outs)

    return jax.jit(f)


@functools.lru_cache(maxsize=1024)
def _hier_allreduce_fn(mesh_key, axis, op, n, shapes, n_groups, group,
                       has_prescale, has_postscale,
                       wire_format="none", wire_block=0,
                       tail_policy="strict", max_staleness=0):
    """Two-stage hierarchical allreduce (reference:
    NCCLHierarchicalAllreduce, SURVEY §5.8): reduce-scatter within the
    group (ICI), allreduce the 1/group-size chunk across groups (DCN),
    all-gather within the group — DCN bytes drop by the group size.

    The worker mesh is viewed as 2-D (groups × group); the stacked dim
    shards over both axes, process-major.  ``wire_format != "none"``
    quantizes the cross-group (DCN) stage only — block-scaled tiles +
    scales instead of a full-width psum — the negotiated per-bucket wire
    format under its HOROVOD_COMPRESSION_DCN_ONLY default.

    ``tail_policy != "strict"`` makes the DCN stage tail-tolerant
    (``tail_allreduce_p``): the jitted fn grows a runtime participation
    mask argument (``present``, fp32 ``[n_groups]``, from the eager
    deadline gate ``tail_round``), and under ``stale`` additionally the
    per-bucket state arguments/outputs (``prev`` global
    ``[n, n_groups, chunk]`` sharded over the mesh, ``staleness`` int32
    ``[n_groups]`` replicated):

    * strict : ``f(pre, post, *arrays) -> outs``
    * bounded: ``f(pre, post, present, *arrays) -> outs``
    * stale  : ``f(pre, post, present, prev, staleness, *arrays)
               -> outs + (new_prev, new_staleness)``
    """
    mesh1d = _MESHES[mesh_key]
    devs = np.asarray(mesh1d.devices).reshape(n_groups, group)
    mesh = jax.sharding.Mesh(devs, ("hvd_cross", "hvd_local"))
    fmt = resolve_wire_format(wire_format, wire_block or None)

    def shard_fn(prescale, postscale, *rest):
        if tail_policy == "strict":
            present = prev = staleness = None
            xs = rest
        elif tail_policy == "bounded":
            present, xs = rest[0], rest[1:]
            prev = staleness = None
        else:
            present, prev, staleness = rest[0], rest[1][0], rest[2]
            xs = rest[3:]
        locals_ = [x[0] for x in xs]  # [1, ...] shard → drop worker dim
        if has_prescale:
            locals_ = [x * prescale.astype(x.dtype) for x in locals_]
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        flat = (jnp.concatenate([x.reshape(-1) for x in locals_])
                if len(locals_) > 1 else locals_[0].reshape(-1))
        total = flat.shape[0]
        pad = (-total) % group
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        # stage 1 (ICI): each chip keeps 1/group of the intra-group sum
        chunk = lax.psum_scatter(flat, "hvd_local", scatter_dimension=0,
                                 tiled=True)
        # stage 2 (DCN): allreduce the chunk across groups
        new_prev = new_stal = None
        if tail_policy != "strict":
            chunk, new_prev, new_stal = tail_allreduce_p(
                chunk, "hvd_cross", tail_policy, present=present,
                prev=prev, staleness=staleness,
                max_staleness=max_staleness, wire_format=fmt,
                agree_axes=("hvd_local",))
        elif fmt is not None:
            chunk, _ = quantized_allreduce_p(chunk, "hvd_cross", fmt,
                                             op=ReduceOp.SUM)
        else:
            chunk = lax.psum(chunk, "hvd_cross")
        # stage 3 (ICI): regather the full vector within the group
        red = lax.all_gather(chunk, "hvd_local", tiled=True)
        if pad:
            red = red[:total]
        if op == ReduceOp.AVERAGE:
            red = red / n
        outs, offset = [], 0
        for s, sz in zip(shapes, sizes):
            outs.append(red[offset:offset + sz].reshape(s))
            offset += sz
        if has_postscale:
            outs = [x * postscale.astype(x.dtype) for x in outs]
        if tail_policy == "stale":
            return tuple(outs) + (new_prev[None], new_stal)
        return tuple(outs)

    axis2d = P(("hvd_cross", "hvd_local"))
    tail_in = ()
    tail_out = ()
    if tail_policy == "bounded":
        tail_in = (P(),)                      # present: replicated
    elif tail_policy == "stale":
        tail_in = (P(), axis2d, P())          # present, prev, staleness
        tail_out = (axis2d, P())              # new_prev, new_staleness
    in_specs = (P(), P()) + tail_in + tuple(axis2d for _ in shapes)
    out_specs = tuple(P() for _ in shapes) + tail_out
    f = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    return jax.jit(f)


@functools.lru_cache(maxsize=1024)
def _hier_allgather_fn(mesh_key, axis, n_groups, group):
    """Two-stage allgather: gather within the group (ICI) then across
    groups (DCN) — the HOROVOD_HIERARCHICAL_ALLGATHER analog."""
    mesh1d = _MESHES[mesh_key]
    devs = np.asarray(mesh1d.devices).reshape(n_groups, group)
    mesh = jax.sharding.Mesh(devs, ("hvd_cross", "hvd_local"))

    def shard_fn(x):
        g = lax.all_gather(x[0], "hvd_local", tiled=False)
        g = g.reshape((-1,) + g.shape[2:])
        gg = lax.all_gather(g, "hvd_cross", tiled=False)
        return gg.reshape((-1,) + gg.shape[2:])

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(("hvd_cross", "hvd_local")),
        out_specs=P(), check_vma=False))


@functools.lru_cache(maxsize=1024)
def _stacked_allgather_fn(mesh_key, axis):
    """Allgather: concatenate per-worker contributions along dim 0.

    Stacked input [n, d0, ...] → output [n*d0, ...] replicated, matching the
    reference's ``hvd.allgather`` concat-on-dim-0 contract
    (horovod/common/ops/collective_operations.cc AllgatherOp).
    """
    mesh = _MESHES[mesh_key]

    def shard_fn(x):
        g = lax.all_gather(x[0], axis, tiled=False)  # [n, d0, ...]
        return g.reshape((-1,) + g.shape[2:])

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False))


@functools.lru_cache(maxsize=1024)
def _broadcast_fn(mesh_key, axis, root):
    """Broadcast worker ``root``'s contribution to all workers.

    Stacked input [n, ...] → output [...] replicated (= shard ``root``).
    """
    mesh = _MESHES[mesh_key]

    def shard_fn(x):
        idx = lax.axis_index(axis)
        body = x[0]
        dt = body.dtype
        if dt == jnp.bool_:
            body = body.astype(jnp.int32)
        contrib = jnp.where(idx == root, body, jnp.zeros_like(body))
        out = lax.psum(contrib, axis)
        return out.astype(dt)

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False))


@functools.lru_cache(maxsize=1024)
def _alltoall_fn(mesh_key, axis):
    """All-to-all: worker i's row j goes to worker j (equal splits).

    Stacked input [n, n*c, ...]: worker i holds [n*c, ...], the k-th chunk of
    size c destined for worker k.  Output stacked [n, n*c, ...] where worker
    j receives the concatenation of every worker's j-th chunk — the
    reference's ``hvd.alltoall`` with uniform splits
    (horovod/common/ops/mpi_operations.cc MPIAlltoall).
    """
    mesh = _MESHES[mesh_key]

    def shard_fn(x):
        # x: [1, n*c, ...]; tiled all_to_all splits dim 0 into n chunks,
        # sends chunk j to worker j, concatenates what it receives
        out = lax.all_to_all(x[0], axis, split_axis=0, concat_axis=0,
                             tiled=True)
        return out[None]

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False))


@functools.lru_cache(maxsize=1024)
def _stacked_reducescatter_fn(mesh_key, axis, op, n):
    """Reduce-scatter: reduce across workers, each keeps slice i of dim 0.

    Stacked input [n, d0, ...] (d0 divisible by n) → output stacked
    [n, d0/n, ...]: worker i's shard is rows [i*d0/n:(i+1)*d0/n] of the
    reduction.  Reference: ReducescatterOp (horovod/common/ops/).
    """
    mesh = _MESHES[mesh_key]

    def shard_fn(x):
        body = x[0]
        out = lax.psum_scatter(body, axis, scatter_dimension=0, tiled=True)
        if op == ReduceOp.AVERAGE:
            out = out / n
        return out[None]

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False))


# Registry mapping hashable mesh keys to live Mesh objects (lru_cache needs
# hashable keys; Mesh hashing is identity-unstable across re-creation).
_MESHES = {}


def mesh_key(ps) -> Tuple:
    key = (ps.process_set_id, tuple(d.id for d in ps.mesh.devices.flat),
           ps.axis)
    _MESHES[key] = ps.mesh
    return key


def reset_kernel_caches():
    """Drop every compiled-kernel cache.  Called by ``runtime.init`` on
    re-initialization: after ``clear_backends`` the new incarnation's
    device objects differ in identity while their ids collide with the
    old mesh keys, so a cached jitted fn would be bound to dead devices.
    """
    _stacked_allreduce_fn.cache_clear()
    _replicated_allreduce_fn.cache_clear()
    _stacked_allgather_fn.cache_clear()
    _broadcast_fn.cache_clear()
    _alltoall_fn.cache_clear()
    _stacked_reducescatter_fn.cache_clear()
    _MESHES.clear()
    _TAIL_STATE.clear()
    from .adasum import reset_kernel_caches as _adasum_reset
    _adasum_reset()


# ---------------------------------------------------------------------------
# eager tail-round gate: the deadline decision the compiled program can't make
# ---------------------------------------------------------------------------

#: Per-bucket stale state (prev gathered contributions + staleness
#: counters), keyed by the same tuple that keys the compiled kernel —
#: one state per (mesh, signature) bucket identity.  Cleared with the
#: kernel caches on re-init.
_TAIL_STATE: Dict[Tuple, tuple] = {}


def plan_tail_round(name: str, tail_policy: str, n_groups: int,
                    deadline_s: float, max_staleness: int = 0,
                    staleness=None, stall=None):
    """Decide one DCN tail round: which cross-groups count, and how long
    the round waits on the wall clock.

    Pure decision function (no sleeping — ``tail_round`` sleeps), so
    tests pin it deterministically.  Per-group arrival lateness comes
    from the ``collective.dcn`` chaos site (``action=delay:<secs>`` =
    that group's DCN contribution arrives that late; ``action=drop`` =
    it never arrives this round); without an installed schedule every
    group arrives instantly.  Decision:

    * ``strict``  — wait out the slowest group (``wait = max lateness``);
      a dropped contribution is a transport error
      (:class:`~..chaos.ChaosConnectionError`), exactly like the other
      eager injection sites.
    * ``bounded``/``stale`` — groups later than ``deadline_s`` are
      excluded (mask 0) and the round waits ``deadline_s`` at most;
      rounds where every group makes the deadline never pay it.  Under
      ``stale``, a group whose ``staleness`` counter has reached
      ``max_staleness`` is *waited out* instead (the compiled clamp
      mirrors this, so mask and arithmetic agree).

    Observed lateness (including 0.0 for on-time groups) feeds the stall
    inspector's per-host straggler EWMA (``stall.note_lateness``).

    Returns ``(present, wait_s, lateness)``: the fp32 mask
    ``[n_groups]``, the wall-clock wait, and the per-group lateness list.
    """
    if tail_policy not in TAIL_POLICIES:
        raise ValueError(
            f"tail_policy must be one of {TAIL_POLICIES}, got "
            f"{tail_policy!r}")
    lateness = [0.0] * n_groups
    dropped = [False] * n_groups
    if _chaos.ACTIVE:
        for g in range(n_groups):
            act = _chaos.fire("collective.dcn", name=name, group=g,
                              policy=tail_policy,
                              _defer=("delay", "drop"))
            if act is None:
                continue
            if act.kind == "delay":
                lateness[g] = act.arg_float(0.05)
            elif act.kind == "drop":
                dropped[g] = True
    present = np.ones((n_groups,), np.float32)
    if tail_policy == "strict":
        if any(dropped):
            raise _chaos.ChaosConnectionError(
                f"chaos: DCN contribution of groups "
                f"{[g for g in range(n_groups) if dropped[g]]} dropped "
                f"at collective.dcn ({name})")
        wait_s = max(lateness) if lateness else 0.0
    else:
        waited = []
        deadline_fired = False
        for g in range(n_groups):
            late = float("inf") if dropped[g] else lateness[g]
            at_cap = (tail_policy == "stale" and staleness is not None
                      and int(staleness[g]) >= max_staleness)
            if late > deadline_s and not at_cap:
                present[g] = 0.0
                deadline_fired = True
            else:
                # waited out: on time, or stale-capped (cap beats drop —
                # the round must block until the host answers)
                waited.append(min(late, deadline_s)
                              if not at_cap else lateness[g])
        wait_s = max(waited) if waited else 0.0
        if deadline_fired:
            wait_s = max(wait_s, deadline_s)
    if stall is not None:
        for g in range(n_groups):
            # a DROPPED contribution never arrived: feed the censored
            # observation (>= the deadline) — else a host that drops
            # every round would score as perfectly on-time and the
            # straggler → blacklist path could never fire for total
            # loss, only for delay
            obs = (max(lateness[g], deadline_s) if dropped[g]
                   else lateness[g])
            stall.note_lateness(g, obs)
    return present, wait_s, lateness


def tail_round(name: str, tail_policy: str, n_groups: int,
               deadline_s: float, max_staleness: int = 0,
               staleness=None, stall=None):
    """One eager DCN tail round: plan (``plan_tail_round``), wait the
    planned wall-clock time, count the round
    (``hvd_tail_rounds_total{policy}``), and return the mask."""
    t0 = _tracing.now() if _tracing.ACTIVE else 0.0
    present, wait_s, lateness = plan_tail_round(
        name, tail_policy, n_groups, deadline_s,
        max_staleness=max_staleness, staleness=staleness, stall=stall)
    if tail_policy == "stale" and staleness is not None:
        # training-health feed: substitution counters AT the cap mean
        # that group's staleness budget is spent (one false branch
        # when HOROVOD_HEALTH=0)
        from .. import health as _health
        if _health.ACTIVE:
            _health.note_staleness(name, staleness, max_staleness)
    if _metrics.ACTIVE:
        _m_tail_rounds.inc(policy=tail_policy)
    if wait_s > 0:
        time.sleep(wait_s)
    if _tracing.ACTIVE:
        # the DCN phase span the critical-path analyzer pivots on:
        # which cross-groups were excluded by the deadline, and how
        # late each one ran (docs/observability.md "Distributed trace")
        _tracing.span(
            "dcn", name, t0, _tracing.now(), policy=tail_policy,
            deadline_s=float(deadline_s), wait_s=round(float(wait_s), 6),
            excluded=[g for g in range(n_groups) if present[g] == 0.0],
            lateness=[round(float(v), 6) for v in lateness])
    return present


def _tail_params():
    """(deadline_s, max_staleness, stall) from the live runtime config."""
    from .. import runtime
    st = runtime._state()
    cfg = st.config
    deadline_s = (cfg.tail_deadline_ms / 1000.0 if cfg is not None
                  else 0.25)
    max_stal = cfg.tail_max_staleness if cfg is not None else 4
    return deadline_s, max_stal, st.stall_inspector


# ---------------------------------------------------------------------------
# public eager entry points (used by the engine; one-tensor fast paths)
# ---------------------------------------------------------------------------


def _scale_arg(v) -> Tuple[jnp.ndarray, bool]:
    if v is None:
        return jnp.float32(1.0), False
    return jnp.asarray(v, dtype=jnp.float32), True


def allreduce_arrays(arrays: List, ps, op: str = ReduceOp.AVERAGE,
                     prescale_factor=None, postscale_factor=None,
                     stacked: Optional[bool] = None,
                     wire_format: str = "none",
                     wire_block: int = 0,
                     tail_policy: str = "strict",
                     tail_name: str = "allreduce",
                     tail_bucket_names: Optional[Tuple[str, ...]] = None
                     ) -> List:
    """Fused allreduce of a list of arrays over a process set (one bucket).

    ``wire_format`` is the bucket's negotiated quantized wire format
    ("none" = full width): on the hierarchical path it quantizes the
    cross-group (DCN) stage; on the flat stacked path it quantizes the
    whole fused reduction (only requested when the DCN-only policy is
    off).  The replicated no-communication path ignores it — there are
    no wire bytes to shrink.

    ``tail_policy`` is the bucket's negotiated straggler tolerance
    (:data:`TAIL_POLICIES`); it only takes effect on the hierarchical
    path — a flat mesh has no DCN stage to bound — where each dispatch
    runs one ``tail_round`` (deadline gate + chaos arrival injection +
    straggler scoring) and feeds the resulting participation mask to the
    compiled program.  ``stale`` buckets carry their previous-round DCN
    contributions and staleness counters in a per-bucket state slot
    keyed like the kernel cache.
    """
    if op == ReduceOp.ADASUM:
        from .adasum import adasum_arrays
        return adasum_arrays(arrays, ps, prescale_factor, postscale_factor)
    if stacked is None:
        stacked = is_stacked(arrays[0], ps)
    if stacked and any(is_stacked(a, ps) != stacked for a in arrays):
        raise ValueError("cannot fuse stacked and replicated tensors")
    if not stacked and spans_processes(ps):
        # eager multi-process: each process's local array is its
        # contribution — lift onto the mesh for a real DCN/ICI reduction
        arrays = [lift_to_workers(a, ps) for a in arrays]
        stacked = True
    pre, has_pre = _scale_arg(prescale_factor)
    post, has_post = _scale_arg(postscale_factor)
    n = ps.size()
    if stacked:
        shapes = tuple(tuple(a.shape[1:]) for a in arrays)
        dtypes = tuple(str(a.dtype) for a in arrays)
        fuse = len(set(dtypes)) == 1
        if op not in _SUMMABLE or not fuse:
            wire_format = "none"
        hier = None
        if op in _SUMMABLE and fuse:
            from .. import runtime
            st = runtime._state()
            hier_on = (st.config is not None
                       and st.config.hierarchical_allreduce)
            if st.engine is not None and st.engine.autotuner is not None:
                # tuned dimension: the engine's applied value (local or
                # negotiated) overrides config WITHOUT mutating it
                hier_on = st.engine._hierarchical_enabled()
            if hier_on:
                hier = ps.hier_shape()
        if hier is None or op not in _SUMMABLE or not fuse:
            tail_policy = "strict"
        if hier is not None:
            key = (mesh_key(ps), ps.axis, op, n, shapes, hier[0], hier[1],
                   has_pre, has_post, wire_format, wire_block)
            deadline_s, max_stal, stall = _tail_params()
            fn = _hier_allreduce_fn(*key, tail_policy, max_stal)
            if tail_policy == "strict":
                if _chaos.ACTIVE or _metrics.ACTIVE or _tracing.ACTIVE:
                    # strict rounds still observe injected DCN arrival
                    # delays (they wait them out — the straggler
                    # baseline), count toward the round metric, and
                    # record their dcn span for the job-wide trace
                    tail_round(tail_name, "strict", hier[0], deadline_s,
                               stall=stall)
                return list(fn(pre, post, *arrays))
            if tail_policy == "bounded":
                present = tail_round(tail_name, "bounded", hier[0],
                                     deadline_s, stall=stall)
                return list(fn(pre, post, jnp.asarray(present), *arrays))
            # stale: thread the per-bucket (prev, staleness) state.
            # The kernel-cache tuple alone is NOT a bucket identity —
            # two buckets with identical shapes/op/scales (e.g. twin
            # layers split across buckets) would share and clobber each
            # other's prev chunks — so the state key adds the bucket's
            # full tensor-name tuple (identical-name duplicates within
            # one cycle remain a documented aliasing edge)
            key = key + (tail_bucket_names
                         if tail_bucket_names is not None
                         else (tail_name,))
            state = _TAIL_STATE.get(key)
            if state is None:
                total = sum(int(np.prod(s)) if s else 1 for s in shapes)
                chunk_len = (total + (-total) % hier[1]) // hier[1]
                mesh1d = _MESHES[key[0]]
                devs = np.asarray(mesh1d.devices).reshape(hier[0], hier[1])
                mesh2d = jax.sharding.Mesh(devs, ("hvd_cross", "hvd_local"))
                prev = jax.device_put(
                    jnp.zeros((n, hier[0], chunk_len),
                              jnp.dtype(dtypes[0])),
                    NamedSharding(mesh2d, P(("hvd_cross", "hvd_local"))))
                state = (prev, jnp.zeros((hier[0],), jnp.int32))
            present = tail_round(tail_name, "stale", hier[0], deadline_s,
                                 max_staleness=max_stal,
                                 staleness=np.asarray(state[1]),
                                 stall=stall)
            outs = fn(pre, post, jnp.asarray(present), state[0], state[1],
                      *arrays)
            _TAIL_STATE[key] = (outs[-2], outs[-1])
            return list(outs[:-2])
        fn = _stacked_allreduce_fn(
            mesh_key(ps), ps.axis, op, n, shapes, dtypes, has_pre,
            has_post, fuse, wire_format, wire_block)
    else:
        fn = _replicated_allreduce_fn(
            mesh_key(ps), op, n, len(arrays), has_pre, has_post)
    return list(fn(pre, post, *arrays))


def _allgather_fn_for(ps):
    from .. import runtime
    cfg = runtime._state().config
    if cfg is not None and cfg.hierarchical_allgather:
        hier = ps.hier_shape()
        if hier is not None:
            return _hier_allgather_fn(mesh_key(ps), ps.axis, *hier)
    return _stacked_allgather_fn(mesh_key(ps), ps.axis)


def allgather_array(x, ps, peer_rows=None):
    """``peer_rows`` is the negotiation-agreed ``(procs, sizes)`` for
    this array (Allgatherv, reference: the controller's tensor-size
    gathering rides the round — see engine._negotiate); uniform sizes
    take the plain path at zero extra cost.  Without a controller
    (single process, or HOROVOD_TPU_CONTROLLER=0), cross-process
    allgather requires uniform dim-0."""
    if is_stacked(x, ps):
        return _allgather_fn_for(ps)(x)
    if spans_processes(ps):
        if peer_rows is not None:
            procs, sizes = peer_rows
            if any(s != sizes[0] for s in sizes):
                return _allgather_uneven(x, ps, procs, sizes)
        return _allgather_fn_for(ps)(lift_to_workers(x, ps))
    # replicated: every worker contributes the same tensor → tile
    n = ps.size()
    return jnp.concatenate([x] * n, axis=0)


def _allgather_uneven(x, ps, procs, sizes):
    """Uneven (Allgatherv) payload path: pad this process's rows to
    max(sizes), run ONE uniform allgather over the mesh, slice each
    worker's block back to its process's true row count.  Wire cost is
    n_workers * max(sizes) rows — the same bounded-padding trade as the
    uneven alltoall."""
    mx = max(sizes)
    x = np.asarray(x)
    if x.shape[0] < mx:
        pad = np.zeros((mx - x.shape[0],) + x.shape[1:], x.dtype)
        x = np.concatenate([x, pad], axis=0)
    full = _allgather_fn_for(ps)(lift_to_workers(x, ps))
    rows_by_proc = dict(zip(procs, sizes))
    out = []
    for w, d in enumerate(ps.mesh.devices.flat):
        r = rows_by_proc[int(d.process_index)]
        out.append(full[w * mx: w * mx + r])
    return jnp.concatenate(out, axis=0)


def broadcast_array(x, root_rank: int, ps):
    if is_stacked(x, ps):
        return _broadcast_fn(mesh_key(ps), ps.axis, int(root_rank))(x)
    if spans_processes(ps):
        return _broadcast_fn(mesh_key(ps), ps.axis, int(root_rank))(
            lift_to_workers(x, ps))
    return x  # replicated: already everywhere


def alltoall_array(x, ps, splits=None):
    n = ps.size()
    if splits is not None:
        splits = np.asarray(splits)
        if splits.ndim != 1 or splits.shape[0] != n:
            raise ValueError(f"splits must have length {n}")
        if not np.all(splits == splits[0]):
            return _alltoall_uneven(x, ps, splits)
    if not is_stacked(x, ps) and spans_processes(ps):
        x = lift_to_workers(x, ps)
    if is_stacked(x, ps):
        if x.shape[1] % n != 0:
            raise ValueError(
                f"alltoall dim-1 size {x.shape[1]} not divisible by {n} "
                f"workers; pass explicit splits")
        return _alltoall_fn(mesh_key(ps), ps.axis)(x)
    # replicated input: every worker sends the same rows, so worker j's
    # result is n copies of chunk j — realized locally, no comm.
    chunk = x.shape[0] // n
    rows = [jnp.concatenate([x[j * chunk:(j + 1) * chunk]] * n, axis=0)
            for j in range(n)]
    return stack_on_workers(rows, ps)


def _alltoall_uneven(x, ps, splits):
    """Uneven alltoall (MPI_Alltoallv parity, SURVEY §2.1).

    XLA's ``all_to_all`` is uniform-split only, so uneven splits pad
    each destination chunk to ``max(splits)`` rows, run ONE uniform
    all_to_all, and slice per receiver.  Per-worker wire cost is
    ``n * max(splits)`` rows versus the ``n * sum(splits)`` a full
    allgather would move — i.e. the overhead over true Alltoallv
    semantics is bounded by ``max(splits) / mean(splits)``, not ``n``.
    Worker *j* receives ``n * splits[j]`` rows, so the per-worker
    results are ragged and the return value is a **list** of per-worker
    arrays (matching the reference, where each rank simply sees its own
    differently-sized output tensor).
    """
    n = ps.size()
    splits = np.asarray(splits)
    offs = np.concatenate([[0], np.cumsum(splits)])
    mx = int(splits.max())
    if not is_stacked(x, ps) and spans_processes(ps):
        x = lift_to_workers(x, ps)
    if is_stacked(x, ps):
        # [n, sum, ...] -> padded [n, n*mx, ...]: sender i's chunk for
        # receiver j sits at [i, j*mx : j*mx + splits[j]]
        tail = x.shape[2:]
        padded = jnp.zeros((x.shape[0], n * mx) + tail, x.dtype)
        for j in range(n):
            if splits[j]:
                padded = padded.at[:, j * mx: j * mx + int(splits[j])].set(
                    x[:, offs[j]:offs[j + 1]])
        out = _alltoall_fn(mesh_key(ps), ps.axis)(padded)
        # worker j's block: mx rows from each sender i at [i*mx:(i+1)*mx],
        # of which the first splits[j] are payload
        return [jnp.concatenate(
            [out[j, i * mx: i * mx + int(splits[j])] for i in range(n)],
            axis=0) for j in range(n)]
    return [jnp.concatenate([x[offs[j]:offs[j + 1]]] * n, axis=0)
            for j in range(n)]


def reducescatter_array(x, ps, op: str = ReduceOp.AVERAGE):
    n = ps.size()
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        # matches the reference: reducescatter supports Sum/Average only
        raise ValueError(f"reducescatter unsupported op {op}")
    if not is_stacked(x, ps) and spans_processes(ps):
        x = lift_to_workers(x, ps)
    if is_stacked(x, ps):
        if x.shape[1] % n != 0:
            raise ValueError(
                f"reducescatter dim-1 {x.shape[1]} not divisible by {n}")
        return _stacked_reducescatter_fn(mesh_key(ps), ps.axis, op, n)(x)
    # replicated: reduction of n copies, worker i keeps slice i
    if x.shape[0] % n != 0:
        raise ValueError(f"reducescatter dim-0 {x.shape[0]} not divisible by {n}")
    scale = {ReduceOp.SUM: n, ReduceOp.AVERAGE: 1}.get(op)
    if scale is None:
        raise ValueError(f"reducescatter unsupported op {op}")
    chunk = x.shape[0] // n
    rows = [x[i * chunk:(i + 1) * chunk] * scale for i in range(n)]
    return stack_on_workers(rows, ps)


# ---------------------------------------------------------------------------
# in-jit (traceable) forms — for use inside shard_map'ed training steps
# ---------------------------------------------------------------------------


def allreduce_p(x, axis_name: str, op: str = ReduceOp.AVERAGE):
    """Traceable allreduce for use inside ``shard_map``/``pjit`` programs.

    The idiomatic hot path: call inside your compiled step function with the
    mesh axis name; XLA emits one fused all-reduce over ICI.
    """
    n = lax.axis_size(axis_name)
    return _reduce_shard(x, axis_name, op, n)


def allgather_p(x, axis_name: str):
    g = lax.all_gather(x, axis_name, tiled=False)
    return g.reshape((-1,) + g.shape[2:]) if x.ndim else g


def broadcast_p(x, root_rank: int, axis_name: str):
    idx = lax.axis_index(axis_name)
    contrib = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis_name)


def alltoall_p(x, axis_name: str):
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)


def reducescatter_p(x, axis_name: str, op: str = ReduceOp.AVERAGE):
    out = lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
    if op == ReduceOp.AVERAGE:
        out = out / lax.axis_size(axis_name)
    return out


def hierarchical_allreduce_p(x, cross_axis: str, local_axis: str,
                             op: str = ReduceOp.AVERAGE,
                             wire_format=None,
                             tail_policy: str = "strict",
                             tail_present=None, tail_state=None,
                             tail_max_staleness: int = 0):
    """Traceable two-stage allreduce over a (cross, local) mesh factoring
    (reference: NCCLHierarchicalAllreduce; SURVEY §5.8 ICI/DCN analog):
    reduce-scatter over ``local_axis`` (ICI), psum the chunk over
    ``cross_axis`` (DCN), all-gather over ``local_axis`` — cross-axis
    bytes drop by the local axis size.

    ``wire_format`` (a name or :class:`~..compression.WireFormat`)
    additionally quantizes the CROSS stage only: the chunk crosses DCN as
    block-scaled int8/fp8 tiles + fp32 scales (quantize → exchange →
    dequantize-accumulate staging), dropping cross-host bytes another
    ~4x, while the ICI stages stay full-precision — the OptiReduce
    prescription (compress where bandwidth is scarcest).

    ``tail_policy`` makes the CROSS stage straggler-tolerant
    (:func:`tail_allreduce_p`; OptiReduce's other prescription — bound
    the tail where it is longest).  ``tail_present`` is the round's
    runtime participation mask (fp32 ``[axis_size(cross_axis)]``).
    ``stale`` additionally threads per-call state: ``tail_state`` is
    ``(prev, staleness)`` (previous-round gathered chunk contributions
    ``[n_cross, chunk_len]`` and int32 staleness counters ``[n_cross]``;
    zeros on the first round) and the return value becomes
    ``(reduced, (new_prev, new_staleness))``.  The default ``strict``
    path is byte-identical to the pre-tail schedule.
    """
    fmt = resolve_wire_format(wire_format)
    group = lax.axis_size(local_axis)
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % group
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunk = lax.psum_scatter(flat, local_axis, scatter_dimension=0,
                             tiled=True)
    new_state = None
    if tail_policy != "strict":
        prev, staleness = (tail_state if tail_state is not None
                           else (None, None))
        chunk, new_prev, new_stal = tail_allreduce_p(
            chunk, cross_axis, tail_policy, present=tail_present,
            prev=prev, staleness=staleness,
            max_staleness=tail_max_staleness, wire_format=fmt,
            agree_axes=(local_axis,))
        if tail_policy == "stale":
            new_state = (new_prev, new_stal)
    elif fmt is not None:
        chunk, _ = quantized_allreduce_p(chunk, cross_axis, fmt,
                                         op=ReduceOp.SUM)
    else:
        chunk = lax.psum(chunk, cross_axis)
    red = lax.all_gather(chunk, local_axis, tiled=True)
    if pad:
        red = red[:flat.shape[0] - pad]
    if op == ReduceOp.AVERAGE:
        red = red / (group * lax.axis_size(cross_axis))
    red = red.reshape(shape)
    if tail_policy == "stale":
        return red, new_state
    return red
