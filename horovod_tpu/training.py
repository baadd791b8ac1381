"""Train-step assembly: the full SPMD training program over a ParallelMesh.

This is where the framework's layers meet: the model forward (models/),
the parallel axes (parallel/), and the fused distributed gradient
reduction (optim/) compose into ONE jit-compiled shard_map program per
step — the TPU-native replacement for the reference's
DistributedOptimizer-around-autograd architecture (SURVEY.md §3.3), with
the gradient bucket fusion happening inside the compiled program where XLA
overlaps it with the backward pass.

Gradient reduction: the step runs under ``check_vma=True``, so JAX's
transpose rules insert the correct cross-shard psums for every parameter
automatically (replicated params get their partial gradients summed over
tp/pp/sp/dp as needed; sharded params stay local).  What remains for us is
the loss-averaging normalization — a uniform 1/(dp·sp) — and XLA's
all-reduce combiner batches the inserted psums into fused transfers (the
reference's fusion buffer as a compiler pass).  See reduce_grads.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .models import llama as llama_mod
from .models.llama import LlamaConfig, ParallelSpec
from .parallel.mesh import ParallelMesh
from .tracing import TracedStep
# the names the steps put on their parts, handed on under this module's name
from .scopes import (  # noqa: F401
    SCOPE_FORWARD, SCOPE_REDUCE, SCOPE_OPTIMIZER, SCOPE_SYNC_BN, SCOPE_EMBED,
    SCOPE_ATTENTION, SCOPE_MLP, SCOPE_HEAD, SCOPE_STEM, SCOPE_STAGE,
    SCOPE_SSM_MIXER, SCOPE_GMU, SCOPE_DIFF_ATTENTION, SCOPE_SSD_MIXER,
    SCOPE_SSD_SCAN, SCOPE_KDA_MIXER, SCOPE_KDA_SCAN, SCOPE_WINDOW_ATTENTION,
    SCOPE_ROPE, SCOPE_MLA_ATTENTION, SCOPE_MLA_LATENT, SCOPE_CONV_MIXER,
    SCOPE_GATED_CONV)


@dataclasses.dataclass
class TrainStep:
    """A compiled training step plus its sharding contract."""
    step_fn: Callable            # (params, opt_state, tokens, targets) -> ...
    init_fn: Callable            # (rng) -> (params, opt_state) [sharded]
    par: ParallelSpec
    mesh: Any
    data_spec: Any               # PartitionSpec for token batches
    param_sharding: Any          # pytree of NamedSharding


def opt_state_partition_specs(opt_state_shape, param_shapes, pspec_tree):
    """PartitionSpecs for an optax state: any subtree structurally identical
    to the params (adam mu/nu, momentum buffers, …) inherits the param
    specs; everything else (counters, scalars) is replicated."""
    pdef = jax.tree_util.tree_structure(param_shapes)

    def is_param_tree(x):
        try:
            return jax.tree_util.tree_structure(x) == pdef
        except Exception:  # noqa: BLE001 - non-pytree nodes
            return False

    return jax.tree_util.tree_map(
        lambda sub: pspec_tree if is_param_tree(sub) else P(),
        opt_state_shape, is_leaf=is_param_tree)


def _axis_or_none(pmesh: ParallelMesh, name: str) -> Optional[str]:
    return name if pmesh.config.axis_sizes()[name] > 1 else None


def make_llama_parallel_spec(pmesh: ParallelMesh, attn: str = "ring",
                             use_ep: bool = False) -> ParallelSpec:
    # Experts shard over pmesh.ep_axis: the dedicated "ep" axis when
    # MeshConfig.ep is set, else aliased onto dp (mesh.py).  Either way the
    # batch is sharded over that axis too (see data_spec below), so the MoE
    # all_to_all routes distinct tokens between expert shards.
    ep = pmesh.ep_axis if use_ep else None
    if ep is not None and pmesh.axis_size(ep) <= 1:
        ep = None
    return ParallelSpec(
        dp_axis=_axis_or_none(pmesh, "dp"),
        tp_axis=_axis_or_none(pmesh, "tp"),
        sp_axis=_axis_or_none(pmesh, "sp"),
        pp_axis=_axis_or_none(pmesh, "pp"),
        ep_axis=ep,
        attn=attn)


def make_llama_train_step(cfg: LlamaConfig, pmesh: ParallelMesh,
                          optimizer: Optional[optax.GradientTransformation]
                          = None,
                          attn: str = "ring",
                          n_microbatches: int = 0,
                          zero1: bool = False,
                          grad_accum: int = 0,
                          overlap: bool = False,
                          objective: Optional[Callable] = None) -> TrainStep:
    """Build the full data/tensor/sequence/pipeline/expert-parallel step.

    ``objective(params, batch, cfg, par) -> (loss, stats)`` replaces
    next-token cross-entropy: ``batch`` is a tuple of arrays whose first
    axis is the batch (each sharded as the tokens are), ``stats`` a small
    float32 array the step returns summed over the data shards (routing
    statistics of dropless experts, ``llama.loss_fn(with_stats=True)``).
    The step is then ``step_fn(params, opt_state, batch) -> (params,
    opt_state, loss, stats)``; plain data parallelism only.

    ``zero1=True`` additionally shards the optimizer state over the dp
    axis (ZeRO stage 1): each dp shard keeps 1/dp of every moment buffer,
    updates its slice, and the updated parameter slices are all-gathered
    — per-chip optimizer HBM drops by the dp factor.  The reference has
    no analog (its DP state is fully replicated); on TPU the all-gather
    rides ICI and overlaps with the next step's compute.

    ``grad_accum=k`` accumulates gradients over k local microbatches
    inside the compiled step (a ``lax.scan`` of fwd+bwd, one optimizer
    update) — the jit-path form of the reference's
    ``backward_passes_per_step`` (horovod/torch/optimizer.py), trading
    activation memory for k× the per-step batch.

    ``overlap=True`` (dp-only meshes; the real-chip A/B lever behind
    ``examples/llama_benchmark.py --overlap``) routes the gradient
    reduction through ``DistributedGradientTransform(overlap=True)``:
    the model's grad taps dispatch each layer's fusion buckets inside
    the backward scan (reverse layer order), hiding DCN latency behind
    the remaining backprop compute, instead of relying on one fused
    post-backprop block.  The step's shard_map runs with
    ``check_vma=False`` so the explicit per-bucket collectives are the
    ONLY dp reduction (no transpose-inserted psums to double-count);
    tp/sp/pp meshes need those transposes and are not composed yet.
    """
    par = make_llama_parallel_spec(pmesh, attn, use_ep=cfg.n_experts > 0)
    mesh = pmesh.mesh
    opt = optimizer if optimizer is not None else optax.adamw(3e-4)
    tp = pmesh.config.tp
    pp = pmesh.config.pp
    dp = pmesh.config.dp
    sp = pmesh.config.sp
    # a dedicated ep axis multiplies the data-parallel degree (experts shard
    # over it; everything else treats it as extra dp)
    ep_dedicated = pmesh.config.ep or 1
    if cfg.n_experts > 0 and par.ep_axis is not None:
        ep_size = pmesh.axis_size(par.ep_axis)
        if cfg.n_experts % ep_size:
            raise ValueError(
                f"n_experts={cfg.n_experts} must divide over "
                f"{par.ep_axis}={ep_size}")
    if tp > 1 and (cfg.n_heads % tp or cfg.n_kv_heads % tp
                   or cfg.d_ff % tp):
        raise ValueError(
            f"n_heads={cfg.n_heads}, n_kv_heads={cfg.n_kv_heads} and "
            f"d_ff={cfg.d_ff} must all be divisible by tp={tp}")
    if pp > 1 and cfg.n_layers % pp:
        raise ValueError(
            f"n_layers={cfg.n_layers} must be divisible by pp={pp}")

    specs = llama_mod.param_specs(par, cfg)
    param_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    # data: batch over dp (and the dedicated ep axis, which acts as extra
    # data parallelism for non-expert compute), sequence over sp
    if ep_dedicated > 1 and par.ep_axis == "ep":
        batch_axes = tuple(a for a in (par.dp_axis, "ep") if a is not None)
        data_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
                      par.sp_axis)
    else:
        data_spec = P(par.dp_axis, par.sp_axis)

    def reduce_grads(grads):
        # The step's shard_map runs with check_vma=True, so JAX's transpose
        # rules already insert the correct cross-shard psums: for every
        # mesh axis a parameter is replicated over, its gradient arrives as
        # Σ_shards ∂L_shard/∂θ (this is also what makes tp/pp gradients
        # correct — with the check off they come out ×tp·pp, a bug this
        # framework hit; see tests/test_llama.py SGD equivalence).  The
        # auto-inserted psums are small per-parameter all-reduces that
        # XLA's all-reduce combiner batches into fused transfers — the
        # reference's fusion buffer realized as a compiler pass.
        #
        # dp, sp — and a dedicated ep axis, which carries extra batch
        # shards — are loss-averaging axes (each shard's local_loss is the
        # mean over its own tokens), so the summed gradient only needs a
        # uniform 1/(dp·sp·ep): the same rule covers dense (replicated) and
        # MoE expert (ep-sharded, backward-all_to_all-summed) parameters.
        scale = 1.0 / (dp * sp * ep_dedicated)
        if scale == 1.0:
            return grads
        return jax.tree_util.tree_map(
            lambda g: g * jnp.asarray(scale, g.dtype), grads)

    def local_loss(params, tokens, targets):
        loss = llama_mod.loss_fn(params, tokens, targets, cfg, par,
                                 n_microbatches)
        if par.pp_axis is not None:
            # only the last stage's loss is real; broadcast it over pp so
            # every shard (and the grads of shared leaves) agree
            is_last = lax.axis_index(par.pp_axis) == pp - 1
            loss = lax.psum(jnp.where(is_last, loss, 0.0), par.pp_axis)
        return loss

    pspec_tree = specs
    param_shapes = jax.eval_shape(
        partial(llama_mod.init_params, cfg, tp=1), jax.random.PRNGKey(0))

    # --- ZeRO-1: which leaves can shard their optimizer state over dp?
    # A leaf qualifies when its (pp/tp-local) leading axis divides by dp.
    # Non-elementwise gradient transforms (global-norm clipping, adafactor
    # row/col stats) would see slices, so zero1 requires an elementwise
    # optimizer — the adam/sgd families all are.
    use_zero = bool(zero1) and dp > 1 and par.dp_axis is not None

    def _spec_axes(entry):
        return (entry if isinstance(entry, tuple)
                else (() if entry is None else (entry,)))

    def _zero_entry(spec, shape):
        entries = list(spec) + [None] * (len(shape.shape) - len(spec))
        # a leaf already sharded over dp on ANY axis (e.g. MoE expert
        # weights with ep aliased onto dp) must not gain a second dp entry
        if any("dp" in _spec_axes(e) for e in entries) or not shape.shape:
            return None
        axes0 = _spec_axes(entries[0] if entries else None)
        denom = 1
        for a in axes0:
            denom *= pmesh.axis_size(a)
        local0 = shape.shape[0] // denom
        if local0 % dp:
            return None
        entries[0] = tuple(axes0) + ("dp",) if axes0 else "dp"
        return P(*entries)

    if use_zero:
        zspec_or_none = jax.tree_util.tree_map(
            _zero_entry, specs, param_shapes,
            is_leaf=lambda x: isinstance(x, P))
        zero_pspecs = jax.tree_util.tree_map(
            lambda z, s: s if z is None else z, zspec_or_none, specs,
            is_leaf=lambda x: x is None or isinstance(x, P))
    else:
        zero_pspecs = pspec_tree

    def _mean_loss(loss):
        loss_axes = [par.dp_axis, par.sp_axis, par.tp_axis]
        if ep_dedicated > 1:
            loss_axes.append("ep")
        for ax in loss_axes:
            if ax is not None:
                loss = lax.pmean(loss, ax)
        return loss

    def loss_and_grads(params, tokens, targets):
        if grad_accum <= 1:
            return jax.value_and_grad(local_loss)(params, tokens, targets)
        k = grad_accum
        B = tokens.shape[0]
        if B % k:
            raise ValueError(
                f"local batch {B} not divisible by grad_accum={k}")
        tok_mb = tokens.reshape(k, B // k, *tokens.shape[1:])
        tgt_mb = targets.reshape(k, B // k, *targets.shape[1:])

        def body(carry, xt):
            loss_acc, g_acc = carry
            l, g = jax.value_and_grad(local_loss)(params, xt[0], xt[1])
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            return (loss_acc + l, g_acc), None

        # accumulators derive from traced values so they carry the right
        # varying mesh axes under check_vma
        loss0 = (tokens.astype(jnp.float32) * 0).sum()
        g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, grads), _ = lax.scan(body, (loss0, g0), (tok_mb, tgt_mb))
        inv_k = 1.0 / k
        return loss * inv_k, jax.tree_util.tree_map(
            lambda g: g * jnp.asarray(inv_k, g.dtype), grads)

    if overlap:
        if (tp > 1 or sp > 1 or pp > 1 or ep_dedicated > 1 or zero1
                or grad_accum > 1 or par.dp_axis is None
                or cfg.n_experts > 0):
            raise ValueError(
                "overlap=True currently composes with dp-only DENSE "
                "meshes (the grad taps psum every leaf over dp, but "
                "MoE aliases ep onto dp so expert weights are "
                "dp-SHARDED — averaging them across ranks holding "
                "different experts would corrupt training; tp/sp/pp "
                "need the transpose-inserted psums of the check_vma "
                "path) — drop --tp/--sp/--pp/--zero1/--grad-accum/"
                "--moe")
        from .optim import overlap as _ovl
        from .optim.distributed import DistributedGradientTransform
        from .runtime import ReduceOp
        ov_tx = DistributedGradientTransform(
            inner=opt, axis_name=par.dp_axis, op=ReduceOp.AVERAGE,
            overlap=True)

        def ov_shard_step(params, opt_state, tokens, targets):
            with _ovl.overlapped_backprop(ov_tx):
                loss, grads = jax.value_and_grad(local_loss)(
                    params, tokens, targets)
            updates, opt_state = ov_tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, _mean_loss(loss)

        ov_state_shape = jax.eval_shape(lambda p: ov_tx.init(p),
                                        param_shapes)
        ov_specs = opt_state_partition_specs(
            ov_state_shape, param_shapes, pspec_tree)
        ov_sharding = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), ov_specs,
            is_leaf=lambda x: isinstance(x, P))
        step_fn = jax.jit(jax.shard_map(
            ov_shard_step, mesh=mesh,
            in_specs=(pspec_tree, ov_specs, data_spec, data_spec),
            out_specs=(pspec_tree, ov_specs, P()),
            check_vma=False), donate_argnums=(0, 1))

        def ov_init_fn(rng):
            params = jax.jit(
                partial(llama_mod.init_params, cfg, tp=1),
                out_shardings=param_sharding)(rng)
            opt_state = jax.jit(
                ov_tx.init, out_shardings=ov_sharding)(params)
            return params, opt_state

        return TrainStep(step_fn=TracedStep(step_fn, 2), init_fn=ov_init_fn,
                         par=par, mesh=mesh, data_spec=data_spec,
                         param_sharding=param_sharding)

    if objective is not None:
        if (tp > 1 or sp > 1 or pp > 1 or ep_dedicated > 1 or zero1
                or grad_accum > 1 or overlap):
            raise ValueError(
                "objective= composes with plain data parallelism only")

        def objective_step(params, opt_state, batch):
            with jax.named_scope(SCOPE_FORWARD):
                (loss, stats), grads = jax.value_and_grad(
                    lambda p: objective(p, batch, cfg, par),
                    has_aux=True)(params)
            with jax.named_scope(SCOPE_REDUCE):
                grads = reduce_grads(grads)
                if par.dp_axis is not None:
                    stats = lax.psum(stats, par.dp_axis)
            with jax.named_scope(SCOPE_OPTIMIZER):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, _mean_loss(loss), stats

    def shard_step(params, opt_state, tokens, targets):
        loss, grads = loss_and_grads(params, tokens, targets)
        grads = reduce_grads(grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, _mean_loss(loss)

    def shard_grads(params, tokens, targets):
        loss, grads = loss_and_grads(params, tokens, targets)
        return _mean_loss(loss), reduce_grads(grads)

    opt_state_shape = jax.eval_shape(lambda p: opt.init(p), param_shapes)
    opt_specs = opt_state_partition_specs(
        opt_state_shape, param_shapes, zero_pspecs)
    opt_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P))

    # donate params/opt_state: the updated pytrees reuse the same HBM,
    # halving peak memory and avoiding a full copy per step
    if use_zero:
        # ZeRO at the GSPMD level: the fwd/bwd shard_map emits (psum'd,
        # dp-invariant) grads; the elementwise optimizer update runs at
        # jit level where the dp-sharded opt-state shardings make XLA
        # partition it over dp (each shard updates 1/dp of every buffer)
        # and the replicated-params output constraint inserts the one
        # all-gather of updated slices — the ZeRO-1 dance as sharding
        # propagation instead of hand-written collectives.
        grads_fn = jax.shard_map(
            shard_grads, mesh=mesh,
            in_specs=(pspec_tree, data_spec, data_spec),
            out_specs=(P(), pspec_tree), check_vma=True)

        def _step(params, opt_state, tokens, targets):
            loss, grads = grads_fn(params, tokens, targets)
            opt_state = lax.with_sharding_constraint(opt_state,
                                                     opt_sharding)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = lax.with_sharding_constraint(params, param_sharding)
            return params, opt_state, loss

        step_fn = jax.jit(_step, donate_argnums=(0, 1))
    elif objective is not None:
        step_fn = jax.jit(jax.shard_map(
            objective_step, mesh=mesh,
            in_specs=(pspec_tree, opt_specs, P(par.dp_axis)),
            out_specs=(pspec_tree, opt_specs, P(), P()),
            check_vma=True), donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(jax.shard_map(
            shard_step, mesh=mesh,
            in_specs=(pspec_tree, opt_specs, data_spec, data_spec),
            out_specs=(pspec_tree, opt_specs, P()),
            check_vma=True), donate_argnums=(0, 1))

    def init_fn(rng):
        params = jax.jit(
            partial(llama_mod.init_params, cfg, tp=1),
            out_shardings=param_sharding)(rng)
        opt_state = jax.jit(
            opt.init, out_shardings=opt_sharding)(params)
        return params, opt_state

    return TrainStep(step_fn=TracedStep(step_fn, 2), init_fn=init_fn,
                     par=par, mesh=mesh, data_spec=data_spec,
                     param_sharding=param_sharding)


def fsdp_param_specs(param_shapes, dp: int, axis: str = "dp"):
    """FSDP shardings: each leaf shards its largest dp-divisible axis.

    Stacked layer leaves (under the ``"layers"`` subtree) never shard
    axis 0 — it is the ``lax.scan`` dimension, and sharding it would put
    whole layers on single devices instead of splitting every layer
    across all of them.  Non-stacked leaves (embed, final_norm) may
    shard any axis.  Leaves with no divisible axis stay replicated
    (the small norms; their optimizer state is negligible)."""
    def spec_for(path, shape):
        dims = shape.shape
        stacked = any(
            getattr(k, "key", getattr(k, "name", None)) == "layers"
            for k in path)
        start = 1 if (stacked and len(dims) > 1) else 0
        best, best_i = 0, None
        for i in range(start, len(dims)):
            if dims[i] % dp == 0 and dims[i] > best:
                best, best_i = dims[i], i
        if best_i is None:
            return P()
        entries = [None] * len(dims)
        entries[best_i] = axis
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec_for, param_shapes)


def spec_all_gather(tree, specs, axis: str):
    """Materialize the full value of every leaf sharded over ``axis``
    (per-leaf tiled ``all_gather`` along the sharded dimension; leaves
    whose spec does not name ``axis`` pass through).  The shard_map-side
    inverse of ``fsdp_param_specs``-style storage sharding."""
    def gather_leaf(spec, leaf):
        for dim, entry in enumerate(spec):
            axes = entry if isinstance(entry, tuple) else (entry,)
            if axis in axes:
                return lax.all_gather(leaf, axis, axis=dim, tiled=True)
        return leaf
    return jax.tree_util.tree_map(
        gather_leaf, specs, tree, is_leaf=lambda x: isinstance(x, P))


def spec_shard(tree, specs, axis: str):
    """This shard's slice of every leaf sharded over ``axis`` — the
    inverse of :func:`spec_all_gather` (full values in, local shards
    out, sliced by ``lax.axis_index(axis)`` along the spec'd dim)."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)

    def shard_leaf(spec, leaf):
        for dim, entry in enumerate(spec):
            axes = entry if isinstance(entry, tuple) else (entry,)
            if axis in axes:
                size = leaf.shape[dim] // n
                return lax.dynamic_slice_in_dim(leaf, idx * size, size,
                                                axis=dim)
        return leaf
    return jax.tree_util.tree_map(
        shard_leaf, specs, tree, is_leaf=lambda x: isinstance(x, P))


def make_llama_fsdp_step(cfg: LlamaConfig, pmesh: ParallelMesh,
                         optimizer: Optional[optax.GradientTransformation]
                         = None, overlap: bool = False) -> TrainStep:
    """Fully-sharded data parallelism (ZeRO-3 class): params, grads AND
    optimizer state all live dp-sharded; each layer's weights are
    all-gathered just-in-time inside the scanned layer loop and the
    gradients reduce-scatter back — per-chip param+optimizer memory is
    1/dp of the model instead of a full replica.

    TPU-native form: no hand-written collectives at all.  The step is a
    plain ``jit`` whose sharding constraints (params sharded over dp on a
    weight axis, batch sharded over dp) make XLA's SPMD partitioner insert
    the per-layer all-gather/reduce-scatter pairs; because the layer
    weights enter ``lax.scan`` as per-iteration slices, the gathers stay
    inside the loop and only one layer is ever resident unsharded.  The
    reference's DP (SURVEY.md §2.9) always replicates the full model; this
    is the capability class FSDP/ZeRO-3 adds beyond it.

    ``overlap=True`` composes FSDP storage with the overlapped gradient
    plane (ISSUE 14): the step becomes an explicit ``shard_map``
    program — params enter as their dp shards, one gather block
    materializes the working copy, the model's grad taps reduce-scatter
    each layer's fusion buckets INSIDE the backward scan
    (``DistributedGradientTransform(overlap=True, sharded_update=
    True)``: flat 1/dp optimizer-state tiles, updates all-gathered at
    the boundary), and the updated shards are sliced back to storage.
    Persistent per-chip bytes stay at the 1/dp fraction; the tradeoff
    vs the GSPMD path is one whole-model gather per step instead of
    just-in-time per-layer gathers (documented in docs/performance.md).

    Capability gates (each refusal names exactly what is unsupported):
    MoE stays refused — expert parallelism aliases onto dp, so expert
    weights are dp-sharded and dp-averaging taps would corrupt them —
    and tp/pp/sp/ep meshes shard the model on axes this step does not
    gather over (use ``make_llama_train_step``).
    """
    if cfg.n_experts > 0:
        raise ValueError(
            "make_llama_fsdp_step does not support MoE: expert "
            "parallelism aliases the ep axis onto dp, so expert "
            "weights are dp-SHARDED by routing — FSDP's dp-gathered "
            "working copy (and any dp-averaging gradient plane) would "
            "mix weights of DIFFERENT experts across ranks; use "
            "make_llama_train_step for MoE")
    for ax in ("tp", "pp", "sp"):
        if getattr(pmesh.config, ax) > 1:
            raise ValueError(
                f"make_llama_fsdp_step does not compose with {ax}>1: "
                f"the model is sharded over the {ax!r} axis, but this "
                f"step only gathers/scatters over dp — use "
                f"make_llama_train_step (optionally with zero1) for "
                f"{ax} meshes")
    if (pmesh.config.ep or 1) > 1:
        raise ValueError(
            "make_llama_fsdp_step does not compose with a dedicated "
            "ep axis: expert routing shards weights over ep, which "
            "this step does not gather over — use "
            "make_llama_train_step for MoE/ep meshes")
    mesh = pmesh.mesh
    dp = pmesh.config.dp
    opt = optimizer if optimizer is not None else optax.adamw(3e-4)
    if overlap:
        return _make_llama_fsdp_overlap_step(cfg, pmesh, opt)
    par = ParallelSpec()  # no named-axis collectives — GSPMD does it all
    param_shapes = jax.eval_shape(
        partial(llama_mod.init_params, cfg, tp=1), jax.random.PRNGKey(0))
    pspec_tree = fsdp_param_specs(param_shapes, dp)
    param_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))
    opt_state_shape = jax.eval_shape(lambda p: opt.init(p), param_shapes)
    opt_specs = opt_state_partition_specs(
        opt_state_shape, param_shapes, pspec_tree)
    opt_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P))
    data_spec = P("dp")

    def loss_fn(params, tokens, targets):
        return llama_mod.loss_fn(params, tokens, targets, cfg, par)

    def _step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        # pin grads to the param sharding: XLA turns the gradient
        # all-reduce into reduce-scatter + sharded update (ZeRO's trick)
        grads = lax.with_sharding_constraint(grads, param_sharding)
        opt_state = lax.with_sharding_constraint(opt_state, opt_sharding)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        params = lax.with_sharding_constraint(params, param_sharding)
        return params, opt_state, loss

    step_fn = jax.jit(_step, donate_argnums=(0, 1))

    def init_fn(rng):
        params = jax.jit(
            partial(llama_mod.init_params, cfg, tp=1),
            out_shardings=param_sharding)(rng)
        opt_state = jax.jit(opt.init, out_shardings=opt_sharding)(params)
        return params, opt_state

    return TrainStep(step_fn=TracedStep(step_fn, 2), init_fn=init_fn,
                     par=par, mesh=mesh, data_spec=data_spec,
                     param_sharding=param_sharding)


def _make_llama_fsdp_overlap_step(cfg: LlamaConfig, pmesh: ParallelMesh,
                                  opt) -> TrainStep:
    """FSDP storage + overlapped gradient dispatch (see
    ``make_llama_fsdp_step(overlap=True)``).  An explicit shard_map
    program: gather sharded params → tap-armed backward (per-layer
    reduce-scatters inside the scan) → 1/dp-tile optimizer step →
    boundary all-gather of updates → slice shards back to storage."""
    from .optim import overlap as _ovl
    from .optim.distributed import (DistributedGradientTransform,
                                    state_partition_specs)
    from .runtime import ReduceOp
    mesh = pmesh.mesh
    dp = pmesh.config.dp
    par = ParallelSpec(dp_axis="dp")
    param_shapes = jax.eval_shape(
        partial(llama_mod.init_params, cfg, tp=1), jax.random.PRNGKey(0))
    pspec_tree = fsdp_param_specs(param_shapes, dp)
    param_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))
    data_spec = P("dp")
    # flat 1/dp optimizer-state tiles + in-backward-scan dispatch; the
    # taps psum_scatter each layer bucket, the transform carves tiles
    ov_tx = DistributedGradientTransform(
        inner=opt, axis_name="dp", op=ReduceOp.AVERAGE, overlap=True,
        sharded_update=True)

    def local_loss(params, tokens, targets):
        # par carries dp_axis for loss semantics only; the gradient
        # collectives are the taps' (check_vma=False below)
        return llama_mod.loss_fn(params, tokens, targets, cfg,
                                 ParallelSpec())

    def ov_shard_step(params_local, opt_state, tokens, targets):
        full = spec_all_gather(params_local, pspec_tree, "dp")
        with _ovl.overlapped_backprop(ov_tx):
            loss, grads = jax.value_and_grad(local_loss)(full, tokens,
                                                         targets)
        updates, opt_state = ov_tx.update(grads, opt_state, full)
        new_full = optax.apply_updates(full, updates)
        params_local = spec_shard(new_full, pspec_tree, "dp")
        return params_local, opt_state, lax.pmean(loss, "dp")

    # the sharded-update state structure references the mapped axis at
    # init, so derive it under an abstract axis env and shard_map the
    # real init (state tiles are per-worker: varying over dp)
    _, state_shape = jax.make_jaxpr(
        lambda p: ov_tx.init(p), axis_env=[("dp", dp)],
        return_shape=True)(param_shapes)
    state_specs = state_partition_specs(state_shape, "dp",
                                        sharded_update=True)
    state_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))
    step_fn = jax.jit(jax.shard_map(
        ov_shard_step, mesh=mesh,
        in_specs=(pspec_tree, state_specs, data_spec, data_spec),
        out_specs=(pspec_tree, state_specs, P()),
        check_vma=False), donate_argnums=(0, 1))

    def init_fn(rng):
        params = jax.jit(
            partial(llama_mod.init_params, cfg, tp=1),
            out_shardings=param_sharding)(rng)

        def _init(params_local):
            return ov_tx.init(
                spec_all_gather(params_local, pspec_tree, "dp"))

        opt_state = jax.jit(jax.shard_map(
            _init, mesh=mesh, in_specs=(pspec_tree,),
            out_specs=state_specs, check_vma=False),
            out_shardings=state_sharding)(params)
        return params, opt_state

    return TrainStep(step_fn=TracedStep(step_fn, 2), init_fn=init_fn,
                     par=par, mesh=mesh, data_spec=data_spec,
                     param_sharding=param_sharding)


def make_data_sharding(ts: TrainStep):
    return NamedSharding(ts.mesh, ts.data_spec)


@dataclasses.dataclass
class ClassifierTrainStep:
    """Compiled DP image-classifier step (benchmark configs 1/2/5)."""
    step_fn: Callable    # (params, state, opt_state, images, labels) ->
    #                      (params, state, opt_state, loss, accuracy)
    init_fn: Callable    # (rng) -> (params, state, opt_state)
    eval_fn: Callable    # (params, state, images) -> logits [batch-sharded]
    mesh: Any
    data_spec: Any


def make_classifier_train_step(forward_fn, model_init_fn, pmesh: ParallelMesh,
                               optimizer: Optional[
                                   optax.GradientTransformation] = None,
                               sync_bn: bool = True) -> ClassifierTrainStep:
    """Data-parallel training step for image classifiers (ResNet/MNIST).

    ``forward_fn(params, state, images, train, axis_name)`` must return
    ``(logits, new_state)`` — stateless models pass state through
    untouched.  ``model_init_fn(rng) -> (params, state)``.

    The reference's equivalent is DistributedOptimizer around a torch
    module with opt-in SyncBatchNorm (SURVEY.md §2.2); here the gradient
    all-reduce AND the batch-stat sync compile into the one step program,
    so XLA overlaps both with compute.
    """
    mesh = pmesh.mesh
    opt = optimizer if optimizer is not None else optax.sgd(0.1, momentum=0.9)
    dp = pmesh.config.dp
    dp_axis = "dp" if dp > 1 else None
    bn_axis = dp_axis if sync_bn else None
    data_spec = P(dp_axis)

    def local_loss(params, state, images, labels):
        with jax.named_scope(SCOPE_FORWARD):
            logits, new_state = forward_fn(params, state, images,
                                           train=True, axis_name=bn_axis)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            acc = (logits.argmax(-1) == labels).mean()
        return loss, (new_state, acc)

    def shard_step(params, state, opt_state, images, labels):
        (loss, (state, acc)), grads = jax.value_and_grad(
            local_loss, has_aux=True)(params, state, images, labels)
        if dp > 1:
            with jax.named_scope(SCOPE_REDUCE):
                # check_vma inserted the cross-shard psum; normalize the
                # summed gradient of the per-shard mean losses
                grads = jax.tree_util.tree_map(
                    lambda g: g * jnp.asarray(1.0 / dp, g.dtype), grads)
                loss = lax.pmean(loss, "dp")
                acc = lax.pmean(acc, "dp")
                if not sync_bn:
                    # unsynced batch stats diverge per shard; average so
                    # the replicated state stays identical everywhere
                    state = jax.tree_util.tree_map(
                        lambda s: lax.pmean(s, "dp"), state)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, state, opt_state, loss, acc

    step_fn = jax.jit(jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P(), P(), data_spec, data_spec),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=True), donate_argnums=(0, 1, 2))

    def shard_eval(params, state, images):
        logits, _ = forward_fn(params, state, images, train=False,
                               axis_name=None)
        return logits

    eval_fn = jax.jit(jax.shard_map(
        shard_eval, mesh=mesh, in_specs=(P(), P(), data_spec),
        out_specs=data_spec, check_vma=True))

    replicated = NamedSharding(mesh, P())

    def init_fn(rng):
        params, state = jax.jit(model_init_fn,
                                out_shardings=replicated)(rng)
        opt_state = jax.jit(opt.init, out_shardings=replicated)(params)
        return params, state, opt_state

    return ClassifierTrainStep(step_fn=TracedStep(step_fn, 3),
                               init_fn=init_fn, eval_fn=eval_fn, mesh=mesh,
                               data_spec=data_spec)
