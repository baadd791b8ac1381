"""Llama-3-style decoder: the flagship model (BASELINE config 4).

TPU-first design choices:
  * bf16 activations / fp32 master params — MXU-native matmuls, fp32 RMSNorm
    and softmax accumulation.
  * Layers stacked on a leading dim and driven by ``lax.scan`` — one
    compiled block body regardless of depth (fast compile, XLA-friendly).
  * Parallelism as mesh-axis hooks (``ParallelSpec``): megatron-style
    column/row tensor parallel (one psum per attention + one per MLP),
    ring-attention or Ulysses sequence parallel for long context, optional
    GPipe pipeline over the layer stack, data parallel gradient psum.
  * GQA (grouped-query attention) with RoPE, SwiGLU MLP — the Llama-3
    architecture family.

The reference has no model zoo of its own (its examples wrap torchvision/
transformers models); this module provides the equivalent capability
surface natively, and is the model the benchmarks drive.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import metrics as _metrics
from ..optim import overlap as _overlap
from ..parallel.ring_attention import ring_attention
from ..parallel.ulysses import ulysses_attention
from ..scopes import (SCOPE_ATTENTION, SCOPE_EMBED, SCOPE_HEAD, SCOPE_MLP,
                      SCOPE_ROPE)

_m_remat = _metrics.counter(
    "hvd_remat_policy_total",
    "Remat'd layer stacks built by policy, one per traced stack; saves "
    "says which named residuals the policy keeps beside the layer's "
    "input (flash: the attention kernels' output and row statistics)",
    labels=("policy", "saves"))


@dataclasses.dataclass(frozen=True)
class RopeTable:
    """One layer kind's rotary table: Hugging Face's ``rope_parameters`` of
    a layer type.  ``rope_type`` "default" is plain RoPE at ``theta``;
    "yarn" (arXiv 2309.00071, ``transformers``' ``_compute_yarn_parameters``)
    stretches it by ``factor`` from ``original_max_position_embeddings``
    positions (:func:`rope_inv_freq`, ``truncate`` at its default);
    ``attention_factor`` multiplies cos and sin both (0 = YaRN's ``0.1
    ln(factor) + 1``)."""
    theta: float = 500000.0
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError("rope_type must be 'default' or 'yarn', got "
                             f"{self.rope_type!r}")
        if self.rope_type == "yarn" and (
                self.factor < 1.0 or self.original_max_position_embeddings <= 0):
            raise ValueError(
                "a yarn table needs factor >= 1 and the "
                "original_max_position_embeddings it stretches from")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16     # activation / compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # remat granularity: "full" recomputes every op XLA makes (max memory
    # savings), "dots" saves matmul outputs without batch dims (cheap
    # recompute of elementwise/norm only — the right default when memory
    # allows).  Under either a hand-written attention kernel is never
    # rerun: its output and row statistics (ops/flash_attention.py's
    # OUT_NAME, LSE_NAME) are a layer's saved residuals beside the
    # layer's input, L x B x T x H x head_dim x 2 + L x B x H x T x 4
    # bytes a chip (bf16 out, float32 lse; docs/models.md has the sums)
    remat_policy: str = "dots"
    # Mixture-of-Experts (0 experts = dense SwiGLU MLP)
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    # chunked cross-entropy: > 0 computes the loss in sequence chunks of
    # this many tokens, recomputing each chunk's [B, chunk, V] logits in
    # the backward pass instead of materializing the full [B, T, V] fp32
    # logits + log-softmax (≈ 2 GB at B8·T1024·V32k).  0 = one-shot.
    loss_chunk: int = 0
    # partial remat: the LAST k layers (per pipeline stage) run without
    # rematerialization — their activations are saved, trading HBM for
    # skipped recompute.  Spend freed memory here: each skipped layer
    # saves one forward-recompute of itself in the backward pass.
    remat_skip_layers: int = 0
    # vocab-parallel embedding/head (megatron VocabParallelEmbedding):
    # shards the tied embedding's vocab axis over tp — at Llama-3-8B the
    # 0.53 GB embedding stops being replicated per tp shard.  Lookup
    # masks out-of-shard tokens + psum; the loss reduces lse/target
    # across shards (pmax + psum) so no full-vocab logits exist on any
    # shard.  Ignored when tp is off.
    vocab_parallel: bool = False
    # width of a head; 0 = d_model // n_heads, worked out once when the
    # config is made (so dataclasses.replace with another d_model or
    # n_heads passes head_dim=0 to have it worked out again)
    head_dim: int = 0
    # RMSNorm with a learned weight over each head of q and of k, before
    # RoPE (here, and in the "attention" and "swa" kinds of a trunk of
    # several kinds)
    qk_norm: bool = False
    # False: an output head ``params["head"] [V, D]`` of its own
    tie_embeddings: bool = True
    # experts (models/moe.py): "capacity" = static capacity, overflow
    # tokens drop; "dropless" = no capacity, on the experts this chip
    # holds: ``experts_held`` of the ``n_experts`` the router scores
    # (0 = all), the first of them ``experts_first``
    moe_dispatch: str = "capacity"
    experts_held: int = 0
    experts_first: int = 0
    # how dropless experts' router scores its outputs (``moe.route``):
    # "softmax" over all of them, or "sigmoid" of each (then a layer also
    # carries a selection bias, ``router_bias``, that moves the choice
    # alone); and how many shared experts of width ``d_ff`` every token
    # passes through beside the routed ones (as one of that many times the
    # width; the trunk of several kinds alone)
    router_score: str = "softmax"
    n_shared_experts: int = 0
    # DeepSeek-V3's ``routed_scaling_factor``: the chosen experts' weights,
    # renormalised, times this (dropless experts alone; 1.0 = none).  And
    # its ``first_k_dense_replace``: that many leading layers of a trunk of
    # several kinds keep a dense feed-forward, ``dense_d_ff`` wide (0 =
    # ``d_ff``), before the routed ones (0 = every layer routed).
    # ``router_eps`` is added to the chosen scores' sum before they are
    # divided by it (dropless experts alone; 0.0 = nothing is added)
    routed_scaling_factor: float = 1.0
    router_eps: float = 0.0
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    # a trunk whose layers are of several kinds (models/hybrid.py): each
    # layer's kind, "mamba" | "window" | "full" | "gmu" | "cross" |
    # "mamba2" | "attention" | "kda" | "swa" | "mla" | "conv" (() = the
    # trunk of identical layers here),
    # and its index in the published model (() = its place in the trunk);
    # the window of the "window" kind's attention; the state-space
    # mixers' inner width, states (a channel for "mamba", a head's
    # columns for "mamba2"), convolution width, and "mamba"'s step rank;
    # "mamba2"'s heads (of ssm_inner / ssm_heads channels), the groups
    # that share B and C, and the positions a chunk of its scan takes.
    # "kda" (Kimi Delta Attention) has ssm_heads heads whose keys are
    # ssm_state and whose values ssm_inner / ssm_heads wide, a convolution
    # ssm_conv wide and chunks of ssm_chunk positions; ``attn_gate`` puts a
    # sigmoid gate of the layer's input on the "attention" kind's output,
    # before ``wo``.  "swa" is the "attention" kind's plain grouped-query
    # attention under the window.  Such a trunk has a fused gate/up MLP
    # (or, with ``n_experts``, dropless routed experts); its layers' norm
    # is ``trunk_norm``: "layernorm" (weight and bias) or "rmsnorm"
    # (weight).  Positions are a kind's: ``rope_tables`` pairs "attention",
    # "swa" or "mla" with its :class:`RopeTable`, ``((kind, table), ...)``,
    # and a kind without one (every other kind; () = all) has none.
    # "mla" is DeepSeek-V3's multi-head latent attention without a query
    # latent: keys and values come from a latent ``kv_lora_rank`` wide
    # (normed), a head's scores are a ``qk_nope_head_dim``-wide product with
    # its own keys plus a ``qk_rope_head_dim``-wide one with the one rotary
    # key every head shares, its values ``v_head_dim`` wide.  "conv" is the
    # gated short convolution, ``ssm_conv`` taps a channel of ``d_model``.
    layer_kinds: tuple = ()
    layer_ids: tuple = ()
    sliding_window: int = 0
    ssm_inner: int = 0
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    ssm_heads: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 256
    attn_gate: bool = False
    rope_tables: tuple = ()
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    trunk_norm: str = "layernorm"
    # Granite's four multipliers, of the trunk of several kinds alone
    # (the trunk of identical layers refuses them), each at what a trunk
    # computes without it: the embedding's rows times
    # ``embedding_multiplier``; each sublayer's output times
    # ``residual_multiplier`` before it joins the stream; the softmax's
    # scale (0 = head_dim ** -0.5); the logits divided by
    # ``logits_scaling``
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        if self.moe_dispatch not in ("capacity", "dropless"):
            raise ValueError("moe_dispatch must be 'capacity' or "
                             f"'dropless', got {self.moe_dispatch!r}")
        if self.trunk_norm not in ("layernorm", "rmsnorm"):
            raise ValueError("trunk_norm must be 'layernorm' or 'rmsnorm', "
                             f"got {self.trunk_norm!r}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError("router_score must be 'softmax' or 'sigmoid', "
                             f"got {self.router_score!r}")
        if not self.layer_kinds and (self.n_shared_experts or self.attn_gate
                                     or self.rope_tables
                                     or self.first_dense_layers):
            raise ValueError(
                "n_shared_experts, attn_gate, rope_tables and "
                "first_dense_layers are wired through the trunk of several "
                "kinds (layer_kinds) alone")
        if ((self.routed_scaling_factor != 1.0 or self.router_eps)
                and self.moe_dispatch != "dropless"):
            raise ValueError("routed_scaling_factor and router_eps are the "
                             "dropless experts' (moe_dispatch='dropless')")
        multipliers = (self.embedding_multiplier, self.residual_multiplier,
                       self.attention_multiplier, self.logits_scaling)
        if not self.layer_kinds and multipliers != (1.0, 1.0, 0.0, 1.0):
            raise ValueError(
                "embedding_multiplier, residual_multiplier, "
                "attention_multiplier and logits_scaling are wired through "
                "the trunk of several kinds (layer_kinds) alone")


def llama3_8b() -> LlamaConfig:
    """Llama-3-8B geometry (the BASELINE config-4 target)."""
    return LlamaConfig()


# BASELINE config-4 mesh: dp16 x tp4 = 64 chips (v5p-128)
LLAMA8B_TP = 4
LLAMA8B_DP = 16


def llama3_8b_train_cfg(seq: int = 4096) -> LlamaConfig:
    """The exact config-4 TRAINING configuration, shared by the bench
    mode (``bench.py`` llama8b_dp) and the AOT rehearsal
    (``tools/rehearse_8b.py``) so 'the rehearsal rehearses the measured
    step' can never drift: vocab-parallel embedding/head, chunk-1024
    cross-entropy, full remat."""
    return dataclasses.replace(
        llama3_8b(), vocab_parallel=True, loss_chunk=1024, remat=True,
        remat_policy="full", max_seq_len=seq)


def tiny(vocab: int = 256, seq: int = 128) -> LlamaConfig:
    """Test-scale config: same code paths, toy sizes."""
    return LlamaConfig(vocab_size=vocab, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, d_ff=128, max_seq_len=seq,
                       dtype=jnp.float32)


@dataclasses.dataclass(frozen=True)
class ParallelSpec:
    """Which mesh axes the forward pass should use (None = off)."""
    dp_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    sp_axis: Optional[str] = None
    pp_axis: Optional[str] = None
    ep_axis: Optional[str] = None  # usually aliased to dp (see mesh.py)
    attn: str = "ring"            # "ring" | "ulysses" | "local"


def init_params(cfg: LlamaConfig, key, tp: int = 1) -> Dict:
    """Initialize parameters; with ``tp > 1`` returns the FULL stacked
    params — shard them over the mesh with :func:`param_specs`."""
    if cfg.layer_kinds:
        from . import hybrid
        k = jax.random.split(key, 2)
        params = {
            "embed": jax.random.normal(
                k[0], (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
            * cfg.d_model ** -0.5,
            "layers": hybrid.init_layers(cfg, k[1]),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype)}
        if cfg.trunk_norm == "layernorm":
            params["final_norm_bias"] = jnp.zeros((cfg.d_model,),
                                                  cfg.param_dtype)
        if not cfg.tie_embeddings:
            params["head"] = jax.random.normal(
                jax.random.fold_in(key, 8), (cfg.vocab_size, cfg.d_model),
                cfg.param_dtype) * cfg.d_model ** -0.5
        return params
    k = jax.random.split(key, 8)
    D, H, Hkv, Dh, F, L, V = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim, cfg.d_ff, cfg.n_layers,
                              cfg.vocab_size)
    if H % tp or Hkv % tp or F % tp:
        raise ValueError(
            f"heads({H})/kv_heads({Hkv})/d_ff({F}) must divide tp={tp}")

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, cfg.param_dtype)
                * (fan_in ** -0.5))

    layers = {
        "attn_norm": jnp.ones((L, D), cfg.param_dtype),
        "wq": norm(k[1], (L, D, H * Dh), D),
        "wk": norm(k[2], (L, D, Hkv * Dh), D),
        "wv": norm(k[3], (L, D, Hkv * Dh), D),
        "wo": norm(k[4], (L, H * Dh, D), H * Dh),
        "mlp_norm": jnp.ones((L, D), cfg.param_dtype),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, Dh), cfg.param_dtype)
        layers["k_norm"] = jnp.ones((L, Dh), cfg.param_dtype)
    if cfg.n_experts > 0:
        from .moe import init_moe_layer_params
        layers.update(init_moe_layer_params(
            k[5], L, D, F, cfg.n_experts, cfg.param_dtype,
            n_held=cfg.experts_held))
    else:
        layers.update({
            "w_gate": norm(k[5], (L, D, F), D),
            "w_up": norm(k[6], (L, D, F), D),
            "w_down": norm(k[7], (L, F, D), F),
        })
    params = {
        "embed": norm(k[0], (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = norm(jax.random.fold_in(key, 8), (V, D), D)
    return params


def param_specs(par: ParallelSpec, cfg: Optional[LlamaConfig] = None):
    """PartitionSpecs for the param pytree (megatron layout).

    Column-parallel (wq/wk/wv/w_gate/w_up) shard the output dim over tp;
    row-parallel (wo/w_down) shard the input dim; norms and embeddings are
    replicated; the stacked layer dim shards over pp when pipelining; MoE
    expert weights shard their expert dim over ep.
    """
    from jax.sharding import PartitionSpec as P
    if cfg is not None and cfg.layer_kinds:
        from . import hybrid
        specs = {"embed": P(), "layers": hybrid.layer_specs(cfg),
                 "final_norm": P()}
        if cfg.trunk_norm == "layernorm":
            specs["final_norm_bias"] = P()
        if not cfg.tie_embeddings:
            specs["head"] = P()
        return specs
    tp = par.tp_axis
    pp = par.pp_axis
    embed_spec = (P(tp, None) if cfg is not None and cfg.vocab_parallel
                  and tp is not None else P())
    layers = {
        "attn_norm": P(pp, None),
        "wq": P(pp, None, tp),
        "wk": P(pp, None, tp),
        "wv": P(pp, None, tp),
        "wo": P(pp, tp, None),
        "mlp_norm": P(pp, None),
    }
    if cfg is not None and cfg.qk_norm:
        layers.update({"q_norm": P(pp, None), "k_norm": P(pp, None)})
    if cfg is not None and cfg.n_experts > 0:
        ep = par.ep_axis
        layers.update({
            "router": P(pp, None, None),
            "we_gate": P(pp, ep, None, tp),
            "we_up": P(pp, ep, None, tp),
            "we_down": P(pp, ep, tp, None),
        })
    else:
        layers.update({
            "w_gate": P(pp, None, tp),
            "w_up": P(pp, None, tp),
            "w_down": P(pp, tp, None),
        })
    specs = {
        "embed": embed_spec,
        "layers": layers,
        "final_norm": P(),
    }
    if cfg is not None and not cfg.tie_embeddings:
        specs["head"] = embed_spec
    return specs


def _vp_active(cfg: LlamaConfig, par: ParallelSpec) -> bool:
    return cfg.vocab_parallel and par.tp_axis is not None


def _embed_lookup(embed, tokens, cfg: LlamaConfig, par: ParallelSpec):
    """Token embedding; with vocab_parallel the shard holds rows
    ``[i·V/tp, (i+1)·V/tp)`` — out-of-shard tokens contribute zero and
    one psum over tp assembles the full rows (megatron
    VocabParallelEmbedding forward)."""
    w = embed.astype(cfg.dtype)
    if not _vp_active(cfg, par):
        return _times_embedding_multiplier(w[tokens], cfg)
    Vl = w.shape[0]
    off = lax.axis_index(par.tp_axis) * Vl
    local = tokens - off
    inside = (local >= 0) & (local < Vl)
    rows = w[jnp.clip(local, 0, Vl - 1)]
    rows = rows * inside[..., None].astype(w.dtype)
    return lax.psum(rows, par.tp_axis)


def _times_embedding_multiplier(rows, cfg: LlamaConfig):
    if cfg.embedding_multiplier == 1.0:
        return rows
    return (rows.astype(jnp.float32) * cfg.embedding_multiplier
            ).astype(rows.dtype)


def _vp_chunk_losses(h, w, targets, par: ParallelSpec):
    """Sum of ``lse - target_logit`` over one sequence chunk against a
    tp-sharded vocabulary: local partial logits ``[B, c, V/tp]``,
    cross-shard pmax/psum of the logsumexp and a masked psum of the
    target logit — no shard ever sees a full vocabulary row."""
    Vl = w.shape[0]
    logits_l = (h @ w.T).astype(jnp.float32)          # [B, c, V/tp]
    # the stability max carries no gradient (pmax also has no diff rule)
    m = lax.pmax(lax.stop_gradient(logits_l).max(axis=-1), par.tp_axis)
    sumexp = lax.psum(
        jnp.exp(logits_l - m[..., None]).sum(axis=-1), par.tp_axis)
    lse = m + jnp.log(sumexp)
    off = lax.axis_index(par.tp_axis) * Vl
    local = targets - off
    inside = (local >= 0) & (local < Vl)
    tgt_l = jnp.take_along_axis(
        logits_l, jnp.clip(local, 0, Vl - 1)[..., None], axis=-1)[..., 0]
    tgt = lax.psum(tgt_l * inside.astype(jnp.float32), par.tp_axis)
    return (lse - tgt).sum()


def _vocab_parallel_xent(h, embed, targets, par: ParallelSpec,
                         chunk: int = 0):
    """Mean cross-entropy over a tp-sharded vocabulary; with ``chunk``
    dividing the local sequence, the ``[B, T, V/tp]`` partial logits are
    additionally tiled over sequence chunks with per-chunk backward
    recompute (``loss_chunk`` composed with vocab parallelism)."""
    w = embed.astype(h.dtype)
    B, T, D = h.shape
    if chunk <= 0 or T % chunk:
        return _vp_chunk_losses(h, w, targets, par) / (B * T)
    n = T // chunk
    hs = jnp.moveaxis(h.reshape(B, n, chunk, D), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def body(acc, xt):
        hc, tc = xt
        return acc + _vp_chunk_losses(hc, w, tc, par), None

    acc0 = (h.astype(jnp.float32) * 0).sum()
    total, _ = lax.scan(body, acc0, (hs, ts))
    return total / (B * T)


def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding; x: [B, T, H, D], positions: [B, T] (global)."""
    Dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, Dh // 2, dtype=jnp.float32) / (Dh // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,Dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def rope_inv_freq(table: RopeTable, head_dim: int):
    """``(inverse frequencies [head_dim / 2], the factor on cos and sin)``
    of one kind's table, in numpy (float64, rounded once to float32).
    Plain: ``e[j] = theta ** (-2j / head_dim)``, factor 1.  YaRN: ``n[j] =
    e[j] / factor``; a dimension that turns ``r`` times within the original
    length is ``c(r) = head_dim ln(L / (2 pi r)) / (2 ln theta)``; ``low =
    floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, clipped to ``[0,
    head_dim - 1]``; ``ramp[j] =
    clip((j - low) / (high - low), 0, 1)``; ``inv_freq[j] = n[j] ramp[j] +
    e[j] (1 - ramp[j])``: the fast dimensions keep their frequency, the
    slow ones are interpolated."""
    half = head_dim // 2
    j = np.arange(half, dtype=np.float64)
    e = float(table.theta) ** (-j / half)
    if table.rope_type == "default":
        return e.astype(np.float32), 1.0

    def turns(r):
        return (head_dim * math.log(table.original_max_position_embeddings
                                    / (r * 2 * math.pi))
                / (2 * math.log(table.theta)))

    low = max(math.floor(turns(table.beta_fast)), 0)
    high = min(math.ceil(turns(table.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001       # transformers': no division by zero
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    inv_freq = e / table.factor * ramp + e * (1.0 - ramp)
    return (inv_freq.astype(np.float32),
            table.attention_factor or 0.1 * math.log(table.factor) + 1.0)


def rope_table(table: RopeTable, head_dim: int, T: int):
    """``(cos, sin) [T, head_dim / 2]`` float32 of the positions
    ``arange(T)``, the table's factor on both."""
    inv_freq, factor = rope_inv_freq(table, head_dim)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    return (jnp.cos(angles) * jnp.float32(factor),
            jnp.sin(angles) * jnp.float32(factor))


def rotate(x, cos, sin):
    """:func:`_rope`'s rotation (rotate-half) by a table made beforehand:
    x ``[B, T, H, D]``, cos and sin ``[T, D / 2]``; float32 inside."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _qk_norm_tables(h, layers, cfg: LlamaConfig, positions):
    """``(cos, sin)`` of ``positions`` for ``ops/rope.py``'s pass of q/k
    norm and rotation, made once for every layer of a stack, where the
    trunk norms ``q`` and ``k`` and the kernel takes the products' rows
    (a TPU, heads of 128, ``T`` a multiple of its block); else None, and
    :func:`_attention` keeps the standing form."""
    if not cfg.qk_norm:
        return None
    # imported here, as in ``remat_policy``
    from ..ops import rope as _rotary
    table = jax.ShapeDtypeStruct(
        positions.shape + (cfg.head_dim // 2,), jnp.float32)
    for wn, nn in (("wq", "q_norm"), ("wk", "k_norm")):
        rows = jax.ShapeDtypeStruct(
            h.shape[:2] + layers[wn].shape[-1:], h.dtype)
        # (a leaf of the stack is [layers, ...])
        weight = jax.ShapeDtypeStruct(layers[nn].shape[1:], h.dtype)
        if not _rotary.norm_supported(rows, weight, table, table):
            return None
    with jax.named_scope(SCOPE_ATTENTION), jax.named_scope(SCOPE_ROPE):
        # ``_rope``'s own expressions
        half = cfg.head_dim // 2
        freqs = cfg.rope_theta ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half)
        angles = positions[..., None].astype(jnp.float32) * freqs
        return jnp.cos(angles), jnp.sin(angles)


def _attention(x, lp, cfg: LlamaConfig, par: ParallelSpec, positions,
               mask=None, rope=None):
    """One attention sublayer on tp-local heads and sp-local sequence.
    ``mask``: the key ranges each query row sees (ops/flash_attention.py);
    None = causal.  ``rope``: :func:`_qk_norm_tables`' tables, where q/k
    norm and the rotation are one kernel pass on the products' rows."""
    from ..ops import rope as _rotary
    B, Tl, D = x.shape
    Dh = cfg.head_dim
    # local head counts under tp (weights arrive pre-sharded)
    Hl = lp["wq"].shape[-1] // Dh
    Hkvl = lp["wk"].shape[-1] // Dh
    if rope is not None:
        # the kernel reads the rows the products write and writes the rows
        # the flash kernels read: nothing positions-minor between them
        q, k = x @ lp["wq"].astype(x.dtype), x @ lp["wk"].astype(x.dtype)
        v = (x @ lp["wv"].astype(x.dtype)).reshape(B, Tl, Hkvl, Dh)
        with jax.named_scope(SCOPE_ROPE):
            q = _rotary.norm_rotate(q, lp["q_norm"], *rope, cfg.norm_eps)
            k = _rotary.norm_rotate(k, lp["k_norm"], *rope, cfg.norm_eps)
        q, k = q.reshape(B, Tl, Hl, Dh), k.reshape(B, Tl, Hkvl, Dh)
    else:
        q = (x @ lp["wq"].astype(x.dtype)).reshape(B, Tl, Hl, Dh)
        k = (x @ lp["wk"].astype(x.dtype)).reshape(B, Tl, Hkvl, Dh)
        v = (x @ lp["wv"].astype(x.dtype)).reshape(B, Tl, Hkvl, Dh)
        if cfg.qk_norm:
            q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
            k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
        _rotary.count_xla(normed=cfg.qk_norm)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    # GQA kv heads pass through as-is: ring circulates only the Hkv heads,
    # ulysses repeats to lcm(Hkv, sp) internally only when it must.
    if par.attn == "ulysses":
        if mask is not None:
            raise NotImplementedError("ulysses attention is causal only")
        o = ulysses_attention(q, k, v, par.sp_axis, causal=True)
    else:
        o = ring_attention(q, k, v, par.sp_axis, causal=mask is None,
                           mask=mask)
    o = o.reshape(B, Tl, Hl * Dh) @ lp["wo"].astype(x.dtype)
    if par.tp_axis is not None:
        o = lax.psum(o, par.tp_axis)  # row-parallel output reduction
    return o


def _mlp(x, lp, par: ParallelSpec):
    gate = jax.nn.silu(x @ lp["w_gate"].astype(x.dtype))
    up = x @ lp["w_up"].astype(x.dtype)
    out = (gate * up) @ lp["w_down"].astype(x.dtype)
    if par.tp_axis is not None:
        out = lax.psum(out, par.tp_axis)
    return out


def ffn(pre, lp, cfg: LlamaConfig, par: ParallelSpec):
    """The post-attention FFN sublayer: dense SwiGLU or MoE routing.
    Returns (y, aux_loss) — the single dispatch point shared by the
    training block and the KV-cache decode path."""
    if cfg.n_experts > 0:
        from . import moe
        if cfg.moe_dispatch == "dropless":
            return moe.dropless_moe_layer(pre, lp, cfg, par)
        return moe.moe_layer(pre, lp, cfg, par)
    return _mlp(pre, lp, par), jnp.float32(0.0)


def _dropless(cfg: LlamaConfig) -> bool:
    return cfg.n_experts > 0 and cfg.moe_dispatch == "dropless"


def block(x, lp, cfg: LlamaConfig, par: ParallelSpec, positions,
          mask=None, rope=None):
    """One transformer block (shape-preserving — the pipeline stage unit).
    Returns (x, aux): the load-balance loss of capacity experts (0 for
    dense MLPs), or dropless experts' ``[4]`` routing statistics.  Each
    sublayer with its norm lies under a scope of its own; the residual
    adds are the layer's."""
    with jax.named_scope(SCOPE_ATTENTION):
        a = _attention(_rmsnorm(x, lp["attn_norm"], cfg.norm_eps),
                       lp, cfg, par, positions, mask, rope)
    x = x + a
    with jax.named_scope(SCOPE_MLP):
        y, aux = ffn(_rmsnorm(x, lp["mlp_norm"], cfg.norm_eps), lp, cfg, par)
    return x + y, aux


def remat_policy(name: str):
    """What a remat'd layer keeps beside its input, by
    ``LlamaConfig.remat_policy``'s names; counted, once per traced stack
    (:func:`_layer_stack`, and ``models/bert.py``'s encoder under
    ``"dots"``).  Either policy keeps what the flash kernels name: a
    hand-written kernel is never rerun to make its own residuals again."""
    if name not in ("full", "dots"):
        raise ValueError(
            f"remat_policy must be 'full' or 'dots', got {name!r}")
    # imported here, as the kernels are where they are called: a program
    # without attention never loads Pallas
    from ..ops import flash_attention as _flash
    if _metrics.ACTIVE:
        _m_remat.inc(policy=name, saves="flash")
    cp = jax.checkpoint_policies
    named = cp.save_only_these_names(_flash.OUT_NAME, _flash.LSE_NAME)
    if name == "full":
        return named
    return cp.save_from_both_policies(
        cp.dots_with_no_batch_dims_saveable, named)


def _layer_stack(h, layers, cfg: LlamaConfig, par: ParallelSpec, positions,
                 mask=None):
    # Cast the whole stacked weight tree to compute dtype ONCE before the
    # scan: per-layer `.astype` inside the body re-converts every fp32
    # weight slice in both fwd and bwd scans (~16% matmul slowdown
    # measured); one bulk convert amortizes it and the bwd scan reuses
    # the converted stack as a residual.
    # (a dropless router stays in the parameters' precision: its logits
    # decide a top-k, and are computed in float32)
    keep = ("router",) if _dropless(cfg) else ()
    layers = {n: w if n in keep or w.dtype == cfg.dtype
              else w.astype(cfg.dtype) for n, w in layers.items()}
    rope = _qk_norm_tables(h, layers, cfg, positions)
    # a mask is closed over, not an argument: one known here (numpy)
    # stays so for the kernels' tile tables
    blk = block if mask is None else functools.partial(block, mask=mask)
    if rope is not None:
        blk = functools.partial(blk, rope=rope)
    body = blk
    if cfg.remat:
        body = jax.checkpoint(body, static_argnums=(2, 3),
                              policy=remat_policy(cfg.remat_policy))

    def scan_stack(body_fn, carry, ls):
        def scan_body(carry, lp):
            h, aux = carry
            # overlapped dispatch (identity unless an overlapped_backprop
            # context is armed): the tap's backward rule fires this
            # layer's gradient buckets inside the backward scan, the
            # moment they materialize — before the remaining layers'
            # backprop runs
            lp = _overlap.grad_tap(lp)
            h, aux_l = body_fn(h, lp, cfg, par, positions)
            return (h, aux + aux_l), None
        carry, _ = lax.scan(scan_body, carry, ls)
        return carry

    # aux accumulator derives from h (×0) so it inherits h's varying mesh
    # axes — a fresh constant would be invariant and fail check_vma's
    # carry-type check once the MoE aux (data-dependent) joins it
    aux0 = (h.astype(jnp.float32) * 0).sum()
    if _dropless(cfg):
        aux0 = aux0 + jnp.zeros((4,), jnp.float32)   # routing statistics
    n_local = jax.tree_util.tree_leaves(layers)[0].shape[0]
    k = min(cfg.remat_skip_layers, n_local) if cfg.remat else 0
    if k > 0:
        # remat'd prefix, then the last k layers un-remat'd (activations
        # saved; they are the first to run backward, so their skipped
        # recompute shortens the critical path immediately)
        first = jax.tree_util.tree_map(lambda w: w[:n_local - k], layers)
        last = jax.tree_util.tree_map(lambda w: w[n_local - k:], layers)
        carry = scan_stack(body, (h, aux0), first)
        h, aux = scan_stack(blk, carry, last)
    else:
        h, aux = scan_stack(body, (h, aux0), layers)
    return h, aux


def hidden(params, tokens, cfg: LlamaConfig, par: ParallelSpec,
           n_microbatches: int = 0, positions=None, mask=None):
    """Token ids → final-norm hidden states ``[B, T, D]`` (pre-head).

    ``tokens``: ``[B_local, T_local]`` — batch sharded over dp, sequence
    over sp.  With ``par.pp_axis``, ``n_microbatches`` must divide B_local
    and the layer stack runs through the GPipe scheduler.  ``positions
    [B, T]``: each token's position for RoPE (default: its index);
    ``mask``: the key ranges each query row sees (default: causal).
    """
    if cfg.layer_kinds:
        return _hybrid_hidden(params, tokens, cfg, par, positions, mask)
    Tl = tokens.shape[1]
    sp_idx = (lax.axis_index(par.sp_axis)
              if par.sp_axis is not None else 0)
    if (positions is not None or mask is not None) and (
            par.pp_axis is not None or par.sp_axis is not None):
        raise NotImplementedError(
            "positions and mask per token are not wired through the "
            "pipeline or a sequence-parallel axis")
    if positions is None:
        positions = (jnp.arange(Tl)[None, :] + sp_idx * Tl
                     ).astype(jnp.int32) * jnp.ones_like(tokens)
    with jax.named_scope(SCOPE_EMBED):
        h = _embed_lookup(params["embed"], tokens, cfg, par)
    aux = jnp.float32(0.0)

    if par.pp_axis is not None:
        from ..parallel.pipeline import pipeline_apply
        if n_microbatches <= 0:
            raise ValueError("pipeline parallelism needs n_microbatches > 0")
        B = h.shape[0]
        if B % n_microbatches:
            raise ValueError(
                f"batch {B} not divisible by n_microbatches={n_microbatches}")
        mb = B // n_microbatches
        h_mb = h.reshape(n_microbatches, mb, *h.shape[1:])
        # positions are identical for every batch row (pure function of the
        # sp shard), so stages recompute them instead of wiring them through
        pos_mb = (jnp.arange(Tl)[None, :] + sp_idx * Tl
                  ).astype(jnp.int32) * jnp.ones((mb, 1), jnp.int32)

        def stage_fn(stage_layers, x):
            return _layer_stack(x, stage_layers, cfg, par, pos_mb)

        # the MoE aux loss rides the pipeline's per-stage accumulator,
        # not the shape-preserving inter-stage wire
        out, aux = pipeline_apply(stage_fn, params["layers"], h_mb,
                                  axis_name=par.pp_axis, with_aux=True)
        h = out.reshape(B, Tl, cfg.d_model)
    else:
        h, aux = _layer_stack(h, params["layers"], cfg, par, positions,
                              mask)

    with jax.named_scope(SCOPE_HEAD):
        h = _rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return h, aux


def _hybrid_hidden(params, tokens, cfg: LlamaConfig, par: ParallelSpec,
                   positions, mask):
    """:func:`hidden` of a trunk of several kinds (models/hybrid.py): each
    kind's own mask and, where the config gives the kind a rotary table,
    its own positions (``arange(T)`` a row; none handed in), a final norm
    of the trunk's kind; with routed experts, their routing statistics
    summed over the layers in place of the zero."""
    from . import hybrid
    if (positions is not None or mask is not None
            or any(a is not None for a in (par.tp_axis, par.sp_axis,
                                           par.pp_axis))):
        raise NotImplementedError(
            "a trunk of several kinds takes no positions and no mask from "
            "outside (a kind's rotary table counts arange(T) a row, its "
            "mask is its own) and runs under plain data parallelism only")
    with jax.named_scope(SCOPE_EMBED):
        h = _embed_lookup(params["embed"], tokens, cfg, par)
    h, stats = hybrid.layer_stack(
        h, params["layers"], cfg,
        remat_policy(cfg.remat_policy) if cfg.remat else None,
        with_stats=True)
    with jax.named_scope(SCOPE_HEAD):
        h = hybrid.norm(h, params["final_norm"],
                        params.get("final_norm_bias"), cfg)
    return h, jnp.float32(0.0) if stats is None else stats


def forward(params, tokens, cfg: LlamaConfig, par: ParallelSpec,
            n_microbatches: int = 0):
    """Token ids → logits.  Call inside shard_map over the parallel mesh."""
    h, aux = hidden(params, tokens, cfg, par, n_microbatches)
    with jax.named_scope(SCOPE_HEAD):
        # tied embedding head (Llama-3 unties; tying halves test-model
        # memory and changes no parallel structure — the head matmul
        # stays [D, V])
        logits = h @ _head(params, cfg).T.astype(h.dtype)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if _vp_active(cfg, par):
            # local [B, T, V/tp] partials → full logits, shard order =
            # vocab order (API contract; the loss path never materializes
            # this)
            logits = lax.all_gather(logits, par.tp_axis, axis=-1,
                                    tiled=True)
    return logits, aux


def _head(params, cfg: LlamaConfig):
    """The output head ``[V, D]``: the embedding when tied."""
    return params["embed" if cfg.tie_embeddings else "head"]


def _chunked_xent(h, w_embed, targets, chunk: int, weights=None,
                  scaling: float = 1.0):
    """Mean cross-entropy without materializing full logits.

    Scans the (local) sequence in chunks; each chunk computes its
    ``[B, chunk, V]`` logit tile, reduces it to per-token ``lse - target``
    immediately, and ``jax.checkpoint`` re-derives the tile in the
    backward pass.  The [B, T, V] fp32 logits / log-softmax buffers of
    the one-shot path never exist, at the cost of re-running the head
    matmul once in bwd — the chunked-softmax idea flash attention applies
    to scores, applied to the vocabulary head.  ``weights [B, T]``
    multiply each token's term (the divisor stays ``B * T``); the logits
    are divided by ``scaling``.
    """
    B, T, D = h.shape
    n = T // chunk
    w = w_embed.astype(h.dtype)
    if weights is None:
        weights = jnp.ones((B, T), jnp.float32)
    hs = jnp.moveaxis(h.reshape(B, n, chunk, D), 1, 0)       # [n,B,c,D]
    ts = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)    # [n,B,c]
    ws = jnp.moveaxis(weights.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def body(acc, xt):
        hc, tc, wc = xt
        return acc + (_token_xent(hc, w, tc, scaling) * wc).sum(), None

    # the accumulator derives from h (×0) so it carries h's varying mesh
    # axes — a fresh constant would fail check_vma's carry-type check
    acc0 = (h.astype(jnp.float32) * 0).sum()
    total, _ = lax.scan(body, acc0, (hs, ts, ws))
    return total / (B * T)


def _token_xent(h, w, targets, scaling: float = 1.0):
    """``lse - target logit`` per token, float32; ``w [V, D]``; the logits
    divided by ``scaling``."""
    logits = (h @ w.T).astype(jnp.float32)
    if scaling != 1.0:
        logits = logits / scaling
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


def loss_fn(params, tokens, targets, cfg: LlamaConfig, par: ParallelSpec,
            n_microbatches: int = 0, *, positions=None, mask=None,
            weights=None, with_stats: bool = False):
    """Mean cross-entropy over local tokens plus the MoE load-balance
    auxiliary loss of capacity experts (caller pmeans over dp/sp axes).

    The objective is the caller's: ``targets [B, Tt]`` are what the
    logits at the first ``Tt`` positions are scored against (next-token
    training hands in the shifted tokens at full length), ``weights
    [B, Tt]`` multiply each position's term (the divisor stays ``B *
    Tt``), ``positions`` and ``mask`` go to :func:`hidden`.
    ``with_stats`` also returns dropless experts' routing statistics,
    summed over the layers (``moe.ROUTING_STATS``; zeros otherwise)."""
    # overlapped dispatch: tap the non-scanned leaves (embed, final_norm)
    # as one group HERE so every use — the lookup AND the tied loss head
    # — contributes to one cotangent before the dispatch fires; the
    # scanned stack is tapped per layer inside the scan body.  No-op
    # outside an overlapped_backprop context.
    params = _overlap.tap_root(params)
    h, aux = hidden(params, tokens, cfg, par, n_microbatches, positions,
                    mask)
    with jax.named_scope(SCOPE_HEAD):
        loss = _head_loss(h[:, :targets.shape[1]], _head(params, cfg),
                          targets, cfg, par, weights)
    stats = jnp.zeros((4,), jnp.float32)
    if _dropless(cfg):
        stats = aux
    elif cfg.n_experts > 0:
        loss = loss + cfg.aux_loss_coef * aux / cfg.n_layers
    return (loss, stats) if with_stats else loss


def _head_loss(h, head, targets, cfg: LlamaConfig, par: ParallelSpec,
               weights):
    """Mean cross-entropy of ``h [B, Tt, D]`` through ``head [V, D]``
    against ``targets [B, Tt]``: vocabulary-parallel, in chunks of
    ``cfg.loss_chunk`` rows, or whole."""
    if weights is not None and _vp_active(cfg, par):
        raise NotImplementedError(
            "weights per position go through the chunked or one-shot "
            "cross-entropy, not the vocab-parallel one")

    def warn_unchunked():
        if cfg.loss_chunk > 0 and h.shape[1] % cfg.loss_chunk:
            import logging
            logging.getLogger("horovod_tpu").warning(
                "loss_chunk=%d does not divide the local sequence length "
                "%d (sp sharding?); falling back to one-shot "
                "cross-entropy — the full [B, T, V%s] logits WILL be "
                "materialized", cfg.loss_chunk, h.shape[1],
                "/tp" if _vp_active(cfg, par) else "")

    scaling = cfg.logits_scaling
    if _vp_active(cfg, par):
        warn_unchunked()
        return _vocab_parallel_xent(h, head, targets, par,
                                    chunk=cfg.loss_chunk)
    if cfg.loss_chunk > 0 and h.shape[1] % cfg.loss_chunk == 0:
        return _chunked_xent(h, head, targets, cfg.loss_chunk, weights,
                             scaling)
    warn_unchunked()
    if weights is not None or scaling != 1.0:
        per_token = _token_xent(h, head.astype(h.dtype), targets, scaling)
        return (per_token if weights is None else per_token * weights).mean()
    logits = h @ head.T.astype(h.dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -ll.mean()


def count_params(cfg: LlamaConfig) -> int:
    if cfg.layer_kinds:
        from . import hybrid
        return hybrid.count_params(cfg)
    D, H, Hkv, Dh, F, L, V = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim, cfg.d_ff, cfg.n_layers,
                              cfg.vocab_size)
    per_layer = (2 * D + D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D
                 + 3 * D * F)
    return (1 if cfg.tie_embeddings else 2) * V * D + L * per_layer + D
