"""A decoder trunk whose layers are of several kinds and hand memory to
one another: the SambaY family (arXiv 2507.06607; Phi-4-mini-flash), the
Mamba-2 hybrids (GraniteMoeHybrid; the mixer from arXiv 2405.21060) and
the delta-rule hybrids (Kimi Linear, arXiv 2510.26692: linear attention
layers between gated softmax ones, routed experts in every layer) and the
short-convolution hybrids (gated short convolutions between grouped-query
attention layers with q/k norm).

``LlamaConfig.layer_kinds`` names each layer's kind; :mod:`.llama`'s
``init_params``, ``param_specs``, ``count_params`` and ``hidden`` come
here when it is set.

**The frame, which is the config's.**  Every layer is

    h = x + r Mixer(Norm1(x));  y = h + r FF(Norm2(h))

with no bias in a product; positions are a kind's (``attention``, ``swa``
and ``mla`` with a rotary table, below) and no other kind has any.  ``FF``
is a layer's: ``W2 (silu(g) * v)``, ``[g ; v] = W1 z``, or, with
``cfg.n_experts``, routed experts that drop no token
(``moe.dropless_moe_layer``: the experts this chip holds, scored by
``cfg.router_score``, a sigmoid's chosen with the layer's ``router_bias``,
the chosen weights over their sum plus ``cfg.router_eps``, times
``cfg.routed_scaling_factor``)
plus, with
``cfg.n_shared_experts``, that same ``W1`` / ``W2`` as the expert every
token passes through (``moe.shared_expert``), added once.  With experts,
the first ``cfg.first_dense_layers`` layers (DeepSeek-V3's
``first_k_dense_replace``) keep the dense form, ``cfg.dense_d_ff`` wide:
their leaves differ from the routed layers' of the same kind, so they are a
stack of their own in the parameters' tree, ``dense_<kind>``
(:func:`_stack`); at 0 the tree and the program are what they were.
``cfg.trunk_norm`` says which norm (``layernorm``: weight and bias;
``rmsnorm``: weight), for the layers' two and the final one;
``cfg.residual_multiplier`` is ``r``; the embedding's and the logits'
multipliers are applied in :mod:`.llama`, the softmax's scale
(``cfg.attention_multiplier``) in the ``attention`` kind.  Each at its
default computes what the trunk computed before the config carried it.

**The kinds, which are a family's**: their mixers.

* ``mamba`` — Mamba-1 (SambaY): ``[xs ; z] = W_in u``; ``xs = silu(conv(xs)
  + b)``, depthwise, causal, ``ssm_conv`` wide; ``[r ; B ; C] = W_x xs``;
  ``delta = softplus(W_dt r + b_dt)``; ``A = -exp(A_log)``; ``s`` the
  selective scan (:mod:`horovod_tpu.ops.selective_scan`); the mixer gives
  ``W_out (s * silu(z))``.  The last one before a ``gmu`` also emits ``m
  = s``, the memory.
* ``window``, ``full`` — differential attention (arXiv 2410.05258;
  SambaY): adjacent heads pair, ``A1 = softmax(q1 k1^T / sqrt(Dh) + M)``,
  ``A2`` of the pair's second heads, values the pair's ``[v ; v']``; ``o =
  RMSNorm((A1 - lambda A2) [v ; v']) (1 - lambda_init)`` with ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` and ``lambda_init =
  0.8 - 0.6 exp(-0.3 i)``, ``i`` the layer's published index
  (``layer_ids``).  ``M`` is causal within the last ``sliding_window``
  keys, or causal; a ``full`` layer that a ``cross`` layer follows keeps
  its ``k`` and ``v``.
* ``cross`` — the same with ``W_q`` and ``W_o`` only, attending
  causally to the ``full`` layer's ``k`` and ``v``.
* ``gmu`` — the gated memory unit, ``W_out (silu(W_in u) * m)``.
* ``mamba2`` — Mamba-2 (Granite): ``[z ; xBC ; dt] = W_in u``; ``xBC =
  silu(conv(xBC) + b)`` over all its channels; ``[x ; B ; C] = xBC``, ``x``
  in ``ssm_heads`` heads, ``B`` and ``C`` of ``ssm_state`` columns shared
  by a group's heads; ``delta = softplus(dt + dt_bias)`` and ``A =
  -exp(A_log)`` a head; ``y`` the chunked scan of a matrix state a head
  (:mod:`horovod_tpu.ops.ssd_scan`); the mixer gives ``W_out (RMSNorm(y *
  silu(z)) * w)``, the gate before the norm, over all channels at once.
  The two elementwise chains, convolution + SiLU + split and gate + norm,
  are :mod:`horovod_tpu.ops.mamba2_mixer`'s (float32 inside, the operands'
  dtype out); where its kernels run, ``z`` and ``xBC`` are a product each
  over their columns of ``W_in``, since a kernel reads no slice.
* ``attention`` — plain grouped-query attention (Granite): ``softmax(s q
  k^T + causal) v`` through ``W_o``, ``s = cfg.attention_multiplier`` (0 =
  ``1 / sqrt(Dh)``).  With ``cfg.attn_gate`` the heads' output is gated
  before ``W_o``: ``o * sigmoid(W_gate u)``, elementwise (the form G1 of
  arXiv 2505.06708).  With ``cfg.qk_norm`` ``q`` and ``k`` are RMS-normed a
  head before the rotation, ``q_norm`` and ``k_norm`` ``[Dh]`` every
  head's (``llama._rmsnorm`` at ``cfg.norm_eps``): one pass with the
  rotation (``ops/rope.norm_rotate``, a product each of ``wqkv``'s
  columns) where that kernel takes the rows, heads of 128 on a TPU, else
  XLA's form under ``hvd_rope``.
* ``swa`` — the same layer under a window (Mellum 2's
  ``sliding_attention``): row ``i`` sees the last ``sliding_window`` keys
  with its own, ``[max(0, i - w + 1), i + 1)``.  One body serves both;
  they differ in their key ranges and in their rotary table.  **Positions
  are these two kinds', a table a kind** (``cfg.rope_tables``, Hugging
  Face's ``rope_parameters`` a layer type): ``q`` and ``k`` are rotated
  (rotate-half, float32) by ``cos`` and ``sin`` of ``p * inv_freq[j]``,
  ``p = arange(T)`` a row, before the scores; ``inv_freq`` is plain RoPE's
  or YaRN's with its ``attention_factor`` on cos and sin both
  (``llama.rope_inv_freq`` has the equations), made once where the step
  is traced and handed to the kind's layers.  A kind without a table has
  no positions (Granite's and Solar's ``attention``).
* ``mla`` — multi-head latent attention without a query latent
  (DeepSeek-V3's, arXiv 2412.19437): ``q = u Wq``, a head's
  ``qk_nope_head_dim`` columns without positions and ``qk_rope_head_dim``
  rotary ones; ``[c ; k_pe] = u Wkv_a``, the latent ``c`` ``kv_lora_rank``
  wide and RMS-normed (``kv_norm``), ``k_pe`` ONE rotary key for every
  head, not normed; ``[k_nope ; v] = c Wkv_b`` a head; ``q_pe`` and
  ``k_pe`` rotated by the kind's table (``rope_tables``' ``mla``, made for
  ``qk_rope_head_dim``; rotate-half: a model whose checkpoint stores the
  rotary columns in pairs hands them over as ``[evens ; odds]``, a
  permutation of weight columns); ``softmax((q_nope k_nope^T + q_pe
  k_pe^T) / sqrt(d_nope + d_rope) + causal) v`` through ``W_o``.  The
  parameters hold every head's ``nope`` columns of ``wq`` before every
  head's rotary ones and every head's ``k_nope`` columns of ``wkv_b``
  before every head's ``v``: a product each part, so that the masked
  kernels read ``q_nope``, ``k_nope``, ``v`` as rows of whole heads where
  they lie and take ``(q_pe, k_pe)`` as their second pair
  (``local_attention(pair=)``: the split form; where the kernels refuse it
  the pair is joined into one query and key, the shared key copied a head).
  The rotation is XLA's form (``ops/rope.py`` turns whole heads of 128).
* ``conv`` — the gated short convolution, gated on both sides: ``[B ;
  C ; x] = W_in u``, three slices ``d_model`` wide in that order; ``g = B *
  x``; ``c_t = sum_j w[j] g_(t - Kc + 1 + j)`` a channel, depthwise, causal,
  ``ssm_conv`` taps, ``g`` zero before a row's first position, no bias, no
  activation; the mixer gives ``W_out (C * c)``.  No state but the last
  ``ssm_conv - 1`` positions and no positions of its own.  The elementwise
  chain (:func:`gated_conv`) is float32 inside, the operands' dtype out.
* ``kda`` — Kimi Delta Attention: ``[q ; k ; v] = silu(conv(W_qkv u))``,
  depthwise, causal, ``ssm_conv`` wide, no bias, in ``ssm_heads`` heads,
  keys ``ssm_state`` and values ``ssm_inner / ssm_heads`` wide; ``q`` and
  ``k`` of unit length a head; ``g = -exp(A_log) softplus(W_fb (W_fa u) +
  dt_bias)`` a key channel (``A_log`` a head) and ``beta = 2 sigmoid(W_b
  u)`` a head, both float32; ``o`` the chunked gated delta rule
  (:mod:`horovod_tpu.ops.kda_scan`); the mixer gives ``W_o (RMSNorm(o) w *
  sigmoid(W_gb (W_ga u)))``, the norm over a head's values.  The
  convolution with its SiLU and split is
  :func:`horovod_tpu.ops.mamba2_mixer.conv_silu_split`.

Attention goes through ``ring_attention.local_attention`` with the mask
as key ranges (``window_ranges``, ``causal_ranges``); differential
attention in two calls a layer, ``(q1, k1, [v ; v'])`` and ``(q2, k2, [v
; v'])``, the masked flash kernels taking values twice as wide as queries
and keys.

Parameters are a tree per kind (and per ``dense_<kind>``, above), each leaf
stacked over the stack's layers;
:func:`layer_stack` runs the kinds in order, scanning runs of equal
layers, and carries ``(h, m, kv)``.  Under ``cfg.remat`` each layer (a
run's scan body) is a ``jax.checkpoint`` under the config's policy: the
flash kernels' ``out`` and ``lse`` are kept by name as in the llama
trunk, and ``m`` and the ``full`` layer's ``k``, ``v`` are saved, being
what the remat'd ``gmu`` and ``cross`` layers take as inputs: ``B x T x
ssm_inner x 2 + 2 x B x T x Hkv x Dh x 2`` bytes in bf16 (126 MB at
8,192 positions of the published widths); the emitting layers are made
again in the backward pass like any other, their scan included.

``hvd_layer_kind_total{kind}`` counts the layers traced; the mixers run
under the scopes ``hvd_ssm_mixer``, ``hvd_gmu``, ``hvd_diff_attention``,
``hvd_conv_mixer`` (``conv``: its norm and two products, the elementwise
chain ``B * x``, taps, ``C *`` under ``hvd_gated_conv`` inside it),
``hvd_ssd_mixer`` (the scan's call inside it under ``hvd_ssd_scan``; the
mixer's own kernels ``hvd_conv_silu_fwd`` / ``_bwd`` and
``hvd_gated_norm_fwd`` / ``_bwd`` under no scope of their own, rows of the
mixer's), ``hvd_kda_mixer`` (the scan's call under ``hvd_kda_scan``),
``hvd_attention`` and ``hvd_window_attention`` (``swa``; either's rotary
table and products under ``hvd_rope`` inside it, and
``hvd_rope_tables_total{kind, type}`` counts the tables traced),
``hvd_mla_attention`` (``mla``: ``u Wkv_a``, the latent's norm and ``c
Wkv_b`` under ``hvd_mla_latent`` inside it, the rotation under ``hvd_rope``;
``local_attention``'s ``hvd_mla_call_total{path, form}`` counts its call
sites); the
feed-forward under ``hvd_mlp``, routed experts'
``hvd_moe_route`` / ``hvd_moe_experts`` and the shared expert's
``hvd_moe_shared`` inside it.  Plain data parallelism only: nothing here
is sharded over a tensor-, sequence-, pipeline- or expert-parallel axis
yet.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import metrics as _metrics
from ..ops import flash_attention as _fa
from ..ops import mamba2_mixer as _mixer
from ..ops import rope as _rotary
from ..ops.kda_scan import kda_scan
from ..ops.selective_scan import selective_scan
from ..ops.ssd_scan import ssd_scan, ssd_scan_turned
from ..ops.ssd_scan import supported as ssd_scan_supported
from ..parallel.ring_attention import local_attention
from ..scopes import (SCOPE_ATTENTION, SCOPE_CONV_MIXER,
                      SCOPE_DIFF_ATTENTION, SCOPE_GATED_CONV, SCOPE_GMU,
                      SCOPE_KDA_MIXER, SCOPE_KDA_SCAN, SCOPE_MLA_ATTENTION,
                      SCOPE_MLA_LATENT, SCOPE_MLP, SCOPE_ROPE, SCOPE_SHARED,
                      SCOPE_SSD_MIXER, SCOPE_SSD_SCAN, SCOPE_SSM_MIXER,
                      SCOPE_WINDOW_ATTENTION)
from .bert import _layernorm as layer_norm  # fp32 inside, weight and bias
from . import moe
from .llama import ParallelSpec, _rmsnorm, rope_table, rotate

# the five of PR 33 (SambaY), the two of PR 40 (Granite: ``mamba2`` under
# ``hvd_ssd_mixer``, ``attention`` under ``hvd_attention``), and ``kda``
# (PR 42: Kimi Delta Attention under ``hvd_kda_mixer``; ``attention`` takes
# ``cfg.attn_gate``), and ``swa`` (PR 46: ``attention``'s layer under the
# window and ``hvd_window_attention``; either takes a rotary table), and
# ``mla`` (PR 49: multi-head latent attention under ``hvd_mla_attention``,
# its rotary part under the kind's own table), and ``conv`` (PR 53: the
# gated short convolution under ``hvd_conv_mixer``; ``attention`` and
# ``swa`` take ``cfg.qk_norm``)
KINDS = ("mamba", "window", "full", "gmu", "cross", "mamba2", "attention",
         "kda", "swa", "mla", "conv")
_DIFFERENTIAL = ("window", "full", "cross")
_PLAIN = {"attention": SCOPE_ATTENTION, "swa": SCOPE_WINDOW_ATTENTION}
# the kinds that may have a rotary table, each with the scope the table is
# made under (``layer_stack``; ``mla``'s turns its rotary columns alone)
_TABLED = {**_PLAIN, "mla": SCOPE_MLA_ATTENTION}
_MATRICES = ("w1", "w2", "in_proj", "x_proj", "dt_proj", "out_proj", "wqkv",
             "wq", "wo", "wgate", "f_a", "f_b", "g_a", "g_b", "b_proj",
             "we_gate", "we_up", "we_down", "wkv_a", "wkv_b")
# a leading dense layer's stack in a trunk with routed experts: its kind's
# name after this (``dense_mla``): the leaves differ, so the stack does
_DENSE = "dense_"
_L2_EPS = 1e-6      # under the root of a head's squared length (fla's)

_m_kinds = _metrics.counter(
    "hvd_layer_kind_total",
    "Layers of a trunk of several kinds traced, by kind "
    "(models/hybrid.py)", labels=("kind",))
_m_ropes = _metrics.counter(
    "hvd_rope_tables_total",
    "Rotary tables of a trunk of several kinds traced, one a layer kind "
    "that has one, by kind and rope_type (models/hybrid.py)",
    labels=("kind", "type"))


def check(cfg) -> None:
    """The config's kinds make a trunk: known names, every ``gmu`` after a
    ``mamba`` and every ``cross`` after a ``full``."""
    kinds = cfg.layer_kinds
    if len(kinds) != cfg.n_layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_kinds must name n_layers={cfg.n_layers} "
                         f"layers from {KINDS}, got {kinds!r}")
    if cfg.layer_ids and len(cfg.layer_ids) != len(kinds):
        raise ValueError("layer_ids must give every layer's published index")
    for kind, source in (("gmu", "mamba"), ("cross", "full")):
        if kind in kinds and source not in kinds[:kinds.index(kind)]:
            raise ValueError(f"a {kind} layer needs a {source} layer "
                             "before it")
    if set(kinds) & set(_DIFFERENTIAL) and (cfg.n_heads % 2
                                            or cfg.n_kv_heads % 2):
        raise ValueError("differential attention pairs adjacent heads: "
                         "n_heads and n_kv_heads must be even")
    if "mamba2" in kinds and (
            cfg.ssm_heads <= 0 or cfg.ssm_inner % cfg.ssm_heads
            or cfg.ssm_groups <= 0 or cfg.ssm_heads % cfg.ssm_groups):
        raise ValueError(
            "a mamba2 layer needs ssm_heads dividing ssm_inner and "
            f"ssm_groups dividing ssm_heads, got {cfg.ssm_heads} heads of "
            f"{cfg.ssm_inner} channels in {cfg.ssm_groups} groups")
    if "kda" in kinds and (cfg.ssm_heads <= 0 or cfg.ssm_state <= 0
                           or cfg.ssm_inner % cfg.ssm_heads):
        raise ValueError(
            "a kda layer needs ssm_heads heads of ssm_state key channels "
            f"dividing ssm_inner, got {cfg.ssm_heads} heads, keys "
            f"{cfg.ssm_state} wide, {cfg.ssm_inner} value channels")
    if "swa" in kinds and cfg.sliding_window <= 0:
        raise ValueError("a swa layer needs sliding_window > 0")
    if "mla" in kinds and (
            min(cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.v_head_dim) <= 0 or cfg.qk_rope_head_dim % 2):
        raise ValueError(
            "an mla layer needs kv_lora_rank, qk_nope_head_dim, an even "
            "qk_rope_head_dim and v_head_dim, got "
            f"{cfg.kv_lora_rank}, {cfg.qk_nope_head_dim}, "
            f"{cfg.qk_rope_head_dim}, {cfg.v_head_dim}")
    tabled = [kind for kind, _ in cfg.rope_tables]
    if set(tabled) - set(_TABLED) or len(set(tabled)) != len(tabled):
        raise ValueError(f"rope_tables gives each of {tuple(_TABLED)} one "
                         f"table at most, got {tabled!r}")
    if not 0 <= cfg.first_dense_layers <= len(kinds):
        raise ValueError("first_dense_layers counts leading layers of the "
                         f"trunk's {len(kinds)}, got {cfg.first_dense_layers}")
    if cfg.n_experts > 0 and cfg.moe_dispatch != "dropless":
        raise ValueError("routed experts in a trunk of several kinds are "
                         "the dropless ones (moe_dispatch='dropless')")


def published_kinds(n_layers: int):
    """Each layer's kind by the model's rule: even layers carry a mixer of
    the Mamba family, odd ones of the attention family; the first half
    (the self-decoder) and layer ``n/2`` are Mamba and window attention,
    layer ``n/2 + 1`` is the one full attention, and the cross-decoder
    after it alternates gated memory units and cross attention."""
    half = n_layers // 2
    return tuple(
        ("mamba" if i <= half else "gmu") if i % 2 == 0
        else "window" if i < half else "full" if i == half + 1 else "cross"
        for i in range(n_layers))


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# --------------------------------------------------------------- shapes

def layer_shapes(cfg, kind, dense=False):
    """{leaf: shape} of one layer of ``kind``; ``dense``: a leading layer
    whose feed-forward is the dense one (``cfg.dense_d_ff`` wide) though
    the config has routed experts."""
    D, Dh = cfg.d_model, cfg.head_dim
    F = (cfg.dense_d_ff or cfg.d_ff) if dense else cfg.d_ff
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    Di, N, Kc, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    shapes = {"norm1_w": (D,), "norm1_b": (D,), "norm2_w": (D,),
              "norm2_b": (D,), "w1": (D, 2 * F), "w2": (F, D)}
    if cfg.trunk_norm == "rmsnorm":
        del shapes["norm1_b"], shapes["norm2_b"]
    if kind == "mamba2":
        Hs, conv = cfg.ssm_heads, Di + 2 * cfg.ssm_groups * N
        shapes.update({
            "in_proj": (D, Di + conv + Hs), "conv_w": (Kc, conv),
            "conv_b": (conv,), "dt_bias": (Hs,), "A_log": (Hs,), "D": (Hs,),
            "gate_norm": (Di,), "out_proj": (Di, D)})
    elif kind in _PLAIN:
        shapes.update({"wqkv": (D, (H + 2 * Hkv) * Dh), "wo": (H * Dh, D)})
        if cfg.attn_gate:
            shapes["wgate"] = (D, H * Dh)
        if cfg.qk_norm:
            shapes.update({"q_norm": (Dh,), "k_norm": (Dh,)})
    elif kind == "conv":
        shapes.update({"in_proj": (D, 3 * D), "conv_w": (Kc, D),
                       "out_proj": (D, D)})
    elif kind == "kda":
        Hs, Vd = cfg.ssm_heads, cfg.ssm_inner // cfg.ssm_heads
        conv = 2 * Hs * N + Di
        shapes.update({
            "wqkv": (D, conv), "conv_w": (Kc, conv), "f_a": (D, N),
            "f_b": (N, Hs * N), "dt_bias": (Hs * N,), "A_log": (Hs,),
            "b_proj": (D, Hs), "g_a": (D, Vd), "g_b": (Vd, Di),
            "o_norm": (Vd,), "wo": (Di, D)})
    elif kind == "mla":
        R, Dn, Dr, Dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        shapes.update({
            "wq": (D, H * (Dn + Dr)), "wkv_a": (D, R + Dr), "kv_norm": (R,),
            "wkv_b": (R, H * (Dn + Dv)), "wo": (H * Dv, D)})
    elif kind == "mamba":
        shapes.update({
            "in_proj": (D, 2 * Di), "conv_w": (Kc, Di), "conv_b": (Di,),
            "x_proj": (Di, R + 2 * N), "dt_proj": (R, Di), "dt_bias": (Di,),
            "A_log": (Di, N), "D": (Di,), "out_proj": (Di, D)})
    elif kind == "gmu":
        shapes.update({"in_proj": (D, Di), "out_proj": (Di, D)})
    else:
        width = H * Dh if kind == "cross" else (H + 2 * Hkv) * Dh
        shapes.update({
            "wq" if kind == "cross" else "wqkv": (D, width),
            "wo": (H * Dh, D), "lambda_q1": (Dh,), "lambda_k1": (Dh,),
            "lambda_q2": (Dh,), "lambda_k2": (Dh,), "subln": (2 * Dh,)})
    if cfg.n_experts > 0 and not dense:
        held, S = cfg.experts_held or cfg.n_experts, cfg.n_shared_experts
        del shapes["w1"], shapes["w2"]
        shapes["router"] = (D, cfg.n_experts)
        if cfg.router_score == "sigmoid":   # the selection bias is a sigmoid's
            shapes["router_bias"] = (cfg.n_experts,)
        shapes.update({"we_gate": (held, D, F), "we_up": (held, D, F),
                       "we_down": (held, F, D)})
        if S:
            shapes.update({"w1": (D, 2 * S * F), "w2": (S * F, D)})
    return shapes


def _is_dense(cfg, i) -> bool:
    """Layer ``i`` is a leading dense layer of a trunk with routed experts
    (in one without, every layer is dense and none is set apart)."""
    return cfg.n_experts > 0 and i < cfg.first_dense_layers


def _stack(cfg, i) -> str:
    """The name of layer ``i``'s stack in the parameters' tree: its kind,
    after ``dense_`` where it is a leading dense layer."""
    return (_DENSE if _is_dense(cfg, i) else "") + cfg.layer_kinds[i]


def _stacks(cfg):
    """{stack: (kind, dense, layers)}: the kinds' stacks in ``KINDS``'
    order, then the leading dense layers' in the same."""
    names = [_stack(cfg, i) for i in range(len(cfg.layer_kinds))]
    return {pre + k: (k, bool(pre), names.count(pre + k))
            for pre in ("", _DENSE) for k in KINDS if pre + k in names}


def count_params(cfg) -> int:
    layers = sum(n * sum(int(np.prod(s))
                         for s in layer_shapes(cfg, kind, dense).values())
                 for kind, dense, n in _stacks(cfg).values())
    return ((1 if cfg.tie_embeddings else 2) * cfg.vocab_size * cfg.d_model
            + layers + (2 if cfg.trunk_norm == "layernorm" else 1)
            * cfg.d_model)


def init_layers(cfg, key):
    """{stack: {leaf: [layers of the stack, ...]}}, a stack a kind (and one
    more, ``dense_<kind>``, for leading dense layers before routed ones:
    :func:`_stack`): matrices normal at
    ``fan_in ** -0.5``, norms at 1 and 0, and Mamba's own: ``A_log =
    log(1..N)``, ``D = 1``, ``softplus(dt_bias)`` log-uniform on 1e-3 ..
    1e-1; ``lambda``'s vectors normal(0, 0.1).  Mamba-2's and KDA's ``A_log
    = log(uniform(1, 16))`` a head; a router's selection bias 0."""
    check(cfg)
    dt = cfg.param_dtype
    out = {}
    for a, (stack, (kind, dense, n)) in enumerate(_stacks(cfg).items()):
        tree = {}
        for b, (name, shape) in enumerate(
                layer_shapes(cfg, kind, dense).items()):
            k = jax.random.fold_in(jax.random.fold_in(key, a), b)
            full = (n,) + shape
            if name in ("norm1_w", "norm2_w", "subln", "D", "gate_norm",
                        "o_norm", "kv_norm", "q_norm", "k_norm"):
                leaf = jnp.ones(full, dt)
            elif name in ("norm1_b", "norm2_b", "conv_b", "router_bias"):
                leaf = jnp.zeros(full, dt)
            elif name == "A_log" and kind in ("mamba2", "kda"):
                leaf = jnp.log(jax.random.uniform(k, full, dt, 1.0, 16.0))
            elif name == "A_log":
                leaf = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[1] + 1, dtype=dt)), full)
            elif name == "dt_bias":
                step = jnp.exp(jax.random.uniform(
                    k, full, dt, math.log(1e-3), math.log(1e-1)))
                leaf = step + jnp.log(-jnp.expm1(-step))
            elif name.startswith("lambda_"):
                leaf = jax.random.normal(k, full, dt) * 0.1
            else:       # fan-in: the rows, an expert's rows
                fan_in = shape[1] if name.startswith("we_") else shape[0]
                leaf = jax.random.normal(k, full, dt) * fan_in ** -0.5
            tree[name] = leaf
        out[stack] = tree
    return out


def layer_specs(cfg):
    """PartitionSpecs of :func:`init_layers`' tree: every leaf replicated."""
    from jax.sharding import PartitionSpec as P
    return {stack: {name: P() for name in layer_shapes(cfg, kind, dense)}
            for stack, (kind, dense, _) in _stacks(cfg).items()}


# --------------------------------------------------------------- layers

def _mlp(u, lp):
    return moe.shared_expert(u, lp["w1"], lp["w2"])


def _taps(x32, w):
    """The depthwise causal convolution of float32 ``x32 [B, T, C]`` under
    the taps ``w [Kc, C]``: ``w[Kc - 1]`` on the position itself, zeros
    before a row's first position."""
    Kc, T = w.shape[0], x32.shape[1]
    padded = jnp.pad(x32, ((0, 0), (Kc - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * w[j].astype(jnp.float32)
               for j in range(Kc))


def _conv_silu(xs, lp):
    """``silu(conv(xs) + b)``: depthwise, causal, ``ssm_conv`` wide, in
    float32; back in ``xs``'s dtype."""
    f32 = jnp.float32
    return jax.nn.silu(_taps(xs.astype(f32), lp["conv_w"])
                       + lp["conv_b"].astype(f32)).astype(xs.dtype)


def _mamba(u, lp, cfg):
    """-> (the mixer's output ``[B, T, D]``, the scan's output ``s [B, T,
    ssm_inner]`` before the gate)."""
    N, R = cfg.ssm_state, cfg.ssm_dt_rank
    f32 = jnp.float32
    xs, z = jnp.split(u @ lp["in_proj"], 2, axis=-1)
    xs = _conv_silu(xs, lp)
    r, Bm, Cm = jnp.split(xs @ lp["x_proj"], (R, R + N), axis=-1)
    delta = jax.nn.softplus(
        jnp.dot(r, lp["dt_proj"], preferred_element_type=f32)
        + lp["dt_bias"].astype(f32))
    s = selective_scan(xs, delta, -jnp.exp(lp["A_log"].astype(f32)), Bm, Cm,
                       lp["D"].astype(f32))
    return (s * jax.nn.silu(z)) @ lp["out_proj"], s


def _mamba2(u, lp, cfg):
    """The Mamba-2 mixer's output ``[B, T, D]``."""
    f32 = jnp.float32
    B, T, _ = u.shape
    Di, Hs, G, N = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    P, conv = Di // Hs, Di + 2 * G * N
    sizes = (Di, G * N, G * N)
    W = lp["in_proj"]
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, u.dtype)
    kernels = _mixer.supported(sds(B, T, conv), sizes)
    # the three kernels hand x and y on in the scan's own layout, the
    # positions on the lanes, where all of them run
    turned = (kernels and _mixer.supported(sds(B, T, conv), sizes, True)
              and ssd_scan_supported(
                  sds(B, T, Hs, P), sds(B, T, Hs), lp["A_log"],
                  sds(B, T, G, N), sds(B, T, G, N), lp["D"], cfg.ssm_chunk))
    if kernels:
        # a kernel reads no slice of a joint array: a product each
        z, xBC = u @ W[:, :Di], u @ W[:, Di:Di + conv]
    else:
        z, xBC = jnp.split(u @ W[:, :Di + conv], (Di,), axis=-1)
    # the step's 64 columns come out of their product in float32: a decay
    # is the exponential of up to a chunk's sum of them
    dt = jnp.dot(u, W[:, Di + conv:], preferred_element_type=f32)
    x, Bm, Cm = _mixer.conv_silu_split(xBC, lp["conv_w"], lp["conv_b"], sizes,
                                       turned)
    delta = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
    scan, heads = ((ssd_scan_turned, (B, Hs, P, T)) if turned
                   else (ssd_scan, (B, T, Hs, P)))
    with jax.named_scope(SCOPE_SSD_SCAN):
        y = scan(x.reshape(heads), delta, -jnp.exp(lp["A_log"].astype(f32)),
                 Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N),
                 lp["D"].astype(f32), cfg.ssm_chunk)
    return _mixer.gated_rmsnorm(y.reshape(x.shape), z, lp["gate_norm"],
                                cfg.norm_eps, turned) @ lp["out_proj"]


def _kda(u, lp, cfg):
    """The Kimi Delta Attention mixer's output ``[B, T, D]``."""
    f32 = jnp.float32
    B, T, _ = u.shape
    Hs, K = cfg.ssm_heads, cfg.ssm_state
    Vd = cfg.ssm_inner // Hs
    q, k, v = _mixer.conv_silu_split(
        u @ lp["wqkv"], lp["conv_w"], jnp.zeros_like(lp["conv_w"][0]),
        (Hs * K, Hs * K, Hs * Vd))

    def unit(a):            # a head's keys or queries at length 1
        a32 = a.reshape(B, T, Hs, K).astype(f32)
        return (a32 * lax.rsqrt(jnp.sum(a32 * a32, -1, keepdims=True)
                                + _L2_EPS)).astype(a.dtype)

    # decays and write strengths come out of their products in float32: a
    # decay is the exponential of up to a chunk's sum of them
    f = jnp.dot(u @ lp["f_a"], lp["f_b"], preferred_element_type=f32)
    g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        f + lp["dt_bias"].astype(f32)).reshape(B, T, Hs, K)
    beta = 2.0 * jax.nn.sigmoid(
        jnp.dot(u, lp["b_proj"], preferred_element_type=f32))
    with jax.named_scope(SCOPE_KDA_SCAN):
        o = kda_scan(unit(q), unit(k), v.reshape(B, T, Hs, Vd), g, beta,
                     cfg.ssm_chunk)
    o32 = o.astype(f32)
    o32 = o32 * lax.rsqrt(jnp.mean(o32 * o32, -1, keepdims=True)
                          + cfg.norm_eps) * lp["o_norm"].astype(f32)
    gate = jax.nn.sigmoid(((u @ lp["g_a"]) @ lp["g_b"]).astype(f32))
    return (o32.reshape(gate.shape) * gate).astype(u.dtype) @ lp["wo"]


def gated_conv(b, c, x, w):
    """``c * conv(b * x)``, the gated short convolution's elementwise chain:
    ``b``, ``c``, ``x`` ``[B, T, D]``, ``w [Kc, D]`` the taps a channel,
    ``w[Kc - 1]`` on the position itself; depthwise, causal, ``b * x`` zero
    before a row's first position, no bias and no activation; in float32,
    back in ``x``'s dtype."""
    f32 = jnp.float32
    with jax.named_scope(SCOPE_GATED_CONV):
        return (c.astype(f32) * _taps(b.astype(f32) * x.astype(f32), w)
                ).astype(x.dtype)


def _conv_mixer(u, lp):
    """The gated short convolution's output ``[B, T, D]``."""
    b, c, x = jnp.split(u @ lp["in_proj"], 3, axis=-1)
    return gated_conv(b, c, x, lp["conv_w"]) @ lp["out_proj"]


def _normed_qkv(u, lp, rope, cfg):
    """``(q, k, v)`` a head of the ``attention`` and ``swa`` kinds under
    ``cfg.qk_norm``: ``q`` and ``k`` RMS-normed a head (``q_norm``,
    ``k_norm`` ``[Dh]``, every head's) and then rotated where the kind has
    a table.  Where ``ops/rope.norm_rotate`` takes the rows (a TPU, heads
    of 128) norm and rotation are its one pass on a product each of
    ``wqkv``'s columns, as in the llama trunk; else XLA's form."""
    B, T, _ = u.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ends = (H * Dh, (H + Hkv) * Dh)
    rows = lambda n: jax.ShapeDtypeStruct((B, T, n * Dh), u.dtype)
    if rope is not None and all(
            _rotary.norm_supported(rows(n), lp[w], *rope)
            for n, w in ((H, "q_norm"), (Hkv, "k_norm"))):
        W = lp["wqkv"]
        with jax.named_scope(SCOPE_ROPE):
            q = _rotary.norm_rotate(u @ W[:, :ends[0]], lp["q_norm"], *rope,
                                    cfg.norm_eps)
            k = _rotary.norm_rotate(u @ W[:, ends[0]:ends[1]], lp["k_norm"],
                                    *rope, cfg.norm_eps)
        v = u @ W[:, ends[1]:]
    else:
        q, k, v = jnp.split(u @ lp["wqkv"], ends, axis=-1)
        with jax.named_scope(SCOPE_ROPE):
            q = _rmsnorm(q.reshape(B, T, H, Dh), lp["q_norm"], cfg.norm_eps)
            k = _rmsnorm(k.reshape(B, T, Hkv, Dh), lp["k_norm"], cfg.norm_eps)
            if rope is not None:
                _rotary.count_xla(normed=True)
                q, k = rotate(q, *rope), rotate(k, *rope)
    return (q.reshape(B, T, H, Dh), k.reshape(B, T, Hkv, Dh),
            v.reshape(B, T, Hkv, Dh))


def _plain_qkv(u, lp, rope, cfg):
    """``(q, k, v)`` a head of the ``attention`` and ``swa`` kinds, ``q``
    and ``k`` rotated where the kind has a table."""
    B, T, _ = u.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = u @ lp["wqkv"]
    widths = (H * Dh, Hkv * Dh, Hkv * Dh)
    joined = rope is not None and _rotary.supported(qkv, *rope, widths)
    if joined:
        # the kernel reads the product's columns where they lie and writes
        # q, k (rotated) and v each whole: no slice before it, and backward
        # no concatenate
        with jax.named_scope(SCOPE_ROPE):
            q, k, v = _rotary.split_rotate(qkv, *rope, widths,
                                           (True, True, False))
    else:
        q, k, v = jnp.split(qkv, (H * Dh, (H + Hkv) * Dh), axis=-1)
    q, k = q.reshape(B, T, H, Dh), k.reshape(B, T, Hkv, Dh)
    if rope is not None and not joined:
        _rotary.count_xla()
        with jax.named_scope(SCOPE_ROPE):
            q, k = rotate(q, *rope), rotate(k, *rope)
    return q, k, v.reshape(B, T, Hkv, Dh)


def latent_attention(u, lp, rope, cfg):
    """The latent-attention mixer's output ``[B, T, D]`` of the normed
    stream ``u``; ``rope`` the kind's ``(cos, sin) [T, qk_rope_head_dim /
    2]`` or None."""
    B, T, _ = u.shape
    H, R = cfg.n_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    wq, wb = lp["wq"], lp["wkv_b"]
    # a product each part of q and of [k_nope ; v]: the kernels read rows
    # of whole heads where they lie, and no slice of a joint array
    q_n = (u @ wq[:, :H * Dn]).reshape(B, T, H, Dn)
    q_r = (u @ wq[:, H * Dn:]).reshape(B, T, H, Dr)
    with jax.named_scope(SCOPE_MLA_LATENT):
        c, k_r = jnp.split(u @ lp["wkv_a"], (R,), axis=-1)
        c = _rmsnorm(c, lp["kv_norm"], cfg.norm_eps)
        k_n = (c @ wb[:, :H * Dn]).reshape(B, T, H, Dn)
        v = (c @ wb[:, H * Dn:]).reshape(B, T, H, Dv)
    k_r = k_r.reshape(B, T, 1, Dr)
    if rope is not None:
        # XLA's form: the kernel of ops/rope.py turns whole heads of 128
        _rotary.count_xla()
        with jax.named_scope(SCOPE_ROPE):
            q_r, k_r = rotate(q_r, *rope), rotate(k_r, *rope)
    o = local_attention(q_n, k_n, v, sm_scale=(Dn + Dr) ** -0.5,
                        mask=_fa.causal_ranges(T), pair=(q_r, k_r))
    return o.reshape(B, T, H * Dv) @ lp["wo"]


def _feed_forward(z, lp, cfg, dense=False):
    """The layer's feed-forward of the normed stream ``z`` -> (its output,
    routed experts' ``[4]`` statistics or None): the dense one where the
    config has no experts or the layer is a leading ``dense`` one."""
    if cfg.n_experts == 0 or dense:
        return _mlp(z, lp), None
    y, stats = moe.dropless_moe_layer(z, lp, cfg, ParallelSpec())
    if cfg.n_shared_experts:
        with jax.named_scope(SCOPE_SHARED):
            y = y + _mlp(z, lp)
    return y, stats


def _pairs(x):
    """``[B, T, H, Dh]`` -> the pairs' first and second heads, ``[B, T,
    H / 2, Dh]`` each."""
    B, T, H, Dh = x.shape
    x = x.reshape(B, T, H // 2, 2, Dh)
    return x[:, :, :, 0], x[:, :, :, 1]


def _diff_attention(q, k, v, lp, lam0, mask, cfg):
    """q ``[B, T, H, Dh]``; k, v ``[B, Tk, Hkv, Dh]`` -> ``[B, T, D]``."""
    f32 = jnp.float32
    B, T, H, Dh = q.shape
    (q1, q2), (k1, k2) = _pairs(q), _pairs(k)
    vv = v.reshape(B, v.shape[1], v.shape[2] // 2, 2 * Dh)
    a1 = local_attention(q1, k1, vv, mask=mask).astype(f32)
    a2 = local_attention(q2, k2, vv, mask=mask).astype(f32)
    dot = lambda a, b: jnp.sum(lp[a].astype(f32) * lp[b].astype(f32))
    lam = (jnp.exp(dot("lambda_q1", "lambda_k1"))
           - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam0)
    o = a1 - lam * a2
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * lp["subln"].astype(f32) * (1.0 - lam0)
    return o.astype(q.dtype).reshape(B, T, H * Dh) @ lp["wo"]


def key_ranges(kind, T, cfg):
    """The key ranges ``[T, 4]`` a layer of ``kind`` sees: causal within
    the last ``sliding_window`` keys, or causal."""
    if kind in ("window", "swa"):
        return _fa.window_ranges(T, cfg.sliding_window)
    return _fa.causal_ranges(T)


def norm(x, w, b, cfg):
    """The trunk's norm, by ``cfg.trunk_norm``; ``b`` is None for
    ``rmsnorm``."""
    if cfg.trunk_norm == "rmsnorm":
        return _rmsnorm(x, w, cfg.norm_eps)
    return layer_norm(x, w, b, cfg.norm_eps)


def join(x, y, cfg):
    """The residual stream takes a sublayer's output, times
    ``cfg.residual_multiplier`` (in float32, rounded once)."""
    if cfg.residual_multiplier == 1.0:
        return x + y
    return (x.astype(jnp.float32) + cfg.residual_multiplier
            * y.astype(jnp.float32)).astype(x.dtype)


def _layer(kind, emits, cfg, dense=False):
    """One layer of ``kind`` (``dense``: a leading layer with the dense
    feed-forward before routed ones) as ``f(h, lp, lam0, memory, rope=None)
    -> (h, emitted, stats)``: ``memory`` is ``m`` for a gmu, ``(k, v)`` for a
    cross layer, else None; ``rope`` the kind's ``(cos, sin)``
    (``llama.rope_table``), None where it has no positions; ``emitted`` is
    what an emitting mamba (``s``) or full layer (``(k, v)``) hands on, else
    None; ``stats`` routed experts' ``[4]`` statistics, None of a dense
    feed-forward."""
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def f(h, lp, lam0, memory, rope=None):
        # the products' matrices in the compute dtype; norms, the
        # convolution, the scan's A, D and step bias and lambda's vectors
        # stay in the parameters'
        lp = {n: w.astype(cfg.dtype) if n in _MATRICES else w
              for n, w in lp.items()}
        B, T, _ = h.shape
        norm1 = lambda: norm(h, lp["norm1_w"], lp.get("norm1_b"), cfg)
        emitted = None
        # the kinds of PR 40 and 42 open their scope around the sublayer
        # with its norm, as ``llama.block`` and ``hvd_mlp`` do; the five of
        # PR 33 keep ``norm1`` before theirs, where the accepted metrics
        # read them
        if kind == "mamba2":
            with jax.named_scope(SCOPE_SSD_MIXER):
                y = _mamba2(norm1(), lp, cfg)
        elif kind == "kda":
            with jax.named_scope(SCOPE_KDA_MIXER):
                y = _kda(norm1(), lp, cfg)
        elif kind == "mla":
            with jax.named_scope(SCOPE_MLA_ATTENTION):
                y = latent_attention(norm1(), lp, rope, cfg)
        elif kind == "conv":
            with jax.named_scope(SCOPE_CONV_MIXER):
                y = _conv_mixer(norm1(), lp)
        elif kind in _PLAIN:
            with jax.named_scope(_PLAIN[kind]):
                u = norm1()
                if cfg.qk_norm:
                    q, k, v = _normed_qkv(u, lp, rope, cfg)
                else:
                    q, k, v = _plain_qkv(u, lp, rope, cfg)
                o = local_attention(
                    q, k, v,
                    sm_scale=cfg.attention_multiplier or None,
                    mask=key_ranges(kind, T, cfg)).reshape(B, T, H * Dh)
                if cfg.attn_gate:
                    gate = jax.nn.sigmoid(
                        (u @ lp["wgate"]).astype(jnp.float32))
                    o = (o.astype(jnp.float32) * gate).astype(o.dtype)
                y = o @ lp["wo"]
        elif kind == "mamba":
            u = norm1()
            with jax.named_scope(SCOPE_SSM_MIXER):
                y, s = _mamba(u, lp, cfg)
            emitted = s if emits else None
        elif kind == "gmu":
            u = norm1()
            with jax.named_scope(SCOPE_GMU):
                y = (jax.nn.silu(u @ lp["in_proj"]) * memory) @ lp["out_proj"]
        else:
            u = norm1()
            with jax.named_scope(SCOPE_DIFF_ATTENTION):
                if kind == "cross":
                    q = (u @ lp["wq"]).reshape(B, T, H, Dh)
                    k, v = memory
                else:
                    q, k, v = jnp.split(u @ lp["wqkv"],
                                        (H * Dh, (H + Hkv) * Dh), axis=-1)
                    q = q.reshape(B, T, H, Dh)
                    k, v = (a.reshape(B, T, Hkv, Dh) for a in (k, v))
                    emitted = (k, v) if emits else None
                y = _diff_attention(q, k, v, lp, lam0, key_ranges(kind, T, cfg),
                                    cfg)
        h = join(h, y, cfg)
        with jax.named_scope(SCOPE_MLP):
            y, stats = _feed_forward(
                norm(h, lp["norm2_w"], lp.get("norm2_b"), cfg), lp, cfg,
                dense)
        return join(h, y, cfg), emitted, stats

    return f


def _runs(cfg):
    """[(stack, first of the stack, layers' published ids, emits)]: runs of
    equal layers in order, a layer's stack its kind or, a leading dense
    layer before routed ones, ``dense_<kind>`` (:func:`_stack`); an
    emitting layer is a run of its own.  A run is several layers (one
    scan) only where it is its kind's
    whole stack: a scan over a part of a stack takes a slice, whose
    gradient comes back padded to the whole stack and is added to the
    other parts' (three copies of every leaf's gradient, and an optimizer
    pass of its own: 9.3 GB of temporaries at nine Mamba-2 layers in runs
    of five and four, 3.5 GB layer by layer, where each weight gradient's
    product is fused with that weight's update)."""
    kinds = cfg.layer_kinds
    ids = cfg.layer_ids or tuple(range(len(kinds)))
    last_mamba = (max(i for i, k in enumerate(kinds) if k == "mamba"
                      and i < kinds.index("gmu")) if "gmu" in kinds else -1)
    last_full = (max(i for i, k in enumerate(kinds) if k == "full"
                     and i < kinds.index("cross")) if "cross" in kinds
                 else -1)
    runs, seen = [], {}
    for i in range(len(kinds)):
        stack = _stack(cfg, i)
        emits = i in (last_mamba, last_full)
        at = seen.get(stack, 0)
        seen[stack] = at + 1
        if (runs and runs[-1][0] == stack and not emits
                and not runs[-1][3]):
            runs[-1][2].append(ids[i])
        else:
            runs.append([stack, at, [ids[i]], emits])
    # (with routed experts every layer is a run of its own too: a layer
    # then holds 160 M parameters at the solar-open2-250b cell's sizes, and
    # a scan's stacked gradient and its stack of weights cast for the
    # products were 3.3 GB of the step's temporaries)
    shared = {stack for stack in seen
              if sum(r[0] == stack for r in runs) > 1 or cfg.n_experts > 0}
    return [run for stack, at, ids_, emits in runs
            for run in ([[stack, at + j, [i], emits]
                         for j, i in enumerate(ids_)] if stack in shared
                        else [[stack, at, ids_, emits]])]


def layer_stack(h, layers, cfg, policy=None, with_stats=False):
    """The trunk: ``h [B, T, D]`` through every layer.  ``policy``: the
    remat policy of ``cfg.remat`` (``llama.remat_policy``).
    ``with_stats`` also returns routed experts' ``[4]`` statistics summed
    over the layers (``moe.ROUTING_STATS``), None of a dense trunk."""
    check(cfg)
    m = kv = stats = None
    ropes = {}      # a kind's (cos, sin), made once for all its layers
    for kind, table in cfg.rope_tables:
        if kind in cfg.layer_kinds:
            if _metrics.ACTIVE:
                _m_ropes.inc(kind=kind, type=table.rope_type)
            turned = (cfg.qk_rope_head_dim if kind == "mla"
                      else cfg.head_dim)
            with jax.named_scope(_TABLED[kind]), jax.named_scope(SCOPE_ROPE):
                ropes[kind] = rope_table(table, turned, h.shape[1])
    stacks = _stacks(cfg)
    made = {}       # one function a (stack, emits): equal layers trace once
    for stack, first, ids, emits in _runs(cfg):
        kind, dense, _ = stacks[stack]
        n = len(ids)
        if _metrics.ACTIVE:
            _m_kinds.inc(n, kind=kind)
        if (stack, emits) not in made:
            f = _layer(kind, emits, cfg, dense)
            made[stack, emits] = (jax.checkpoint(f, policy=policy)
                                  if cfg.remat else f)
        f = made[stack, emits]
        memory = m if kind == "gmu" else kv if kind == "cross" else None
        lam0 = jnp.asarray([lambda_init(i) for i in ids], jnp.float32)
        lps = jax.tree_util.tree_map(lambda w: w[first:first + n],
                                     layers[stack])
        if n == 1:
            h, emitted, new = f(h, jax.tree_util.tree_map(lambda w: w[0], lps),
                                lam0[0], memory, ropes.get(kind))
            if kind == "mamba" and emits:
                m = emitted
            elif emits:
                kv = emitted
            if new is not None:
                stats = new if stats is None else stats + new
        else:       # never a routed layer: ``_runs`` gives each its own run
            h, _ = lax.scan(
                lambda h_, at: (f(h_, at[0], at[1], memory,
                                  ropes.get(kind))[0], None),
                h, (lps, lam0))
    return (h, stats) if with_stats else h
