"""BERT-style bidirectional encoder (BASELINE config 3: BERT fine-tune).

The reference's BERT capability is an *example* wrapping an external
model (SURVEY.md §2.3 — its examples drive torchvision/transformers
models through Horovod DP); this module provides the equivalent
capability natively, TPU-first, in the same style as
:mod:`horovod_tpu.models.llama`:

  * bf16 activations / fp32 master params; fp32 LayerNorm + softmax.
  * Layers stacked on a leading dim, driven by ``lax.scan`` — one
    compiled block body regardless of depth.
  * Parallelism via the same ``ParallelSpec`` mesh-axis hooks: megatron
    column/row tensor parallel (one psum per attention + one per MLP),
    sequence parallel through non-causal ring attention, data parallel.
  * Unmasked path goes through ``local_attention`` (fused Pallas flash
    kernel on TPU); padded batches take a dense masked path (the flash
    kernel has no mask operand — fine-tune batches are short).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..optim import overlap as _overlap
from ..parallel.ring_attention import local_attention, ring_attention
from ..scopes import (SCOPE_ATTENTION, SCOPE_EMBED, SCOPE_FORWARD, SCOPE_HEAD,
                      SCOPE_MLP, SCOPE_OPTIMIZER, SCOPE_REDUCE)
from ..tracing import TracedStep
from .llama import ParallelSpec, remat_policy


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    norm_eps: float = 1e-12
    num_labels: int = 2           # fine-tune classification head
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def bert_base(num_labels: int = 2) -> BertConfig:
    """BERT-Base geometry (the BASELINE config-3 target)."""
    return BertConfig(num_labels=num_labels)


def bert_large(num_labels: int = 2) -> BertConfig:
    return BertConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                      num_labels=num_labels)


def tiny(vocab: int = 256, seq: int = 64, num_labels: int = 2) -> BertConfig:
    """Test-scale config: same code paths, toy sizes."""
    return BertConfig(vocab_size=vocab, d_model=64, n_layers=2, n_heads=4,
                      d_ff=128, max_seq_len=seq, num_labels=num_labels,
                      dtype=jnp.float32)


def init_params(cfg: BertConfig, key, tp: int = 1) -> Dict:
    """Initialize parameters; with ``tp > 1`` shard the result with
    :func:`param_specs` (weights stay full here, megatron layout)."""
    k = jax.random.split(key, 12)
    D, H, Dh, F, L, V = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                         cfg.n_layers, cfg.vocab_size)
    if H % tp or F % tp:
        raise ValueError(f"heads({H})/d_ff({F}) must divide tp={tp}")

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, cfg.param_dtype)
                * (fan_in ** -0.5))

    layers = {
        "attn_norm_w": jnp.ones((L, D), cfg.param_dtype),
        "attn_norm_b": jnp.zeros((L, D), cfg.param_dtype),
        "wq": norm(k[1], (L, D, H * Dh), D),
        "bq": jnp.zeros((L, H * Dh), cfg.param_dtype),
        "wk": norm(k[2], (L, D, H * Dh), D),
        "bk": jnp.zeros((L, H * Dh), cfg.param_dtype),
        "wv": norm(k[3], (L, D, H * Dh), D),
        "bv": jnp.zeros((L, H * Dh), cfg.param_dtype),
        "wo": norm(k[4], (L, H * Dh, D), H * Dh),
        "bo": jnp.zeros((L, D), cfg.param_dtype),
        "mlp_norm_w": jnp.ones((L, D), cfg.param_dtype),
        "mlp_norm_b": jnp.zeros((L, D), cfg.param_dtype),
        "w_in": norm(k[5], (L, D, F), D),
        "b_in": jnp.zeros((L, F), cfg.param_dtype),
        "w_out": norm(k[6], (L, F, D), F),
        "b_out": jnp.zeros((L, D), cfg.param_dtype),
    }
    return {
        "word_embed": norm(k[0], (V, D), D),
        "pos_embed": norm(k[7], (cfg.max_seq_len, D), D),
        "type_embed": norm(k[8], (cfg.type_vocab_size, D), D),
        "embed_norm_w": jnp.ones((D,), cfg.param_dtype),
        "embed_norm_b": jnp.zeros((D,), cfg.param_dtype),
        "layers": layers,
        "pooler_w": norm(k[9], (D, D), D),
        "pooler_b": jnp.zeros((D,), cfg.param_dtype),
        "cls_w": norm(k[10], (D, cfg.num_labels), D),
        "cls_b": jnp.zeros((cfg.num_labels,), cfg.param_dtype),
    }


def param_specs(par: ParallelSpec, cfg: Optional[BertConfig] = None):
    """PartitionSpecs (megatron layout): column-parallel qkv/w_in shard
    the output dim over tp, row-parallel wo/w_out the input dim; biases
    of column-parallel layers shard with their outputs."""
    from jax.sharding import PartitionSpec as P
    tp = par.tp_axis
    return {
        "word_embed": P(),
        "pos_embed": P(),
        "type_embed": P(),
        "embed_norm_w": P(),
        "embed_norm_b": P(),
        "layers": {
            "attn_norm_w": P(None, None), "attn_norm_b": P(None, None),
            "wq": P(None, None, tp), "bq": P(None, tp),
            "wk": P(None, None, tp), "bk": P(None, tp),
            "wv": P(None, None, tp), "bv": P(None, tp),
            "wo": P(None, tp, None), "bo": P(None, None),
            "mlp_norm_w": P(None, None), "mlp_norm_b": P(None, None),
            "w_in": P(None, None, tp), "b_in": P(None, tp),
            "w_out": P(None, tp, None), "b_out": P(None, None),
        },
        "pooler_w": P(),
        "pooler_b": P(),
        "cls_w": P(),
        "cls_b": P(),
    }


def _layernorm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _dense_masked_attention(q, k, v, mask, scale):
    """Dense path for padded batches; mask: [B, Tk] (1 = attend)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, None, :].astype(bool), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _attention(x, lp, cfg: BertConfig, par: ParallelSpec, mask):
    B, Tl, D = x.shape
    Dh = cfg.head_dim
    Hl = lp["wq"].shape[-1] // Dh  # tp-local heads
    q = (x @ lp["wq"].astype(x.dtype)
         + lp["bq"].astype(x.dtype)).reshape(B, Tl, Hl, Dh)
    k = (x @ lp["wk"].astype(x.dtype)
         + lp["bk"].astype(x.dtype)).reshape(B, Tl, Hl, Dh)
    v = (x @ lp["wv"].astype(x.dtype)
         + lp["bv"].astype(x.dtype)).reshape(B, Tl, Hl, Dh)
    scale = Dh ** -0.5
    if mask is not None:
        o = _dense_masked_attention(q, k, v, mask, scale)
    elif par.sp_axis is not None:
        o = ring_attention(q, k, v, par.sp_axis, causal=False,
                           sm_scale=scale)
    else:
        o = local_attention(q, k, v, causal=False, sm_scale=scale)
    o = o.reshape(B, Tl, Hl * Dh) @ lp["wo"].astype(x.dtype)
    if par.tp_axis is not None:
        o = lax.psum(o, par.tp_axis)  # row-parallel reduction
    return o + lp["bo"].astype(x.dtype)


def _mlp(x, lp, par: ParallelSpec):
    h = jax.nn.gelu(x @ lp["w_in"].astype(x.dtype)
                    + lp["b_in"].astype(x.dtype), approximate=True)
    out = h @ lp["w_out"].astype(x.dtype)
    if par.tp_axis is not None:
        out = lax.psum(out, par.tp_axis)
    return out + lp["b_out"].astype(x.dtype)


def block(x, lp, cfg: BertConfig, par: ParallelSpec, mask):
    """One post-LN encoder block (BERT layout: residual then LayerNorm);
    each sublayer lies with its residual's LayerNorm under its scope."""
    with jax.named_scope(SCOPE_ATTENTION):
        a = _attention(x, lp, cfg, par, mask)
        x = _layernorm(x + a, lp["attn_norm_w"], lp["attn_norm_b"],
                       cfg.norm_eps)
    with jax.named_scope(SCOPE_MLP):
        m = _mlp(x, lp, par)
        return _layernorm(x + m, lp["mlp_norm_w"], lp["mlp_norm_b"],
                          cfg.norm_eps)


def encode(params, tokens, cfg: BertConfig, par: ParallelSpec,
           token_types=None, mask=None):
    """Token ids ``[B, T]`` → hidden states ``[B, T, D]``.

    Call inside ``shard_map`` over the parallel mesh (batch over dp,
    sequence over sp when unmasked).  ``mask``: optional ``[B, T]`` of
    0/1 attention mask for padded batches (forces the dense path and is
    incompatible with sp sharding).
    """
    if mask is not None and par.sp_axis is not None:
        raise ValueError("attention masks require unsharded sequence "
                         "(pad-free batches for the sp path)")
    B, Tl = tokens.shape
    sp_idx = (lax.axis_index(par.sp_axis)
              if par.sp_axis is not None else 0)
    positions = jnp.arange(Tl, dtype=jnp.int32)[None, :] + sp_idx * Tl
    with jax.named_scope(SCOPE_EMBED):
        h = params["word_embed"].astype(cfg.dtype)[tokens]
        h = h + params["pos_embed"].astype(cfg.dtype)[positions]
        tt = (token_types if token_types is not None
              else jnp.zeros_like(tokens))
        h = h + params["type_embed"].astype(cfg.dtype)[tt]
        h = _layernorm(h, params["embed_norm_w"], params["embed_norm_b"],
                       cfg.norm_eps)

    layers = jax.tree_util.tree_map(
        lambda w: w.astype(cfg.dtype) if w.dtype != cfg.dtype else w,
        params["layers"])
    body = block
    if cfg.remat:
        # dots, and the packed flash kernel's named output and row
        # statistics: the kernel is not rerun in the backward pass
        body = jax.checkpoint(body, static_argnums=(2, 3),
                              policy=remat_policy("dots"))

    def scan_body(h, lp):
        # overlapped dispatch tap (identity unless an overlapped_backprop
        # context is armed): this layer's gradient buckets fire inside
        # the backward scan, overlapped with the remaining backprop
        lp = _overlap.grad_tap(lp)
        return body(h, lp, cfg, par, mask), None

    h, _ = lax.scan(scan_body, h, layers)
    return h


def classify(params, tokens, cfg: BertConfig, par: ParallelSpec,
             token_types=None, mask=None):
    """Sequence classification logits ``[B, num_labels]`` (pooled [CLS])."""
    h = encode(params, tokens, cfg, par, token_types, mask)
    with jax.named_scope(SCOPE_HEAD):
        cls = h[:, 0, :]  # [CLS] position
        pooled = jnp.tanh(cls @ params["pooler_w"].astype(cls.dtype)
                          + params["pooler_b"].astype(cls.dtype))
        return (pooled @ params["cls_w"].astype(pooled.dtype)
                + params["cls_b"].astype(pooled.dtype)).astype(jnp.float32)


def loss_fn(params, tokens, labels, cfg: BertConfig, par: ParallelSpec,
            token_types=None, mask=None):
    """Mean classification cross-entropy over the local batch (caller
    pmeans over dp)."""
    # overlapped dispatch: tap the non-scanned leaves (embeddings,
    # pooler, classification head) as one group; the scanned stack is
    # tapped per layer inside encode()'s scan body.  No-op outside an
    # overlapped_backprop context.
    params = _overlap.tap_root(params)
    logits = classify(params, tokens, cfg, par, token_types, mask)
    with jax.named_scope(SCOPE_HEAD):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def make_dp_finetune_step(cfg: BertConfig, mesh, axis: str, optimizer,
                          reduce_grads: bool = False):
    """Build the jitted data-parallel fine-tune step shared by the
    example, the bench entry, and the tests: per-shard value_and_grad,
    optimizer update, pmean'd loss.

    ``reduce_grads=True`` pmeans gradients explicitly (plain optax
    optimizers); leave False when ``optimizer`` already reduces across
    ``axis`` (``hvd.DistributedOptimizer``'s fused in-jit reduction).
    """
    import optax
    from jax.sharding import PartitionSpec as P
    par = ParallelSpec(dp_axis=axis)

    def forward(params, tokens, labels):
        with jax.named_scope(SCOPE_FORWARD):
            return loss_fn(params, tokens, labels, cfg, par)

    @jax.jit
    def step(params, opt_state, tokens, labels):
        def shard(params, opt_state, tokens, labels):
            loss, grads = jax.value_and_grad(forward)(params, tokens, labels)
            if reduce_grads:
                with jax.named_scope(SCOPE_REDUCE):
                    grads = jax.tree_util.tree_map(
                        lambda g: lax.pmean(g, axis), grads)
            with jax.named_scope(SCOPE_OPTIMIZER):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
            with jax.named_scope(SCOPE_REDUCE):
                loss = lax.pmean(loss, axis)
            return params, opt_state, loss
        return jax.shard_map(
            shard, mesh=mesh, in_specs=(P(), P(), P(axis), P(axis)),
            out_specs=(P(), P(), P()), check_vma=True)(
                params, opt_state, tokens, labels)

    return TracedStep(step, 2)


def count_params(cfg: BertConfig) -> int:
    D, H, Dh, F, L, V = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                         cfg.n_layers, cfg.vocab_size)
    per_layer = 4 * (D * H * Dh + H * Dh) + 2 * D * F + F + D + 4 * D
    emb = V * D + cfg.max_seq_len * D + cfg.type_vocab_size * D + 2 * D
    head = D * D + D + D * cfg.num_labels + cfg.num_labels
    return emb + L * per_layer + head
