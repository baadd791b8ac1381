"""Plain float32 BERT sequence classifier for the benchmark's check.

Written from the google-research/bert description: summed word, position
and type embeddings under a LayerNorm; post-LN encoder layers (attention
with biases, softmax(QK^T / sqrt(d_head)), output projection, residual,
LayerNorm; GELU feed-forward in its tanh form as the published code has
it, residual, LayerNorm); tanh pooler over [CLS]; a linear classifier;
mean cross-entropy.  Straightforward jax.numpy, no kernels, nothing
imported from the program.  Departures are listed in config.json.

Each layer is wrapped in jax.checkpoint so that a float32 batch fits a
16 GB chip; that changes no number.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The check's limits, each from the readings beside it (my chip runs,
# PR 23, per-chip batch 32: 12 seeds sound, 10 of them with every leaf
# kept, and 5 in the control; PERF.md section 2 has the whole table).
# The control is this file with fp8 (e4m3) operands in every matrix
# product.  bf16 compute with fp32 parameters, LayerNorm, softmax and
# reduction passes; the control fails ``grad_share_gap``.
LIMITS = {
    # sound runs' largest 0.0052, the control's smallest 0.0130: a ratio
    # of 2.5, the widest any number read here reached (PERF.md says why)
    "grad_share_gap": 0.0085,
    # the four below do not tell fp8 from bf16 on this model (Adam's first
    # steps move every weight by the learning rate whatever its gradient,
    # so the second and third losses swing with rounding alone); they are
    # held at about three times the sound runs' largest, against a part of
    # the batch left out, a gradient of the wrong scale and a step that
    # leaves its state.
    "loss_gap": 0.016,              # sound largest 0.0053
    "grad_norm_gap": 0.05,          # sound largest 0.0158
    "grad_norm_mid_gap": 0.03,      # sound largest 0.0103
    "update_norm_gap": 0.075,       # sound largest 0.025
}

_LAYER = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo",
          "attn_ln.w", "attn_ln.b", "w_in", "b_in", "w_out", "b_out",
          "mlp_ln.w", "mlp_ln.b")


def weight_shapes(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {
        "embed.word": (cfg["vocab_size"], d),
        "embed.pos": (cfg["max_position_embeddings"], d),
        "embed.type": (cfg["type_vocab_size"], d),
        "embed.ln.w": (d,), "embed.ln.b": (d,),
    }
    per_layer = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
                 "w_in": (d, f), "w_out": (f, d), "b_in": (f,)}
    for i in range(cfg["num_hidden_layers"]):
        for n in _LAYER:
            shapes[f"l{i}.{n}"] = per_layer.get(n, (d,))
    shapes.update({"pooler.w": (d, d), "pooler.b": (d,),
                   "cls.w": (d, cfg["num_labels"]),
                   "cls.b": (cfg["num_labels"],)})
    return shapes


def make_weights(cfg, key):
    """Flat dict of float32 weights, as the published code initialises."""
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        if len(shape) == 2:
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32)
                         * cfg["initializer_range"])
        elif name.endswith("ln.w"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jnp.zeros(shape, jnp.float32)
    return out


def make_samples(cfg, key, n):
    """n token rows and labels: examples/bert_finetune.py's make_dataset
    (each label biases a disjoint token range), seeded from ``key``."""
    seed = int(np.asarray(jax.random.key_data(key)).astype(np.uint64).sum()
               % (2 ** 32))
    rng = np.random.RandomState(seed)
    vocab, seq, num_labels = cfg["vocab_size"], cfg["seq_len"], cfg["num_labels"]
    labels = rng.randint(0, num_labels, n).astype(np.int32)
    span = (vocab - 10) // num_labels
    base = rng.randint(0, vocab - 1, (n, seq))
    biased = 10 + labels[:, None] * span + rng.randint(0, span, (n, seq))
    tokens = np.where(rng.rand(n, seq) < 0.3, biased, base).astype(np.int32)
    tokens[:, 0] = 1  # [CLS]
    return tokens, labels


def _dot(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=lax.Precision.HIGHEST)


def _layernorm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w + b


def _layer(lw, x, cfg, quant):
    b, t, d = x.shape
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]

    def heads(name):
        y = _dot(x, lw["w" + name], quant) + lw["b" + name]
        return y.reshape(b, t, h, dh).transpose(0, 2, 1, 3)

    q, k, v = heads("q"), heads("k"), heads("v")
    s = _dot(q, k.transpose(0, 1, 3, 2), quant) * dh ** -0.5
    o = _dot(jax.nn.softmax(s, axis=-1), v, quant)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
    a = _dot(o, lw["wo"], quant) + lw["bo"]
    x = _layernorm(x + a, lw["attn_ln.w"], lw["attn_ln.b"],
                   cfg["layer_norm_eps"])
    m = jax.nn.gelu(_dot(x, lw["w_in"], quant) + lw["b_in"], approximate=True)
    m = _dot(m, lw["w_out"], quant) + lw["b_out"]
    return _layernorm(x + m, lw["mlp_ln.w"], lw["mlp_ln.b"],
                      cfg["layer_norm_eps"])


def loss(cfg, w, batch, quant=lambda a: a):
    """Mean cross-entropy of the batch's classification logits."""
    tokens, labels = batch
    t = tokens.shape[1]
    x = (w["embed.word"][tokens] + w["embed.pos"][jnp.arange(t)][None]
         + w["embed.type"][0][None, None])
    x = _layernorm(x, w["embed.ln.w"], w["embed.ln.b"], cfg["layer_norm_eps"])
    for i in range(cfg["num_hidden_layers"]):
        lw = {n: w[f"l{i}.{n}"] for n in _LAYER}
        x = jax.checkpoint(lambda lw_, x_: _layer(lw_, x_, cfg, quant))(lw, x)
    pooled = jnp.tanh(_dot(x[:, 0, :], w["pooler.w"], quant) + w["pooler.b"])
    logits = _dot(pooled, w["cls.w"], quant) + w["cls.b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
