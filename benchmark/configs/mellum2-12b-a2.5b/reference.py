"""Plain float32 reference of the configuration's layers and objective,
for the benchmark's check.  Straightforward jax.numpy, no kernels,
nothing imported from the program.  Departures from the published model
are listed in config.json under ``assumed``.

The model (``mellum``): 28 published layers, ``layer_types`` three
``sliding_attention`` and one ``full_attention`` seven times over, every
feed-forward sparse.  Every layer is

    a = h + Attn(RMSNorm_1(h));   h' = a + FF(RMSNorm_2(a))

RMSNorm with a weight (eps 1e-6), no bias in a product, no q/k norm; the
logits are ``RMSNorm(h_L) W_head^T``, the head untied.

``Attn``, with ``u = RMSNorm_1(h)``: ``q = u Wq`` in 32 heads of 128, ``k``
and ``v`` in 4; ``q`` and ``k`` rotated (rotate-half: ``[x1 cos - x2 sin ;
x1 sin + x2 cos]``, ``x1``, ``x2`` a head's halves) by the layer type's
table at the row's positions 0 .. T - 1; ``o = softmax(q k^T / sqrt(128) +
M) v``, query head ``h`` on key/value head ``h // 8``; ``Attn = o Wo``.
``M``: a ``full_attention`` row ``i`` sees keys ``j <= i``; a
``sliding_attention`` row those with ``i - j < sliding_window`` besides:
1,024 keys with its own.

The tables (``rope_parameters``, one a layer type; angles ``p *
inv_freq[j]`` in float32, ``j = 0 .. 63``), :func:`inv_freq`:
``sliding_attention`` plain, ``e[j] = theta ** (-j / 64)``, cos and sin as
they are; ``full_attention`` YaRN (arXiv 2309.00071 as ``transformers``'
``_compute_yarn_parameters`` has it, ``truncate`` at its default): ``c(r) =
128 ln(L / (2 pi r)) / (2 ln theta)`` with ``L`` the original length; ``low
= floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, clipped to ``[0,
127]``; ``ramp[j] = clip((j - low) / (high - low), 0, 1)``; ``inv_freq[j] =
e[j] / factor * ramp[j] + e[j] (1 - ramp[j])``; cos and sin both times
``attention_factor``.

``FF(z)``: ``s = softmax(z W_r)`` over all ``router_outputs``; the
``num_experts_per_tok`` largest are chosen; ``w = s_chosen /
sum(s_chosen)``; the chosen experts held here, ``E_e(z) = D_e (silu(G_e z)
* U_e z)``, weighted.  No shared expert, no auxiliary loss.  What the
experts this chip does not hold would add is left out.

The cut keeps the published layers ``kept_layers``, the chip's share of
the experts and of the vocabulary.  The objective is next-token
cross-entropy over the ids held, averaged over every position of every
row.

Attention is computed in blocks of query rows against every key under a
dense mask, the experts one after another, the scored logits in blocks,
and each layer, block and expert is under jax.checkpoint, so that the
float32 activations of 16,384 positions fit beside the float32 weights,
gradient and optimizer state; that changes no number.

``loss(..., control=)`` is for the builder's two controls of this
configuration's own, each of which has to fail a limit: ``"no_yarn"``
rotates the full layers by the sliding layers' table (no interpolation, no
``attention_factor``); ``"no_window"`` runs the sliding layers causal.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The check's limits (my chip runs, PR 46, at the timed sizes; PERF.md
# section 2 has the table).  The lower reading is the largest over 17 sound
# runs, each on a seed of its own; the upper ones the smallest over the
# fp8 control on 3 seeds, this file with fp8 (e4m3) operands in every matrix
# product (benchmark/readings.py's), and this configuration's own two
# controls on one seed (``loss(control=)`` below).  bf16 compute with fp32
# parameters, router, norms, rotary tables and softmax statistics.  The fp8
# control fails the first two on every seed; ``no_yarn`` and ``no_window``
# fail the second 280-fold and 175-fold, and the fourth.
LIMITS = {
    # the median leaf of the first gradient: sound 2.8e-5 to 8.2e-5 (16 of
    # the 17 under 4.8e-5), the fp8 control 1.12e-3 to 1.27e-3, a ratio of
    # 13.7; the limit 3.7 times over the one and 3.7 under the other
    "grad_norm_mid_gap": 3.0e-4,
    # the worst leaf of the first gradient, a router's on 16 runs of 17
    # (its gradient moves with every top-8 choice that bf16 activations
    # flip against float32): sound 1.2e-4 to 5.9e-4; the fp8 control 4.4e-3
    # to 4.8e-3, its worst leaf the full layer's wqkv; the full layer under
    # the sliding table 0.56 (l3.wqkv: scores 1.63 times as flat), the
    # sliding layers without their window 0.35 (l2.wqkv).  3.4 times over
    # the sound runs' largest, 2.2 under fp8's smallest
    "grad_norm_gap": 2.0e-3,
    # the losses do not tell fp8 from bf16 by three: sound 1.6e-6 to
    # 1.0e-5, the control 2.3e-5 to 3.5e-5 (2.3 times the sound largest);
    # the two controls of its own read 5.7e-6 and 1.1e-5: at seeded
    # weights the loss is ln(vocabulary) whatever attention sees.  By the
    # contract's rule for such a number it takes the limit of the accepted
    # cells whose losses move for the same reason (sdar-30b-a3b's and
    # solar-open2-250b's): 44 times over the reading here
    "loss_gap": 4.5e-4,
    # the worst leaf of the parameters' change, which Adam moves by sign:
    # sound 3.3e-5 to 8.1e-5, the fp8 control 1.3e-4 to 1.4e-4: precision
    # hardly moves it.  By the contract's rule it lies between the reading
    # and 1, which a state left unchanged reads, with the more room above
    # the reading: 99 times over it, 125 under 1; the configuration's own
    # controls read 0.015 (no_yarn) and 0.0092 (no_window), both over it
    "update_norm_gap": 8.0e-3,
}

_ROWS = 128          # query rows at a time (32 heads x 16,384 keys x 4 B a row)
_SCORED = 256        # scored rows at a time
_RESIDUAL_OUT = ("wo", "we_down")    # what writes into the stream
LEAVES = ("norm1_w", "norm2_w", "wqkv", "wo", "router", "we_gate", "we_up",
          "we_down")
CONTROLS = (None, "no_yarn", "no_window")


def kept_types(cfg):
    return [cfg["layer_types"][i] for i in cfg["kept_layers"]]


def weight_shapes(cfg):
    d, f, dh = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held = cfg["num_experts"]
    leaf = {"norm1_w": (d,), "norm2_w": (d,), "wqkv": (d, (h + 2 * hkv) * dh),
            "wo": (h * dh, d), "router": (d, cfg["router_outputs"]),
            "we_gate": (held, d, f), "we_up": (held, d, f),
            "we_down": (held, f, d)}
    shapes = {"embed": (cfg["vocab_size"], d)}
    for i in range(len(cfg["kept_layers"])):
        for name in LEAVES:
            shapes[f"l{i}.{name}"] = leaf[name]
    shapes.update({"final_norm_w": (d,), "head": (cfg["vocab_size"], d)})
    return shapes


def make_weights(cfg, key):
    """Flat dict of float32 weights from the key, as config.json's
    ``assumed`` says: matrices normal(0, initializer_range), embedding rows
    normal(0, embedding_range), what writes into the residual stream
    normal(0, residual_out_range), norms at 1."""
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        leaf = name.split(".")[-1]
        if len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            std = cfg["embedding_range" if leaf == "embed"
                      else "residual_out_range" if leaf in _RESIDUAL_OUT
                      else "initializer_range"]
            out[name] = jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * std
    return out


def make_samples(cfg, key, n):
    """n full rows: (tokens [n, T], targets [n, T]), ids uniform over the
    slice held, each target the next token.  The data pipeline's work, on
    the host."""
    seed = int(np.asarray(jax.random.key_data(key)).astype(np.uint64).sum()
               % (2 ** 32))
    ids = np.random.RandomState(seed).randint(
        0, cfg["vocab_size"], (n, cfg["seq_len"] + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _dot(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_range(p, dim):
    """``(low, high)``: the dimensions between which YaRN's ramp rises."""
    def c(r):
        return (dim * math.log(p["original_max_position_embeddings"]
                               / (r * 2 * math.pi))
                / (2 * math.log(p["rope_theta"])))
    return (max(math.floor(c(p["beta_fast"])), 0),
            min(math.ceil(c(p["beta_slow"])), dim - 1))


def inv_freq(p, dim):
    """One layer type's ``(inverse frequencies [dim / 2] float32, factor on
    cos and sin)`` from its ``rope_parameters``: constants, worked out in
    float64 on the host and rounded once."""
    j = np.arange(dim // 2, dtype=np.float64)
    e = float(p["rope_theta"]) ** (-j / (dim // 2))
    if p["rope_type"] == "default":
        return e.astype(np.float32), 1.0
    low, high = yarn_range(p, dim)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    factor = p.get("attention_factor") or 0.1 * math.log(p["factor"]) + 1.0
    return (e / p["factor"] * ramp + e * (1.0 - ramp)).astype(np.float32), factor


def rope(x, p):
    """x ``[B, T, H, Dh]`` rotated by the table of ``p`` at positions 0 ..
    T - 1, rotate-half."""
    half = x.shape[-1] // 2
    freqs, factor = inv_freq(p, x.shape[-1])
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = (jnp.cos(angles) * jnp.float32(factor))[None, :, None]
    sin = (jnp.sin(angles) * jnp.float32(factor))[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _by_rows(fn, rows, *arrays):
    """``fn`` over blocks of ``rows`` positions (axis 1) of the arrays,
    each block under jax.checkpoint, side by side again."""
    B, T = arrays[0].shape[:2]
    rows = min(rows, T)
    split = lambda a: jnp.moveaxis(
        a.reshape(B, T // rows, rows, *a.shape[2:]), 1, 0)
    out = lax.map(lambda args: jax.checkpoint(fn)(*args),
                  tuple(map(split, arrays)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, *out.shape[3:])


def attention(u, lw, kind, cfg, quant=lambda a: a, control=None):
    """One layer type's attention of the normed stream ``u [B, T, d]``,
    ``_ROWS`` query rows at a time against every key under the dense
    mask."""
    B, T, _ = u.shape
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    table = ("sliding_attention" if control == "no_yarn" else kind)
    slides = kind == "sliding_attention" and control != "no_window"
    p = cfg["rope_parameters"][table]
    q, k, v = jnp.split(_dot(u, lw["wqkv"], quant),
                        (h * dh, (h + hkv) * dh), axis=-1)
    q = rope(q.reshape(B, T, h, dh), p)
    k = quant(rope(k.reshape(B, T, hkv, dh), p))
    v = quant(v.reshape(B, T, hkv, dh))
    rows = min(_ROWS, T)

    def block(qb, at):      # [B, rows, Hkv, g, Dh]; each row's position
        i, j = at[0][:, None], jnp.arange(T)[None, :]
        live = j <= i
        if slides:
            live = live & (i - j < cfg["sliding_window"])
        s = jnp.einsum("brhgd,bkhd->bhgrk", quant(qb), k,
                       precision=lax.Precision.HIGHEST) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgrk,bkhd->brhgd", quant(pr), v,
                          precision=lax.Precision.HIGHEST)

    at = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    out = _by_rows(block, rows, q.reshape(B, T, hkv, h // hkv, dh), at)
    return _dot(out.reshape(B, T, h * dh), lw["wo"], quant)


def route(z, lw, cfg, quant=lambda a: a):
    """(chosen expert ids [B, T, top], their weights): softmax over every
    router output, the largest chosen, weighed over the chosen."""
    s = jax.nn.softmax(_dot(z, lw["router"], quant), axis=-1)
    top_s, top_i = lax.top_k(s, cfg["num_experts_per_tok"])
    return top_i, top_s / top_s.sum(-1, keepdims=True)


def feed_forward(z, lw, cfg, quant=lambda a: a):
    """The held experts' part of the routed layer."""
    top_i, top_w = route(z, lw, cfg, quant)

    def expert(held):                    # one held expert's part
        e, gate, up, down = held
        w_e = jnp.where(top_i == cfg["experts_first"] + e, top_w, 0.0).sum(-1)
        hidden = jax.nn.silu(_dot(z, gate, quant)) * _dot(z, up, quant)
        return w_e[..., None] * _dot(hidden, down, quant)

    y, _ = lax.scan(lambda y_, held: (y_ + jax.checkpoint(expert)(held), None),
                    jnp.zeros_like(z),
                    (jnp.arange(cfg["num_experts"]), lw["we_gate"],
                     lw["we_up"], lw["we_down"]))
    return y


def _layer(lw, x, kind, cfg, quant, control):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["norm1_w"], eps), lw, kind, cfg, quant,
                      control)
    return x + feed_forward(rms_norm(x, lw["norm2_w"], eps), lw, cfg, quant)


def hidden(cfg, w, tokens, quant=lambda a: a, control=None):
    """Token ids -> the final RMSNorm's output [B, T, d]."""
    if control not in CONTROLS:
        raise ValueError(f"control must be one of {CONTROLS}")
    x = w["embed"][tokens]
    # (a loop, not a scan over stacked leaves: the kinds differ, and under
    # the check's donated update the compiler would copy a stack)
    for n, kind in enumerate(kept_types(cfg)):
        lw = {name: w[f"l{n}.{name}"] for name in LEAVES}
        x = jax.checkpoint(
            lambda lw_, x_, kind=kind: _layer(lw_, x_, kind, cfg, quant,
                                              control))(lw, x)
    return rms_norm(x, w["final_norm_w"], cfg["rms_norm_eps"])


def loss(cfg, w, batch, quant=lambda a: a, control=None):
    """Next-token cross-entropy of the batch over the ids held."""
    tokens, targets = batch
    x = hidden(cfg, w, tokens, quant, control)

    def scored(xb, tb):                  # [B, rows, d], [B, rows]
        logp = jax.nn.log_softmax(_dot(xb, w["head"].T, quant), axis=-1)
        return -jnp.take_along_axis(logp, tb[..., None], -1)

    return _by_rows(scored, _SCORED, x, targets).mean()
