"""Plain float32 reference of the configuration's layers and objective,
for the benchmark's check.  Straightforward jax.numpy, no kernels,
nothing imported from the program.  Departures from the published model
are listed in config.json under ``assumed``.

The model (``lfm2_moe``): 24 published layers, ``layer_types`` 18 ``conv``
and 6 ``full_attention``; the first ``num_dense_layers`` have a dense
feed-forward, the rest routed experts.  Every layer is

    a = h + Op(RMSNorm_1(h));   h' = a + FF(RMSNorm_2(a))

RMSNorm with a weight (eps 1e-5; ``operator_norm`` and ``ffn_norm`` in
``transformers``, ``norm1_w`` and ``norm2_w`` here), no bias anywhere; the
logits are ``RMSNorm(h_L) E^T`` with ``E`` the embedding table
(``embedding_norm`` in ``transformers``, ``final_norm_w`` here; the head
is tied).

``Op`` of a ``conv`` layer (``Lfm2MoeShortConv``), ``u = RMSNorm_1(h)``:
``[B ; C ; x] = u W_in``, three slices of ``hidden_size`` in that order;
``g = B * x``; ``c = Conv1d(groups = hidden_size, kernel conv_L_cache,
padding conv_L_cache - 1, no bias)(g)`` cut to the row's length, so ``c_t =
sum_j w[j] g_(t - 2 + j)`` a channel with ``g`` zero before the row's
first position; ``Op = (C * c) W_out``.  No activation.  ``transformers``
keeps the taps as ``conv.weight [hidden, 1, 3]``; here ``conv_w [3,
hidden]``, the same numbers turned.

``Op`` of a ``full_attention`` layer (``Lfm2MoeAttention``): ``q = u Wq``
in 32 heads of 64, ``k = u Wk`` and ``v = u Wv`` in 8; ``q`` and ``k``
RMS-normed over a head's 64 columns (``q_layernorm``, ``k_layernorm``:
``q_norm``, ``k_norm`` here, one weight for every head), then rotated
(rotate-half: ``[x1 cos - x2 sin ; x1 sin + x2 cos]``, angles ``p *
theta ** (-j / 32)`` in float32, positions 0 .. T - 1 a row); ``o =
softmax(q k^T / 8 + causal) v``, query head ``h`` on key/value head ``h //
4``; ``Op = o Wo``.

``FF(z)`` of a dense layer (``Lfm2MoeMLP``): ``W2 (silu(W1 z) * W3 z)``.
Of a routed one (``Lfm2MoeSparseMoeBlock``): ``s = sigmoid(z W_r)`` over all
``router_outputs``; the ``num_experts_per_tok`` largest of ``s + b`` are
chosen (``b`` the selection bias, ``expert_bias`` in ``transformers``,
``router_bias`` here: it moves the choice and nothing else, and has no
gradient); ``w = s_chosen / (sum(s_chosen) + 1e-6)``, times
``routed_scaling_factor``; the chosen experts held here, ``E_e(z) = D_e
(silu(G_e z) * U_e z)``, weighted.  No shared expert, no auxiliary loss.
What the experts this chip does not hold would add is left out.

The cut keeps the published layers ``kept_layers``, the chip's share of
the experts and of the vocabulary.  The objective is next-token
cross-entropy over the ids held, averaged over every position of every
row.

Attention is computed in blocks of query rows against every key under a
dense mask, the experts one after another, the scored logits in blocks,
and each layer, block and expert is under jax.checkpoint, so that the
float32 activations of two rows of 8,192 positions fit beside the float32
weights, gradient and optimizer state; that changes no number.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The check's limits (my chip runs, PR 53, at the timed sizes; PERF.md
# section 2 has the table).  The lower reading is the largest over the
# sound runs, each on a seed of its own (6 when the limits were set, 16 by
# the end, none over a limit); the upper one the smallest over the fp8
# control (2 seeds then, 5 by the end), this file with fp8 (e4m3) operands
# in every matrix product (benchmark/readings.py's).  bf16 compute with
# fp32 parameters, router, norms, rotation, the convolution's chain and
# softmax statistics.  The fp8 control fails the first on every seed and
# the second on four of five.
LIMITS = {
    # the median leaf of the first gradient: sound 7.4e-5 to 1.39e-4, the
    # fp8 control 5.3e-4 to 9.0e-4, a ratio of 3.8; the limit 2.2 times
    # over the one and 1.8 under the other
    "grad_norm_mid_gap": 3.0e-4,
    # the worst leaf of the first gradient is a router's on 13 runs of 16
    # (its gradient moves with every top-4 choice that bf16 activations
    # flip against float32): sound 5.2e-4 to 1.99e-3 by the seed; the fp8
    # control 3.5e-3 to 7.3e-3 (the dense layer's norm2_w on four seeds of
    # five): 1.8 times the sound largest, no room for a limit between them
    # with room on both sides.  Held at 2.3 times the sound runs' largest,
    # against a part of a layer left out
    "grad_norm_gap": 4.5e-3,
    # the losses: sound 1.0e-5 to 6.8e-5, the fp8 control 1.31e-4 to
    # 1.89e-4, 1.9 times the sound largest: precision hardly moves it.  By
    # the contract's rule for such a number it takes the limit of the
    # accepted cells whose losses move for the same reason (sdar-30b-a3b's,
    # solar-open2-250b's, mellum2-12b-a2.5b's): 6.7 times over the first
    # reading (6.76e-5, still the largest of the 16)
    "loss_gap": 4.5e-4,
    # the worst leaf of the parameters' change, which Adam moves by sign:
    # sound 2.6e-4 to 4.8e-4, the fp8 control 5.8e-4 to 8.9e-4: precision
    # hardly moves it.  By the contract's rule it lies between the reading
    # and 1, which a state left unchanged reads, with the more room above
    # the reading: 17 times over it, 125 under 1 (the accepted mellum and
    # kanana cells' limit)
    "update_norm_gap": 8.0e-3,
}

_ROWS = 128          # query rows at a time (32 heads x 8,192 keys x 4 B a row)
_SCORED = 256        # scored rows at a time
_RESIDUAL_OUT = ("out_proj", "wo", "w2", "we_down")  # what writes into the stream
MIXER = {"conv": ("in_proj", "conv_w", "out_proj"),
         "full_attention": ("wq", "wk", "wv", "q_norm", "k_norm", "wo")}
DENSE = ("w1", "w3", "w2")
ROUTED = ("router", "router_bias", "we_gate", "we_up", "we_down")


def kept_types(cfg):
    return [cfg["layer_types"][i] for i in cfg["kept_layers"]]


def is_dense(cfg, n):
    """Whether the ``n``-th kept layer is one of the leading dense ones."""
    return n < cfg["num_dense_layers"]


def leaves(cfg, n):
    return (("norm1_w", "norm2_w") + MIXER[kept_types(cfg)[n]]
            + (DENSE if is_dense(cfg, n) else ROUTED))


def weight_shapes(cfg):
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    e, held = cfg["router_outputs"], cfg["num_experts"]
    leaf = {
        "norm1_w": (d,), "norm2_w": (d,), "in_proj": (d, 3 * d),
        "conv_w": (cfg["conv_L_cache"], d), "out_proj": (d, d),
        "wq": (d, h * dh), "wk": (d, hkv * dh), "wv": (d, hkv * dh),
        "q_norm": (dh,), "k_norm": (dh,), "wo": (h * dh, d),
        "w1": (d, fd), "w3": (d, fd), "w2": (fd, d), "router": (d, e),
        "router_bias": (e,), "we_gate": (held, d, f), "we_up": (held, d, f),
        "we_down": (held, f, d)}
    shapes = {"embed": (cfg["vocab_size"], d)}
    for n in range(len(cfg["kept_layers"])):
        for name in leaves(cfg, n):
            shapes[f"l{n}.{name}"] = leaf[name]
    shapes["final_norm_w"] = (d,)
    return shapes


def make_weights(cfg, key):
    """Flat dict of float32 weights from the key, as config.json's
    ``assumed`` says: matrices, the taps and the tied table normal(0,
    initializer_range), what writes into the residual stream normal(0,
    residual_out_range), norms at 1, the selection bias 0."""
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        leaf = name.split(".")[-1]
        if leaf == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            std = cfg["residual_out_range" if leaf in _RESIDUAL_OUT
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


def short_conv(g, w):
    """``Conv1d(groups = channels, padding = taps - 1, no bias)`` of ``g
    [B, T, d]`` under the taps ``w [taps, d]``, cut to ``T``: each channel
    alone, causal, ``w[taps - 1]`` on the position itself."""
    taps, d = w.shape
    return lax.conv_general_dilated(
        g, w[:, None, :], window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=d,
        precision=lax.Precision.HIGHEST)


def conv_mixer(u, lw, cfg, quant=lambda a: a):
    """The gated short convolution of the normed stream ``u [B, T, d]``."""
    b, c, x = jnp.split(_dot(u, lw["in_proj"], quant), 3, axis=-1)
    return _dot(c * short_conv(b * x, lw["conv_w"]), lw["out_proj"], quant)


def rope(x, theta):
    """x ``[B, T, H, Dh]`` rotated at positions 0 .. T - 1, rotate-half;
    the frequencies are constants, worked out in float64 on the host and
    rounded once."""
    half = x.shape[-1] // 2
    freqs = (float(theta) ** (-np.arange(half, dtype=np.float64) / half)
             ).astype(np.float32)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
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


def attention(u, lw, cfg, quant=lambda a: a):
    """Causal grouped-query attention of the normed stream ``u [B, T, d]``
    with q/k norm, ``_ROWS`` query rows at a time against every key under
    the dense mask."""
    B, T, _ = u.shape
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = _dot(u, lw["wq"], quant).reshape(B, T, h, dh)
    k = _dot(u, lw["wk"], quant).reshape(B, T, hkv, dh)
    q = rope(rms_norm(q, lw["q_norm"], eps), theta)
    k = quant(rope(rms_norm(k, lw["k_norm"], eps), theta))
    v = quant(_dot(u, lw["wv"], quant).reshape(B, T, hkv, dh))

    def block(qb, at):      # [B, rows, Hkv, g, Dh]; each row's position
        live = jnp.arange(T)[None, :] <= at[0][:, None]
        s = jnp.einsum("brhgd,bkhd->bhgrk", quant(qb), k,
                       precision=lax.Precision.HIGHEST) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgrk,bkhd->brhgd", quant(pr), v,
                          precision=lax.Precision.HIGHEST)

    at = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    out = _by_rows(block, _ROWS, q.reshape(B, T, hkv, h // hkv, dh), at)
    return _dot(out.reshape(B, T, h * dh), lw["wo"], quant)


def route(z, lw, cfg, quant=lambda a: a):
    """(chosen expert ids [B, T, top], their weights): a sigmoid of every
    router output, the largest with the selection bias chosen, the scores
    without it weighed over the chosen's sum plus ``router_eps``."""
    s = jax.nn.sigmoid(_dot(z, lw["router"], quant))
    _, top_i = lax.top_k(s + lw["router_bias"], cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, (top_s / (top_s.sum(-1, keepdims=True) + cfg["router_eps"])
                   * cfg["routed_scaling_factor"])


def dense_ff(z, lw, quant=lambda a: a):
    return _dot(jax.nn.silu(_dot(z, lw["w1"], quant)) * _dot(z, lw["w3"], quant),
                lw["w2"], quant)


def routed_ff(z, lw, cfg, quant=lambda a: a):
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


def _layer(lw, x, kind, dense, cfg, quant):
    eps = cfg["norm_eps"]
    u = rms_norm(x, lw["norm1_w"], eps)
    x = x + (conv_mixer(u, lw, cfg, quant) if kind == "conv"
             else attention(u, lw, cfg, quant))
    z = rms_norm(x, lw["norm2_w"], eps)
    return x + (dense_ff(z, lw, quant) if dense else routed_ff(z, lw, cfg, quant))


def hidden(cfg, w, tokens, quant=lambda a: a):
    """Token ids -> the final RMSNorm's output [B, T, d]."""
    x = w["embed"][tokens]
    # (a loop, not a scan over stacked leaves: the kinds differ, and under
    # the check's donated update the compiler would copy a stack)
    for n, kind in enumerate(kept_types(cfg)):
        lw = {name: w[f"l{n}.{name}"] for name in leaves(cfg, n)}
        x = jax.checkpoint(
            lambda lw_, x_, kind=kind, dense=is_dense(cfg, n): _layer(
                lw_, x_, kind, dense, cfg, quant))(lw, x)
    return rms_norm(x, w["final_norm_w"], cfg["norm_eps"])


def loss(cfg, w, batch, quant=lambda a: a):
    """Next-token cross-entropy of the batch over the ids held, the
    logits by the embedding table (the head is tied)."""
    tokens, targets = batch
    x = hidden(cfg, w, tokens, quant)

    def scored(xb, tb):                  # [B, rows, d], [B, rows]
        logp = jax.nn.log_softmax(_dot(xb, w["embed"].T, quant), axis=-1)
        return -jnp.take_along_axis(logp, tb[..., None], -1)

    return _by_rows(scored, _SCORED, x, targets).mean()
