"""Plain float32 reference of the configuration's layer and objective,
for the benchmark's check.  Straightforward jax.numpy, no kernels,
nothing imported from the program.  Departures from the published model
are listed in config.json under ``assumed``.

The layer (pre-norm, no bias anywhere, RMSNorm eps from the config):

    a = RMSNorm(x);  q = a Wq as [T, H, Dh];  k = a Wk, v = a Wv as
    [T, Hkv, Dh];  q, k each RMSNorm over Dh with a learned weight, then
    RoPE (rotate-half) at the token's position;  query head h attends to
    kv head h // (H / Hkv) under the mask M, scale Dh^-0.5;
    x = x + concat(heads) Wo
    b = RMSNorm(x);  p = softmax(b Wr) over all router outputs;  S = the
    num_experts_per_tok largest;  w_e = p_e / sum_S p;
    x = x + sum_{e in S, e held} w_e (silu(b G_e) * (b U_e)) D_e

The weights are normalised over all of S, held or not; what the experts
this chip does not hold would add is left out.  Final RMSNorm, an output
head of its own over the ids held.

The objective (block diffusion, BD3-LM, arXiv 2503.09573): a sequence x0
of L tokens in blocks of Bk; in each block m of its tokens are replaced
by the mask id, giving xt; the model reads [xt ; x0], 2L positions, both
halves counting positions 0..L-1.  With b(i) the block of token i: a
token of xt sees xt in its own block and x0 in earlier blocks; a token
of x0 sees x0 in its own and earlier blocks; nothing of x0 sees xt.  The
loss is the cross-entropy of the logits at xt's masked positions against
x0 there (no shift), each weighted Bk / m, over B x L.

Attention and the scored logits are computed in blocks of rows, the
experts of a layer are scanned over, and each layer, block and expert is
under jax.checkpoint, so that float32
scores of 8,192 positions fit beside the weights; that changes no number.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The check's limits, each from two readings (my chip runs, PR 27, at the
# timed sizes: 29 sound runs, each on a seed of its own, and
# benchmark/readings.py's control on 4; PERF.md section 2 has the table):
# the largest over the sound runs and the smallest over the control, this
# file with fp8 (e4m3) operands in every matrix product.  bf16 compute
# with fp32 parameters, norms, router and softmax; the control fails
# ``grad_norm_mid_gap``.
LIMITS = {
    # the median leaf of the first gradient: sound largest 6.5e-4, the
    # control's smallest 1.14e-3, a ratio of 1.75, the widest any number
    # read here reached (PERF.md says why it is no wider)
    "grad_norm_mid_gap": 1.0e-3,
    # the three below do not tell fp8 from bf16 on this model: their worst
    # leaf is a router's, whose gradient and update move with every top-8
    # choice that bf16 activations flip against float32 (0.5-0.9% of pairs
    # a layer), and the second and third losses follow those updates.
    # They are held at about three times the sound runs' largest, against
    # a part of the batch left out, a gradient of the wrong scale and a
    # step that leaves its state.
    # (28 sound runs under 3.9e-5 and one at 1.48e-4, seed 2147484905)
    "loss_gap": 4.5e-4,             # sound largest 1.48e-4, control 3.2e-5
    "grad_norm_gap": 0.12,          # sound largest 0.039, control 0.024
    "update_norm_gap": 0.06,        # sound largest 0.0145, control 0.0048
}

_ROWS = 256          # query rows, and scored rows, taken at a time
_RESIDUAL_OUT = ("wo", "we_down")   # what writes into the residual stream
_LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
          "mlp_norm", "router", "we_gate", "we_up", "we_down")


def weight_shapes(cfg):
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    per_layer = {
        "attn_norm": (d,), "wq": (d, h * dh), "wk": (d, hkv * dh),
        "wv": (d, hkv * dh), "wo": (h * dh, d), "q_norm": (dh,),
        "k_norm": (dh,), "mlp_norm": (d,),
        "router": (d, cfg["router_outputs"]),
        "we_gate": (held, d, f), "we_up": (held, d, f),
        "we_down": (held, f, d)}
    shapes = {"embed": (cfg["vocab_size"], d)}
    for i in range(cfg["num_hidden_layers"]):
        for n in _LAYER:
            shapes[f"l{i}.{n}"] = per_layer[n]
    shapes.update({"final_norm": (d,), "head": (cfg["vocab_size"], d)})
    return shapes


def make_weights(cfg, key):
    """Flat dict of float32 weights from the key: normal(0,
    initializer_range) matrices, the projections back into the residual
    stream normal(0, residual_out_range), normal(0, embedding_range)
    embedding rows, norms at 1; then each layer's experts are numbered so
    that this chip holds ``hot_experts_here`` of the mask token's choices
    (:func:`_place_hot_experts`).  config.json, ``assumed``, says why."""
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        if len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            std = cfg["embedding_range" if name == "embed"
                      else "residual_out_range" if name.endswith(_RESIDUAL_OUT)
                      else "initializer_range"]
            out[name] = jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * std
    for i in range(cfg["num_hidden_layers"]):
        out[f"l{i}.router"] = _place_hot_experts(
            out[f"l{i}.router"], out["embed"][-1], cfg)
    return out


def _place_hot_experts(router, mask_row, cfg):
    """The router's columns renumbered by how the mask token ranks them
    (every masked position carries its row, so all choose alike): this
    chip holds the ``hot_experts_here`` it ranks first, which stay among
    its choices as the weights move, and the ones it ranks last, which do
    not come among them; the rest lie on the other chips in their order.
    A permutation of columns drawn alike leaves the matrix drawn as it
    was."""
    outputs, held = cfg["router_outputs"], cfg["num_experts"]
    here, first = cfg["hot_experts_here"], cfg["experts_first"]
    a = mask_row * lax.rsqrt(jnp.mean(mask_row ** 2) + cfg["rms_norm_eps"])
    ranked = jnp.argsort(-jnp.matmul(a, router,
                                     precision=lax.Precision.HIGHEST))
    last = outputs - (held - here)
    mine = jnp.concatenate([ranked[:here], ranked[last:]])
    rest = ranked[here:last]
    return router[:, jnp.concatenate([rest[:first], mine, rest[first:]])]


def make_samples(cfg, key, n):
    """n noised rows: (tokens [n, 2L] = [xt ; x0], positions [n, 2L],
    targets [n, L] = x0, weights [n, L] = Bk / m at xt's masked positions
    and 0 elsewhere).  x0 uniform over the ids held but the last, which
    is the mask id; m uniform on 1..Bk per block; which m of a block's
    tokens are masked, uniform.  The data pipeline's work, on the host."""
    seed = int(np.asarray(jax.random.key_data(key)).astype(np.uint64).sum()
               % (2 ** 32))
    rng = np.random.RandomState(seed)
    L, bk, vocab = cfg["seq_len"], cfg["block_length"], cfg["vocab_size"]
    x0 = rng.randint(0, vocab - 1, (n, L)).astype(np.int32)
    m = rng.randint(1, bk + 1, (n, L // bk, 1))
    rank = rng.rand(n, L // bk, bk).argsort(-1).argsort(-1)
    masked = (rank < m).reshape(n, L)
    xt = np.where(masked, vocab - 1, x0).astype(np.int32)
    weights = np.where(masked, bk / np.repeat(m[..., 0], bk, axis=1),
                       0.0).astype(np.float32)
    positions = np.tile(np.arange(L, dtype=np.int32), (n, 2))
    return np.concatenate([xt, x0], 1), positions, x0, weights


def _sees(qi, ki, L, bk):
    """Whether query position qi of [xt ; x0] sees key position ki."""
    q_clean, k_clean = qi >= L, ki >= L
    qb, kb = (qi % L) // bk, (ki % L) // bk
    return ((~q_clean & ~k_clean & (qb == kb)) | (~q_clean & k_clean & (kb < qb))
            | (q_clean & k_clean & (kb <= qb)))


def attention_mask(L, bk):
    """Boolean [2L, 2L]: which keys each query of [xt ; x0] sees."""
    idx = np.arange(2 * L)
    return _sees(idx[:, None], idx[None, :], L, bk)


def _dot(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate-half; x [B, T, H, Dh], positions [B, T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, L, bk, quant):
    """q [B, T, H, Dh], k, v [B, T, Hkv, Dh] -> [B, T, H*Dh] under the
    mask of [xt ; x0], ``_ROWS`` query rows at a time (the mask of a
    block of rows is made from its positions: 8,192 x 8,192 booleans as
    a constant would be 67 MB of the program)."""
    B, T, H, dh = q.shape
    hkv = k.shape[2]
    rows = min(_ROWS, T)
    kq, vq = quant(k), quant(v)

    def block(args):
        qb, first = args                 # [B, rows, Hkv, g, Dh], its first row
        live = _sees(first + jnp.arange(rows)[:, None], jnp.arange(T)[None, :],
                     L, bk)
        s = jnp.einsum("brhgd,bkhd->bhgrk", quant(qb), kq,
                       precision=lax.Precision.HIGHEST) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgrk,bkhd->brhgd", quant(p), vq,
                          precision=lax.Precision.HIGHEST)

    qs = jnp.moveaxis(q.reshape(B, T // rows, rows, hkv, H // hkv, dh), 1, 0)
    out = lax.map(jax.checkpoint(block), (qs, jnp.arange(T // rows) * rows))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H * dh)


def _layer(lw, x, positions, cfg, quant):
    B, T, d = x.shape
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = _rmsnorm(x, lw["attn_norm"], eps)
    q = _dot(a, lw["wq"], quant).reshape(B, T, h, dh)
    k = _dot(a, lw["wk"], quant).reshape(B, T, hkv, dh)
    v = _dot(a, lw["wv"], quant).reshape(B, T, hkv, dh)
    q = _rope(_rmsnorm(q, lw["q_norm"], eps), positions, theta)
    k = _rope(_rmsnorm(k, lw["k_norm"], eps), positions, theta)
    x = x + _dot(_attend(q, k, v, T // 2, cfg["block_length"], quant),
                 lw["wo"], quant)

    b = _rmsnorm(x, lw["mlp_norm"], eps)
    p = jax.nn.softmax(_dot(b, lw["router"], quant), axis=-1)
    top_p, top_i = lax.top_k(p, cfg["num_experts_per_tok"])
    top_w = top_p / top_p.sum(-1, keepdims=True)

    def expert(held):                    # one held expert's part
        e, gate, up, down = held
        w_e = jnp.where(top_i == cfg["experts_first"] + e, top_w, 0.0).sum(-1)
        hidden = jax.nn.silu(_dot(b, gate, quant)) * _dot(b, up, quant)
        return w_e[..., None] * _dot(hidden, down, quant)

    x, _ = lax.scan(lambda x_, held: (x_ + jax.checkpoint(expert)(held), None),
                    x, (jnp.arange(cfg["num_experts"]), lw["we_gate"],
                        lw["we_up"], lw["we_down"]))
    return x


def loss(cfg, w, batch, quant=lambda a: a):
    """The block-diffusion loss of the batch."""
    tokens, positions, targets, weights = batch
    B, L = targets.shape
    x = w["embed"][tokens]
    # (a loop, not a scan over stacked leaves: under the check's donated
    # update the compiler copies a scan's stacked weights, 2.27 GB that
    # the chip does not have beside the float32 state)
    for i in range(cfg["num_hidden_layers"]):
        lw = {n: w[f"l{i}.{n}"] for n in _LAYER}
        x = jax.checkpoint(
            lambda lw_, x_: _layer(lw_, x_, positions, cfg, quant))(lw, x)
    x = _rmsnorm(x[:, :L], w["final_norm"], cfg["rms_norm_eps"])

    def scored(args):                    # [B, rows, d], [B, rows] twice
        xb, tb, wb = args
        logp = jax.nn.log_softmax(_dot(xb, w["head"].T, quant), axis=-1)
        return -(jnp.take_along_axis(logp, tb[..., None], -1)[..., 0]
                 * wb).sum()

    rows = min(_ROWS, L)
    split = lambda a: jnp.moveaxis(
        a.reshape(B, L // rows, rows, *a.shape[2:]), 1, 0)
    return lax.map(jax.checkpoint(scored),
                   (split(x), split(targets), split(weights))).sum() / (B * L)
