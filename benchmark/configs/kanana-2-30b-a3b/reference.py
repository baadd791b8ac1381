"""Plain float32 reference of the configuration's layers and objective,
for the benchmark's check.  Straightforward jax.numpy, no kernels,
nothing imported from the program.  Departures from the published model
are listed in config.json under ``assumed``.

The model (``deepseek_v3``: ``transformers``' ``DeepseekV3Attention`` /
``DeepseekV3MoE`` / ``DeepseekV3TopkRouter``; arXiv 2412.19437, 2405.04434):
every layer is

    a = h + Attn(RMSNorm_1(h));   h' = a + FF(RMSNorm_2(a))

RMSNorm with a weight (eps 1e-6), no bias in a product; the logits are
``RMSNorm(h_L) W_head^T``, the head untied.

``Attn``, multi-head latent attention without a query latent, with ``u =
RMSNorm_1(h)`` over positions ``p = 0 .. T - 1``:

    q = u Wq                      [T, 32, 192]: a head's first 128 columns
                                  q_nope, its last 64 q_pe
    [c ; k_pe] = u Wkv_a          [T, 512 + 64]; c <- RMSNorm(c; w_c)
                                  (the 512 alone; k_pe is one head, not normed)
    [k_nope ; v] = c Wkv_b        [T, 32, 128 + 128]
    q_pe, k_pe rotated            the stored columns are pairs (x[2j],
                                  x[2j+1]), j = 0 .. 31, turned by p theta_j,
                                  theta_j = 1e6 ** (-j / 32): a' = a cos - b
                                  sin, b' = a sin + b cos (rope_interleave)
    S = ([q_nope ; q_pe] . [k_nope ; k_pe]) / sqrt(192), k_pe the same for
    all 32 heads; causal; softmax; o = P v [T, 32, 128]; Attn = o Wo

``FF(z)``: layer 0 (``first_k_dense_replace`` 1) ``Wd (silu(Wg z) * Wu
z)`` at 6,144.  Later layers: ``s = sigmoid(z W_r)`` over all
``router_outputs``; the ``num_experts_per_tok`` largest of ``s + b`` are
chosen (``n_group`` 1, ``topk_group`` 1: nothing restricted); ``w =
s_chosen / sum(s_chosen) x routed_scaling_factor``; the chosen experts
held here, ``E_e(z) = D_e (silu(G_e z) * U_e z)`` at 768, weighted, plus
the two shared experts as one SwiGLU of 1,536, once.  What the experts this
chip does not hold would add is left out.

The cut keeps the published layers ``kept_layers``, the chip's share of
the experts and of the vocabulary, attention whole.  The objective is
next-token cross-entropy over the ids held, averaged over every position
of every row.

Attention is computed in blocks of query rows against every key under a
dense mask, the experts one after another, the scored logits in blocks,
and each layer, block and expert is under jax.checkpoint, so that the
float32 activations of 16,384 positions fit beside the float32 weights,
gradient and optimizer state; that changes no number.

``loss(..., control=)`` is for the builder's three controls of this
configuration's own, each of which has to fail a limit or the adapter's
guard: ``"no_latent_norm"`` leaves the 512 un-normed; ``"scale_128"``
divides the scores by ``sqrt(128)``; ``"rope_halves"`` turns the published
pairs as if they were halves.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The check's limits (my chip runs, PR 49, at the timed sizes; PERF.md
# section 2 has the table).  The lower reading is the largest over the
# sound runs, each on a seed of its own (5 when the first, second and fourth
# limits were set: the split and the joined form on one seed, three more of
# the split form; 20 by the end, none over a limit); the upper ones the
# smallest over the fp8 control (2 seeds then, 5 by the end), this file with
# fp8 (e4m3) operands in every matrix product, and this configuration's own
# three controls on one seed (``loss(control=)`` below).  bf16 compute with
# fp32 parameters, router, norms, rotation and softmax statistics.  The fp8
# control fails the first and the third on every seed; ``no_latent_norm`` and
# ``scale_128`` fail the second 13-fold and 12-fold, and the fourth;
# ``rope_halves`` is seen by no number of the harness at seeded weights
# (its worst leaf reads 3.2e-3, a router's as the sound runs') and fails the
# adapter's guard, below, as the other two do.
LIMITS = {
    # the median leaf of the first gradient: sound 5.5e-5 to 7.0e-5, the
    # fp8 control 8.1e-4 and 1.09e-3, a ratio of 11.6; the limit 3.4 times
    # over the one and 3.4 under the other (over 20 sound runs and 5 seeds
    # of the control: 1.00e-4 and 7.7e-4, 2.4 times over and 3.2 under)
    "grad_norm_mid_gap": 2.4e-4,
    # the worst leaf of the first gradient is a router's on 4 runs of 5
    # (its gradient moves with every top-6 choice that bf16 activations
    # flip against float32): sound 8.0e-4 to 2.27e-3 by the seed; the fp8
    # control 3.4e-3 and 5.4e-3 (an expert's down matrix): precision moves
    # it less than a seed does.  The latent left un-normed reads 0.154
    # (l1.wkv_b), the scores over sqrt(128) 0.140 (l0.wq).  Held at 5.3
    # times the sound runs' largest, 12 under the two controls', against a
    # part of the attention left out and a gradient of the wrong scale
    "grad_norm_gap": 0.012,
    # the losses: sound 1.4e-6 to 8.5e-6 over 20 runs (the three steps'
    # signed gaps scatter about zero, 4.1e-6 of the loss their root mean
    # square over 8 runs: bf16 rounding averaged over 16,384 positions), the
    # fp8 control 3.4e-5 to 6.5e-5 over 5 seeds, 4.0 times the sound largest
    # at the least: an upper reading.  The limit between the two, twice over
    # the one and half the other (four of the scatter's root mean squares).  The three
    # controls of its own read 1.6e-6 to 2.6e-6 and pass it: at seeded
    # weights the loss is ln(vocabulary) whatever attention sees
    "loss_gap": 1.7e-5,
    # the worst leaf of the parameters' change, a router's, which Adam
    # moves by sign: sound 1.2e-4 to 2.0e-4, the fp8 control 2.7e-4 and
    # 6.3e-4: precision hardly moves it.  By the contract's rule it lies
    # between the reading and 1, which a state left unchanged reads, with
    # the more room above the reading: 40 times over it, 125 under 1 (the
    # accepted mellum cell's limit); ``no_latent_norm`` reads 0.017 and
    # ``scale_128`` 0.020, both over it
    "update_norm_gap": 8.0e-3,
}
# Not the harness's: the adapter's own guard (adapter.py's docstring), the
# last kept layer's attention sublayer (through ``wo``) by the program's path
# at the timed sizes on the seed's weights against :func:`attention` below.
# Sound 5.16e-3 to 5.31e-3 (5 seeds, the split form and the joined alike to
# 1e-7: the MXU's bf16 products); the latent un-normed 0.147, the scores
# over sqrt(128) 0.150, the pairs turned as halves 0.420 (my chip runs, PR
# 49).  The limit 4.7 times over the sound readings, 5.9 under the smallest
# control's.
MLA_O_GAP = 0.025

_ROWS = 128          # query rows at a time (32 heads x 16,384 keys x 4 B a row)
_SCORED = 256        # scored rows at a time
_RESIDUAL_OUT = ("wo", "w2", "we_down")    # what writes into the stream
_ATTENTION = ("norm1_w", "norm2_w", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE = _ATTENTION + ("w1", "w2")
ROUTED = _ATTENTION + ("router", "router_bias", "we_gate", "we_up",
                       "we_down", "w1", "w2")
CONTROLS = (None, "no_latent_norm", "scale_128", "rope_halves")


def is_dense(cfg, n):
    """Whether the ``n``-th kept layer is one of the leading dense ones."""
    return cfg["kept_layers"][n] < cfg["first_k_dense_replace"]


def leaves(cfg, n):
    return DENSE if is_dense(cfg, n) else ROUTED


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        f=cfg["moe_intermediate_size"], fd=cfg["intermediate_size"],
        e=cfg["router_outputs"], held=cfg["n_routed_experts"],
        shared=cfg["n_shared_experts"])


def weight_shapes(cfg):
    z = sizes(cfg)
    d, h, f, held = z["d"], z["h"], z["f"], z["held"]
    leaf = {
        "norm1_w": (d,), "norm2_w": (d,),
        "wq": (d, h * (z["dn"] + z["dr"])), "wkv_a": (d, z["r"] + z["dr"]),
        "kv_norm": (z["r"],), "wkv_b": (z["r"], h * (z["dn"] + z["dv"])),
        "wo": (h * z["dv"], d), "router": (d, z["e"]),
        "router_bias": (z["e"],), "we_gate": (held, d, f),
        "we_up": (held, d, f), "we_down": (held, f, d)}
    shapes = {"embed": (cfg["vocab_size"], d)}
    for n in range(len(cfg["kept_layers"])):
        width = z["fd"] if is_dense(cfg, n) else z["shared"] * f
        for name in leaves(cfg, n):
            shapes[f"l{n}.{name}"] = {"w1": (d, 2 * width),
                                      "w2": (width, d)}.get(name) or leaf[name]
    shapes.update({"final_norm_w": (d,), "head": (cfg["vocab_size"], d)})
    return shapes


def make_weights(cfg, key):
    """Flat dict of float32 weights from the key, as config.json's
    ``assumed`` says: matrices normal(0, initializer_range), embedding rows
    normal(0, embedding_range), what writes into the residual stream
    normal(0, residual_out_range), norms at 1, the selection bias 0."""
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        leaf = name.split(".")[-1]
        if leaf == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif len(shape) == 1:
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


def inv_freq(cfg):
    """``theta ** (-j / (d_r / 2))``, ``j = 0 .. d_r / 2 - 1``: constants,
    worked out in float64 on the host and rounded once."""
    half = cfg["qk_rope_head_dim"] // 2
    return (float(cfg["rope_theta"])
            ** (-np.arange(half, dtype=np.float64) / half)).astype(np.float32)


def rope_pairs(x, cfg, halves=False):
    """x ``[B, T, H, d_r]`` with its columns in pairs ``(x[2j], x[2j+1])``
    turned by ``p theta_j`` at positions ``p = 0 .. T - 1``, as published
    (``rope_interleave``); ``halves`` is the control's: the same columns
    turned as ``(x[j], x[j + d_r / 2])``."""
    angles = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
              * inv_freq(cfg))
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    if halves:
        a, b = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


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


def attention_heads(u, lw, cfg, quant=lambda a: a, control=None):
    """The heads' output ``o [B, T, 32, 128]`` (before ``wo``) of the
    normed stream ``u [B, T, d]``, ``_ROWS`` query rows at a time against
    every key under the dense causal mask."""
    z = sizes(cfg)
    B, T, _ = u.shape
    h, r, dn, dr, dv = z["h"], z["r"], z["dn"], z["dr"], z["dv"]
    halves = control == "rope_halves"
    q = _dot(u, lw["wq"], quant).reshape(B, T, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], cfg, halves)],
                        -1)
    c, k_pe = jnp.split(_dot(u, lw["wkv_a"], quant), (r,), axis=-1)
    if control != "no_latent_norm":
        c = rms_norm(c, lw["kv_norm"], cfg["rms_norm_eps"])
    kv = _dot(c, lw["wkv_b"], quant).reshape(B, T, h, dn + dv)
    k_pe = rope_pairs(k_pe.reshape(B, T, 1, dr), cfg, halves)
    k = quant(jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (B, T, h, dr))], -1))
    v = quant(kv[..., dn:])
    scale = (dn if control == "scale_128" else dn + dr) ** -0.5
    rows = min(_ROWS, T)

    def block(qb, at):      # [B, rows, H, 192]; each row's position
        live = jnp.arange(T)[None, :] <= at[0][:, None]
        s = jnp.einsum("brhd,bkhd->bhrk", quant(qb), k,
                       precision=lax.Precision.HIGHEST) * scale
        pr = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhrk,bkhd->brhd", quant(pr), v,
                          precision=lax.Precision.HIGHEST)

    at = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    return _by_rows(block, rows, q, at)


def attention(u, lw, cfg, quant=lambda a: a, control=None):
    o = attention_heads(u, lw, cfg, quant, control)
    return _dot(o.reshape(*u.shape[:2], -1), lw["wo"], quant)


def route(z, lw, cfg, quant=lambda a: a):
    """(chosen expert ids [B, T, top], their weights): sigmoid scores,
    chosen by score plus bias, weighed by score over the chosen, times
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(_dot(z, lw["router"], quant))
    _, top_i = lax.top_k(s + lw["router_bias"], cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, (top_s / top_s.sum(-1, keepdims=True)
                   * cfg["routed_scaling_factor"])


def swiglu(z, w1, w2, quant=lambda a: a):
    """``W2 (silu(g) * u)``, ``[g ; u] = z W1``: the dense layer's
    feed-forward and the shared experts'."""
    gate, up = jnp.split(_dot(z, w1, quant), 2, axis=-1)
    return _dot(jax.nn.silu(gate) * up, w2, quant)


def feed_forward(z, lw, cfg, quant=lambda a: a):
    """The held experts' part of the routed layer plus the shared experts."""
    top_i, top_w = route(z, lw, cfg, quant)

    def expert(held):                    # one held expert's part
        e, gate, up, down = held
        w_e = jnp.where(top_i == cfg["experts_first"] + e, top_w, 0.0).sum(-1)
        hidden = jax.nn.silu(_dot(z, gate, quant)) * _dot(z, up, quant)
        return w_e[..., None] * _dot(hidden, down, quant)

    shared = lambda z_: swiglu(z_, lw["w1"], lw["w2"], quant)
    y, _ = lax.scan(lambda y_, held: (y_ + jax.checkpoint(expert)(held), None),
                    jax.checkpoint(shared)(z),
                    (jnp.arange(cfg["n_routed_experts"]), lw["we_gate"],
                     lw["we_up"], lw["we_down"]))
    return y


def _layer(lw, x, dense, cfg, quant, control):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["norm1_w"], eps), lw, cfg, quant, control)
    z = rms_norm(x, lw["norm2_w"], eps)
    if dense:
        return x + jax.checkpoint(
            lambda z_: swiglu(z_, lw["w1"], lw["w2"], quant))(z)
    return x + feed_forward(z, lw, cfg, quant)


def hidden(cfg, w, tokens, quant=lambda a: a, control=None):
    """Token ids -> the final RMSNorm's output [B, T, d]."""
    if control not in CONTROLS:
        raise ValueError(f"control must be one of {CONTROLS}")
    x = w["embed"][tokens]
    # (a loop, not a scan over stacked leaves: the layers differ, and under
    # the check's donated update the compiler would copy a stack)
    for n in range(len(cfg["kept_layers"])):
        lw = {name: w[f"l{n}.{name}"] for name in leaves(cfg, n)}
        x = jax.checkpoint(
            lambda lw_, x_, dense=is_dense(cfg, n): _layer(
                lw_, x_, dense, cfg, quant, control))(lw, x)
    return rms_norm(x, w["final_norm_w"], cfg["rms_norm_eps"])


def loss(cfg, w, batch, quant=lambda a: a, control=None):
    """Next-token cross-entropy of the batch over the ids held."""
    tokens, targets = batch
    x = hidden(cfg, w, tokens, quant, control)

    def scored(xb, tb):                  # [B, rows, d], [B, rows]
        logp = jax.nn.log_softmax(_dot(xb, w["head"].T, quant), axis=-1)
        return -jnp.take_along_axis(logp, tb[..., None], -1)

    return _by_rows(scored, _SCORED, x, targets).mean()
