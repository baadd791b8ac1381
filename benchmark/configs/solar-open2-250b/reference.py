"""Plain float32 reference of the configuration's layers and objective,
for the benchmark's check.  Straightforward jax.numpy, no kernels,
nothing imported from the program.  Departures from the published model
are listed in config.json under ``assumed``.

The model (``solar_open2``): 48 published layers, those in ``gqa_layers``
softmax attention, the three between two of them Kimi Delta Attention
(arXiv 2510.26692), every feed-forward sparse.  Every layer is

    a = h + Mixer(RMSNorm_1(h));   h' = a + FF(RMSNorm_2(a))

RMSNorm with a weight, no bias in a product, no positional encoding
anywhere; the logits are ``RMSNorm(h_L) W_head^T``, the head untied.

``kda``, with ``u = RMSNorm_1(h)``: ``[q ; k ; v] = silu(conv(u W_qkv))``,
depthwise, causal, 4 wide, no bias, in heads of 128; ``q`` and ``k`` at
length 1 a head; ``g_t = -exp(A_log[h]) softplus(W_fb (W_fa u_t) +
dt_bias)`` a key channel; ``beta_t = 2 sigmoid(u_t W_b)`` a head;

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(128)                          (S a [K, V] matrix a head)

``y = W_o [RMSNorm_head(o) w * sigmoid(W_gb (W_ga u))]``.  **The
recurrence is walked position by position here**, never in the chunked
form the program uses: the two must not share a derivation.

``attention``: ``q, k, v`` from ``u``, grouped-query, ``softmax(q k^T /
sqrt(128) + causal) v``, gated elementwise by ``sigmoid(u W_gate)`` before
``W_o``.

``FF(z)``: ``s = sigmoid(z W_r)`` over all ``router_outputs``; the
``num_experts_per_tok`` largest of ``s + b`` are chosen; ``w = s_chosen /
sum(s_chosen)``; the chosen experts held here, ``E_e(z) = D_e (silu(G_e z)
* U_e z)``, weighted, plus the shared expert of the same form, once.  What
the experts this chip does not hold would add is left out.

The cut keeps the published layers ``kept_layers``, the chip's share of
the heads, of the experts and of the vocabulary.  The objective is
next-token cross-entropy over the ids held, averaged over every position
of every row.

The recurrence and attention are computed in blocks of rows, the experts
one after another, the scored logits in blocks, and each layer, block and
expert is under jax.checkpoint, so that the float32 activations of 8,192
positions fit beside the float32 weights, gradient and optimizer state;
that changes no number.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The check's limits (my chip runs, PR 42, at the timed sizes; PERF.md
# section 2 has the table).  The lower reading is the largest over the
# sound runs, each on a seed of its own (11 when the limits were set, 18 by
# the end of that session, none over these); the
# upper one the smallest over the control on 3 seeds, this file with fp8
# (e4m3) operands in every matrix product (benchmark/readings.py's).  bf16
# compute with fp32 parameters, router, norms, decays, sums, exponentials,
# triangular inverse, carried state and softmax statistics.  The control
# fails the first on every seed, 3.8-fold at the least.
LIMITS = {
    # the median leaf of the first gradient: sound 3.6e-5 to 5.7e-5, the
    # control 8.4e-4 to 8.8e-4, a ratio of 14.8; the limit 3.9 times over
    # the one and 3.8 under the other
    "grad_norm_mid_gap": 2.2e-4,
    # the three below do not tell fp8 from bf16 on this model, for SDAR's
    # reason: their worst leaf is an expert's or a router's, whose gradient
    # and update move with every top-8 choice that bf16 activations flip
    # against float32, and the second and third losses follow those
    # updates.
    # The losses: sound largest 1.7e-5 (3.5e-6 to 1.7e-5), the control
    # 9.2e-6, 7.3e-5, 8.0e-5: NO UPPER READING.  By the contract's rule for
    # such a number it takes the limit of the accepted cell whose losses
    # move for the same reason (sdar-30b-a3b's, where one sound run of 29
    # read 3.8 times the others' largest): 26 times over the reading here
    "loss_gap": 4.5e-4,
    # the worst leaf of the first gradient is an expert's matrix on every
    # run: sound 5.2e-4 to 3.5e-3 by the seed, the control 3.8e-3 to
    # 5.7e-3: precision moves it less than a seed does.  Held at 5.7 times
    # the sound runs' largest, against a part of the batch left out and a
    # gradient of the wrong scale
    "grad_norm_gap": 0.02,
    # the worst leaf of the parameters' change is a router's, which Adam
    # moves by sign: sound 2.8e-4 to 1.9e-3 (the next 1.2e-3), the control
    # 1.9e-3 to 3.0e-3: precision does not move it.  By the contract's rule
    # it lies between the reading and 1, which a state left unchanged
    # reads, with the room above the reading: 21 times over it, 25 under 1
    "update_norm_gap": 0.04,
}
# Not the harness's: the adapter's own guard (adapter.py's docstring), one
# KDA layer's ``o`` by the program's scan against :func:`recurrence` below.
# Sound 4.4e-3 to 5.2e-3 (18 seeds: the MXU's bf16 products; with float32
# operands handed in the same scan reads 3.9e-3 to 4.2e-3); the carried
# state dropped 0.46 to 0.53.  What it does not see at the seed's weak
# decays: the running sums computed and kept in bfloat16 read 4.9e-3 to
# 5.3e-3, 6% over the sound reading on the same seed (my chip runs, PR 42;
# PERF.md section 2 and question 21): tests/test_kda_scan.py holds the sums
# to float32 against the float64 walk at decays where they matter.
KDA_O_GAP = 8.0e-3

_ROWS = 256          # query rows, recurrence steps, scored rows at a time
_RESIDUAL_OUT = ("wo", "w2", "we_down")    # what writes into the stream
_BASE = ("norm1_w", "norm2_w", "router", "router_bias", "we_gate", "we_up",
         "we_down", "w1", "w2")
LEAVES = {
    "attention": _BASE + ("wqkv", "wgate", "wo"),
    "kda": _BASE + ("wqkv", "conv_w", "f_a", "f_b", "dt_bias", "A_log",
                    "b_proj", "g_a", "g_b", "o_norm", "wo"),
}


def kept_kinds(cfg):
    return ["attention" if i in cfg["gqa_layers"] else "kda"
            for i in cfg["kept_layers"]]


def sizes(cfg):
    lin = cfg["linear_attn_config"]
    return dict(
        d=cfg["hidden_size"], f=cfg["moe_intermediate_size"],
        h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        dh=cfg["head_dim"], hs=lin["num_heads"], dk=lin["head_dim"],
        kc=lin["short_conv_kernel_size"], e=cfg["router_outputs"],
        held=cfg["n_routed_experts"], top=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"])


def weight_shapes(cfg):
    z = sizes(cfg)
    d, f, h, hkv, dh, hs, dk = (z[k] for k in
                                ("d", "f", "h", "hkv", "dh", "hs", "dk"))
    leaf = {
        "norm1_w": (d,), "norm2_w": (d,), "router": (d, z["e"]),
        "router_bias": (z["e"],), "we_gate": (z["held"], d, f),
        "we_up": (z["held"], d, f), "we_down": (z["held"], f, d),
        "w1": (d, 2 * z["shared"] * f), "w2": (z["shared"] * f, d),
        "wgate": (d, h * dh), "f_a": (d, dk), "f_b": (dk, hs * dk),
        "dt_bias": (hs * dk,), "A_log": (hs,), "b_proj": (d, hs),
        "g_a": (d, dk), "g_b": (dk, hs * dk), "o_norm": (dk,)}
    mixer = {"attention": {"wqkv": (d, (h + 2 * hkv) * dh), "wo": (h * dh, d)},
             "kda": {"wqkv": (d, 3 * hs * dk), "conv_w": (z["kc"], 3 * hs * dk),
                     "wo": (hs * dk, d)}}
    shapes = {"embed": (cfg["vocab_size"], d)}
    for i, kind in enumerate(kept_kinds(cfg)):
        for name in LEAVES[kind]:
            shapes[f"l{i}.{name}"] = mixer[kind].get(name) or leaf[name]
    shapes.update({"final_norm_w": (d,), "head": (cfg["vocab_size"], d)})
    return shapes


def make_weights(cfg, key):
    """Flat dict of float32 weights from the key, as config.json's
    ``assumed`` says: matrices normal(0, initializer_range), embedding rows
    normal(0, embedding_range), what writes into the residual stream
    normal(0, residual_out_range); norms at 1, the selection bias 0; the
    convolution uniform +-1/sqrt(4); ``softplus(dt_bias)`` log-uniform on
    dt_min..dt_max, ``A_log = log(uniform(A_init_range))``."""
    z = sizes(cfg)
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        leaf = name.split(".")[-1]
        if leaf in ("norm1_w", "norm2_w", "final_norm_w", "o_norm"):
            w = jnp.ones(shape, jnp.float32)
        elif leaf == "router_bias":
            w = jnp.zeros(shape, jnp.float32)
        elif leaf == "conv_w":
            bound = z["kc"] ** -0.5
            w = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif leaf == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(cfg["dt_min"]),
                math.log(cfg["dt_max"])))
            w = step + jnp.log(-jnp.expm1(-step))      # softplus's inverse
        elif leaf == "A_log":
            lo, hi = cfg["A_init_range"]
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        else:
            std = cfg["embedding_range" if leaf == "embed"
                      else "residual_out_range" if leaf in _RESIDUAL_OUT
                      else "initializer_range"]
            w = jax.random.normal(k, shape, jnp.float32) * std
        out[name] = w
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


def recurrence(q, k, v, g, beta, state_dtype=jnp.float32):
    """The gated delta rule, a position at a time: q, k, g [B, T, H, K],
    v [B, T, H, V], beta [B, T, H] -> o [B, T, H, V].  ``_ROWS`` steps a
    block, each block under jax.checkpoint.  ``state_dtype`` is the
    lower-precision control's: the decays and the carried state rounded to
    it."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    rows = min(_ROWS, T)
    low = lambda a: a.astype(state_dtype).astype(jnp.float32)

    def step(S, at):
        qt, kt, vt, gt, bt = at     # [B, H, K] x2, [B, H, V], [B, H, K], [B, H]
        S = low(jnp.exp(low(gt)))[..., None] * S
        u = bt[..., None] * (vt - (kt[..., None] * S).sum(-2))
        S = low(S + kt[..., None] * u[..., None, :])
        return S, (qt[..., None] * S).sum(-2) * K ** -0.5

    def block(S, at):
        return lax.scan(step, S, at)

    blocks = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        T // rows, rows, *a.shape[:1], *a.shape[2:])
    _, o = lax.scan(jax.checkpoint(block), jnp.zeros((B, H, K, V)),
                    tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape(T, B, H, V), 0, 1)


def kda_operands(u, lw, cfg, quant=lambda a: a):
    """What the recurrence is handed, from the normed stream ``u``: ``q``,
    ``k`` (unit length a head), ``v`` [B, T, H, 128], the log decays ``g``
    [B, T, H, 128] and ``beta`` [B, T, H]."""
    z = sizes(cfg)
    B, T, _ = u.shape
    hs, dk, kc = z["hs"], z["dk"], z["kc"]
    qkv = _dot(u, lw["wqkv"], quant)
    padded = jnp.pad(qkv, ((0, 0), (kc - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + T] * lw["conv_w"][j]
                          for j in range(kc)))
    q, k, v = (a.reshape(B, T, hs, dk) for a in jnp.split(qkv, 3, axis=-1))
    unit = lambda a: a * lax.rsqrt((a * a).sum(-1, keepdims=True)
                                   + cfg["l2_norm_eps"])
    f = _dot(_dot(u, lw["f_a"], quant), lw["f_b"], quant) + lw["dt_bias"]
    g = (-jnp.exp(lw["A_log"])[:, None]
         * jax.nn.softplus(f).reshape(B, T, hs, dk))
    beta = 2.0 * jax.nn.sigmoid(_dot(u, lw["b_proj"], quant))
    return unit(q), unit(k), v, g, beta


def _kda(u, lw, cfg, quant, state_dtype):
    o = recurrence(*kda_operands(u, lw, cfg, quant), state_dtype)
    o = rms_norm(o, lw["o_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_dot(_dot(u, lw["g_a"], quant), lw["g_b"], quant))
    return _dot(o.reshape(gate.shape) * gate, lw["wo"], quant)


def _attention(u, lw, cfg, quant):
    """Causal grouped-query attention without positions, ``_ROWS`` query
    rows at a time, gated before ``wo``."""
    z = sizes(cfg)
    B, T, _ = u.shape
    h, hkv, dh = z["h"], z["hkv"], z["dh"]
    q, k, v = jnp.split(_dot(u, lw["wqkv"], quant),
                        (h * dh, (h + hkv) * dh), axis=-1)
    k, v = (quant(a.reshape(B, T, hkv, dh)) for a in (k, v))
    rows = min(_ROWS, T)

    def block(qb, first):    # [B, rows, Hkv, g, Dh]; each row's block's first
        live = (jnp.arange(T)[None, :]
                <= first[0, 0] + jnp.arange(rows)[:, None])
        s = jnp.einsum("brhgd,bkhd->bhgrk", quant(qb), k,
                       precision=lax.Precision.HIGHEST) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgrk,bkhd->brhgd", quant(p), v,
                          precision=lax.Precision.HIGHEST)

    first = jnp.broadcast_to(jnp.arange(0, T, rows)[None, :, None],
                             (B, T // rows, rows)).reshape(B, T)
    out = _by_rows(block, rows, q.reshape(B, T, hkv, h // hkv, dh), first)
    gate = jax.nn.sigmoid(_dot(u, lw["wgate"], quant))
    return _dot(out.reshape(B, T, h * dh) * gate, lw["wo"], quant)


def route(z, lw, cfg, quant=lambda a: a):
    """(chosen expert ids [B, T, top], their weights): sigmoid scores,
    chosen by score plus bias, weighed by score over the chosen."""
    s = jax.nn.sigmoid(_dot(z, lw["router"], quant))
    _, top_i = lax.top_k(s + lw["router_bias"], cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, (top_s / top_s.sum(-1, keepdims=True)
                   * cfg["routed_scaling_factor"])


def feed_forward(z, lw, cfg, quant=lambda a: a):
    """The held experts' part of the routed layer plus the shared expert."""
    top_i, top_w = route(z, lw, cfg, quant)

    def expert(held):                    # one held expert's part
        e, gate, up, down = held
        w_e = jnp.where(top_i == cfg["experts_first"] + e, top_w, 0.0).sum(-1)
        hidden = jax.nn.silu(_dot(z, gate, quant)) * _dot(z, up, quant)
        return w_e[..., None] * _dot(hidden, down, quant)

    def shared(z_):
        gate, up = jnp.split(_dot(z_, lw["w1"], quant), 2, axis=-1)
        return _dot(jax.nn.silu(gate) * up, lw["w2"], quant)

    y, _ = lax.scan(lambda y_, held: (y_ + jax.checkpoint(expert)(held), None),
                    jax.checkpoint(shared)(z),
                    (jnp.arange(cfg["n_routed_experts"]), lw["we_gate"],
                     lw["we_up"], lw["we_down"]))
    return y


def _layer(lw, x, kind, cfg, quant, state_dtype):
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, lw["norm1_w"], eps)
    x = x + (_kda(u, lw, cfg, quant, state_dtype) if kind == "kda"
             else _attention(u, lw, cfg, quant))
    return x + feed_forward(rms_norm(x, lw["norm2_w"], eps), lw, cfg, quant)


def hidden(cfg, w, tokens, quant=lambda a: a, state_dtype=jnp.float32):
    """Token ids -> the final RMSNorm's output [B, T, d]."""
    x = w["embed"][tokens]
    # (a loop, not a scan over stacked leaves: the kinds differ, and under
    # the check's donated update the compiler would copy a stack)
    for n, kind in enumerate(kept_kinds(cfg)):
        lw = {name: w[f"l{n}.{name}"] for name in LEAVES[kind]}
        x = jax.checkpoint(
            lambda lw_, x_, kind=kind: _layer(lw_, x_, kind, cfg, quant,
                                              state_dtype))(lw, x)
    return rms_norm(x, w["final_norm_w"], cfg["rms_norm_eps"])


def loss(cfg, w, batch, quant=lambda a: a, state_dtype=jnp.float32):
    """Next-token cross-entropy of the batch over the ids held."""
    tokens, targets = batch
    x = hidden(cfg, w, tokens, quant, state_dtype)

    def scored(xb, tb):                  # [B, rows, d], [B, rows]
        logp = jax.nn.log_softmax(_dot(xb, w["head"].T, quant), axis=-1)
        return -jnp.take_along_axis(logp, tb[..., None], -1)

    return _by_rows(scored, _ROWS, x, targets).mean()
