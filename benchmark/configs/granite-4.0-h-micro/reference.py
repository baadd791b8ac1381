"""Plain float32 reference of the configuration's layers and objective,
for the benchmark's check.  Straightforward jax.numpy, no kernels,
nothing imported from the program.  Departures from the published model
are listed in config.json under ``assumed``.

The model (GraniteMoeHybrid's modelling file; the mixer is Mamba-2, arXiv
2405.21060), 40 published layers, ``layer_types`` naming each.  The input
is ``embedding_multiplier x E[token]``; every layer is

    a = h + r Mixer(RMSNorm_1(h));  h' = a + r W_out (silu(g) * v),
    [g ; v] = W_in RMSNorm_2(a),    r = residual_multiplier

RMSNorm with a weight, no bias in a product, no positional encoding
anywhere; the logits are ``RMSNorm(h_L) E^T / logits_scaling``, the head
the embedding (tied).

``mamba``, with ``u = RMSNorm_1(h)``: ``[z ; xBC ; dt] = W_in u``; ``xBC
= silu(conv(xBC) + b)``, depthwise, causal, d_conv wide, over all its
channels; ``[x ; B ; C] = xBC`` (``x`` in heads of ``mamba_d_head``, ``B``
and ``C`` of ``mamba_d_state``, shared by a group's heads); ``delta_t =
softplus(dt_t + dt_bias)`` and ``A = -exp(A_log)`` a head;

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T      (S a [P, N] matrix a head)
    y_t = S_t C_t + D x_t

``o = RMSNorm(y * silu(z)) * w`` over all channels at once (the gate
before the norm); the mixer gives ``W_out o``.  **The recurrence is walked
position by position here**, never in the chunked form the program uses:
the two must not share a derivation.

``attention``: ``q, k, v`` from ``u``, grouped-query, ``softmax(
attention_multiplier x q k^T + causal) v`` through ``W_o``.

The cut keeps the published layers ``kept_layers``.  The objective is
next-token cross-entropy over the ids held, averaged over every position
of every row.

The recurrence and attention are computed in blocks of rows, the MLP and
the scored logits too, and each layer and block is under jax.checkpoint,
so that the float32 activations of 8,192 positions fit beside the float32
weights, gradient and optimizer state; that changes no number.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The check's limits (my chip runs, PR 40, at the timed sizes; PERF.md
# section 2 has the table).  The lower reading is the largest over the
# sound runs, each on a seed of its own: 20 when the first two limits were
# set, 23 for the last two, 34 by the end of that session.  The upper one is
# the smallest over the control on 4 seeds, this file with fp8 (e4m3)
# operands in every matrix product (benchmark/readings.py's), or a planted
# fault's.  bf16 compute with fp32 parameters, norms, delta, the decay's sums
# and exponentials, the carried state and softmax statistics.  The control
# fails the first on every seed, 3.2-fold at the least.
LIMITS = {
    # the median leaf of the first gradient: sound largest 8.8e-5, the
    # control's smallest 9.0e-4, a ratio of 10.2; the limit 3.2 times over
    # the one and 3.2 under the other.  The scan with the carried state
    # dropped (every chunk from zero) reads 8.4e-4
    "grad_norm_mid_gap": 2.8e-4,
    # the three steps' losses agree to a few float32 roundings of a loss
    # of 9.45: sound largest 1.9e-6 (33 of 34 under 1.6e-6).  NO UPPER
    # READING: the control reads 4.5e-6, 7.4e-6, 8.9e-6, 1.3e-5, so one of
    # four passes; three times the sound runs' largest.  `D x` left out
    # reads 4.6e-5; the dropped state 8e-7, inside
    "loss_gap": 6e-6,
    # the worst leaf of the first gradient is a layer's dt_bias or A_log,
    # 64 numbers, on every run: 0.0034 to 0.0115 by the seed.  The control
    # reads 0.0093 to 0.0127: precision does not move this number beyond
    # what a seed does.  The upper reading is a planted fault's, `D x` left
    # out of the scan: 1.33 (l7.conv_b).  5 times over the one, 22 under
    # the other; a fault as small as the dropped state (0.0235) passes it
    "grad_norm_gap": 0.06,
    # the worst leaf of the parameters' change is the same 64-vectors,
    # which Adam moves by sign: sound largest 0.0096 (the next 0.0066),
    # the control 0.0037 to 0.0065: precision does not move it.  By the
    # contract's rule for such a number it lies between the reading and 1,
    # which a state left unchanged reads, with the room above the reading:
    # ten times over it and ten under 1 (`D x` left out reads 0.134)
    "update_norm_gap": 0.1,
}
# Not the harness's: the adapter's own guard (adapter.py's docstring), one
# Mamba-2 layer's `S_t C_t` by the program's scan against `recurrence`
# below.  Sound 2.8e-3 to 3.65e-3 (15 seeds; bf16 operands and products);
# the scan's running sums computed and kept in bfloat16 4.5e-2 to 6.7e-2
# (10 seeds), the carried state dropped 0.30: 3.3 times over the one, 3.7
# under the next (my chip runs, PR 40, calls 7 and 9).
SCAN_Y_GAP = 1.2e-2

_ROWS = 256          # query rows, recurrence steps, scored rows at a time
_MLP_ROWS = 2048
_RESIDUAL_OUT = ("out_proj", "wo", "w2")    # what writes into the stream
_BASE = ("norm1_w", "norm2_w", "w1", "w2")
LEAVES = {
    "mamba": _BASE + ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                      "gate_norm", "out_proj"),
    "attention": _BASE + ("wqkv", "wo"),
}


def kept_kinds(cfg):
    return [cfg["layer_types"][i] for i in cfg["kept_layers"]]


def sizes(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hs, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return dict(d=d, f=cfg["shared_intermediate_size"], h=h,
                hkv=cfg["num_key_value_heads"], dh=d // h, hs=hs, p=p,
                di=hs * p, g=g, n=n, conv=hs * p + 2 * g * n,
                kc=cfg["mamba_d_conv"])


def weight_shapes(cfg):
    z = sizes(cfg)
    d, f, h, hkv, dh = (z[k] for k in ("d", "f", "h", "hkv", "dh"))
    assert z["di"] == cfg["mamba_expand"] * d
    leaf = {
        "norm1_w": (d,), "norm2_w": (d,), "w1": (d, 2 * f), "w2": (f, d),
        "in_proj": (d, z["di"] + z["conv"] + z["hs"]),
        "conv_w": (z["kc"], z["conv"]), "conv_b": (z["conv"],),
        "dt_bias": (z["hs"],), "A_log": (z["hs"],), "D": (z["hs"],),
        "gate_norm": (z["di"],), "out_proj": (z["di"], d),
        "wqkv": (d, (h + 2 * hkv) * dh), "wo": (h * dh, d)}
    shapes = {"embed": (cfg["vocab_size"], d)}
    for i, kind in enumerate(kept_kinds(cfg)):
        for name in LEAVES[kind]:
            shapes[f"l{i}.{name}"] = leaf[name]
    shapes["final_norm_w"] = (d,)
    return shapes


def make_weights(cfg, key):
    """Flat dict of float32 weights from the key, as config.json's
    ``assumed`` says: matrices and the tied embedding normal(0,
    initializer_range), those that write into the residual stream
    normal(0, residual_out_range); norms at 1; the convolution uniform
    +-1/sqrt(d_conv); ``softplus(dt_bias)`` log-uniform on dt_min..dt_max,
    ``A_log = log(uniform(A_init_range))``, ``D = 1``."""
    z = sizes(cfg)
    out = {}
    for i, (name, shape) in enumerate(weight_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        leaf = name.split(".")[-1]
        if leaf in ("norm1_w", "norm2_w", "final_norm_w", "gate_norm", "D"):
            w = jnp.ones(shape, jnp.float32)
        elif leaf in ("conv_w", "conv_b"):
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
            std = cfg["residual_out_range" if leaf in _RESIDUAL_OUT
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


def recurrence(x, delta, A, Bm, Cm, D, state_dtype=jnp.float32):
    """The recurrence, a position at a time: x [B, T, H, P], delta [B, T,
    H], A and D [H], Bm and Cm [B, T, G, N] -> y [B, T, H, P].  ``_ROWS``
    steps a block, each block under jax.checkpoint (the states of every
    step at once would be 17 GB a layer).  ``state_dtype`` is the
    lower-precision control's: the sums of ``delta A``, their
    exponentials and the carried state rounded to it."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    rows = min(_ROWS, T)
    low = lambda a: a.astype(state_dtype).astype(jnp.float32)

    def step(S, at):
        xt, d, b, c = at            # [B, H, P], [B, H], [B, G, N] twice
        b, c = (jnp.repeat(a, H // G, axis=1) for a in (b, c))
        decay = low(jnp.exp(low(d * A)))
        S = low(decay[..., None, None] * S
                + (d[..., None] * xt)[..., None] * b[:, :, None, :])
        return S, (S * c[:, :, None, :]).sum(-1) + D[:, None] * xt

    def block(S, at):
        return lax.scan(step, S, at)

    blocks = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        T // rows, rows, *a.shape[:1], *a.shape[2:])
    _, y = lax.scan(jax.checkpoint(block), jnp.zeros((B, H, P, N)),
                    tuple(map(blocks, (x, delta, Bm, Cm))))
    return jnp.moveaxis(y.reshape(T, B, H, P), 0, 1)


def scan_operands(u, lw, cfg, quant=lambda a: a):
    """What the recurrence is handed, from the normed stream ``u``: (the
    gate ``z`` [B, T, d_inner], ``x`` [B, T, H, P], ``delta`` [B, T, H],
    ``A`` [H], ``B`` and ``C`` [B, T, G, N], ``D`` [H])."""
    z = sizes(cfg)
    B, T, _ = u.shape
    di, conv = z["di"], z["conv"]
    gate, xBC, dt = jnp.split(_dot(u, lw["in_proj"], quant),
                              (di, di + conv), axis=-1)
    padded = jnp.pad(xBC, ((0, 0), (z["kc"] - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(padded[:, j:j + T] * lw["conv_w"][j]
                          for j in range(z["kc"])) + lw["conv_b"])
    x, Bm, Cm = jnp.split(xBC, (di, di + z["g"] * z["n"]), axis=-1)
    return (gate, x.reshape(B, T, z["hs"], z["p"]),
            jax.nn.softplus(dt + lw["dt_bias"]), -jnp.exp(lw["A_log"]),
            Bm.reshape(B, T, z["g"], z["n"]), Cm.reshape(B, T, z["g"], z["n"]),
            lw["D"])


def _mamba(u, lw, cfg, quant, state_dtype):
    gate, *operands = scan_operands(u, lw, cfg, quant)
    y = recurrence(*operands, state_dtype)
    o = rms_norm(y.reshape(gate.shape) * jax.nn.silu(gate), lw["gate_norm"],
                  cfg["rms_norm_eps"])
    return _dot(o, lw["out_proj"], quant)


def _attention(u, lw, cfg, quant):
    """Causal grouped-query attention at the configuration's own scale,
    ``_ROWS`` query rows at a time."""
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
                       precision=lax.Precision.HIGHEST
                       ) * cfg["attention_multiplier"]
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgrk,bkhd->brhgd", quant(p), v,
                          precision=lax.Precision.HIGHEST)

    first = jnp.broadcast_to(jnp.arange(0, T, rows)[None, :, None],
                             (B, T // rows, rows)).reshape(B, T)
    out = _by_rows(block, rows, q.reshape(B, T, hkv, h // hkv, dh), first)
    return _dot(out.reshape(B, T, h * dh), lw["wo"], quant)


def _layer(lw, x, kind, cfg, quant, state_dtype):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = rms_norm(x, lw["norm1_w"], eps)
    y = (_mamba(u, lw, cfg, quant, state_dtype) if kind == "mamba"
         else _attention(u, lw, cfg, quant))
    x = x + r * y

    def mlp(ub):
        g, up = jnp.split(_dot(ub, lw["w1"], quant), 2, axis=-1)
        return _dot(jax.nn.silu(g) * up, lw["w2"], quant)

    return x + r * _by_rows(mlp, _MLP_ROWS, rms_norm(x, lw["norm2_w"], eps))


def hidden(cfg, w, tokens, quant=lambda a: a, state_dtype=jnp.float32):
    """Token ids -> the final RMSNorm's output [B, T, d]."""
    x = cfg["embedding_multiplier"] * w["embed"][tokens]
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
        logits = _dot(xb, w["embed"].T, quant) / cfg["logits_scaling"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tb[..., None], -1)

    return _by_rows(scored, _ROWS, x, targets).mean()
