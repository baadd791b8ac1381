"""FLOPs one sample (a row of ``seq_len`` tokens) needs, from the shapes
alone.

Two per multiply-add; matrix products only: the projections and MLPs of
the kept layers, the tied head over every position, and attention over
the live (query, key) pairs of each layer's mask and no others.  A live
pair costs a head pair ``2 Dh`` multiply-adds for its two scores and ``2
x 2 Dh`` for its two products with the pair's value of ``2 Dh``: 384 at
``Dh = 64``, 7,680 over the 20 head pairs.  The selective scan, the
convolution, norms and gates are no matrix products and count nothing;
embedding rows are gathered; nothing recomputed under remat counts.
Backward is twice forward.

Beside them the cost functions of the two kinds of kernel the rooflines
read: what the mathematics needs, whatever implements it.
"""

_ATTENTION = ("window", "full", "cross")


def _kinds(cfg):
    half = cfg["published"]["num_hidden_layers"] // 2
    return [("mamba" if i <= half else "gmu") if i % 2 == 0 else
            "window" if i < half else "full" if i == half + 1 else "cross"
            for i in cfg["kept_layers"]]


def _sizes(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, cfg["intermediate_size"], h, cfg["num_key_value_heads"], \
        d // h, cfg["mamba_expand"] * d


def live_pairs(cfg, kind):
    """Live (query, key) pairs of one row under the kind's mask."""
    T, w = cfg["seq_len"], min(cfg["sliding_window"], cfg["seq_len"])
    if kind == "window":
        return w * (w + 1) // 2 + (T - w) * w
    return T * (T + 1) // 2


def mixer_params(cfg, kind):
    """The mixer's matrices: what every position meets in a layer."""
    d, _, h, hkv, dh, di = _sizes(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    if kind == "mamba":
        return d * 2 * di + di * (r + 2 * n) + r * di + di * d
    if kind == "gmu":
        return 2 * d * di
    if kind == "cross":
        return 2 * d * h * dh
    return d * (h + 2 * hkv) * dh + h * dh * d


def projection_macs(cfg):
    """Mixers' and MLPs' products and the head, all positions."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_position = sum(mixer_params(cfg, k) + 3 * d * f for k in _kinds(cfg))
    return cfg["seq_len"] * (per_position + d * cfg["vocab_size"])


def attention_macs(cfg):
    """Two scores and two products with the pair's value, live pairs only,
    all head pairs of the attention layers."""
    _, _, h, _, dh, _ = _sizes(cfg)
    per_pair = (h // 2) * (2 * dh + 2 * 2 * dh)
    return sum(live_pairs(cfg, k) * per_pair for k in _kinds(cfg)
               if k in _ATTENTION)


def forward_macs(cfg):
    return projection_macs(cfg) + attention_macs(cfg)


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def mask_flash_kernel_cost(cfg, batch):
    """What the attention of one step needs at the least, whole batch, the
    three attention layers: (FLOPs, HBM bytes).  FLOPs over the live pairs
    only; a softmax of a head pair costs forward a score (Dh) and a
    product with the value (2 Dh), backward the score made again in each
    of ``dq`` and ``dkv`` (the kernels' own, so it counts here and not in
    the model's FLOPs), dP twice and dV (2 Dh each), dK and dQ (Dh each):
    13 Dh multiply-adds, two softmaxes a head pair.  Bytes: forward reads
    q, k, v and writes the two softmaxes' outputs (bf16) and row
    statistics (fp32); backward reads q, k, v, the outputs, their
    cotangents and the statistics and writes dq, dk, dv, each once."""
    d, _, h, hkv, dh, _ = _sizes(cfg)
    T = cfg["seq_len"]
    flops = bytes_ = 0
    for kind in _kinds(cfg):
        if kind not in _ATTENTION:
            continue
        flops += 2 * live_pairs(cfg, kind) * (h // 2) * 2 * 13 * dh * batch
        q_like = batch * T * h * dh * 2
        kv_like = batch * T * hkv * dh * 2
        out_like = 2 * batch * T * (h // 2) * 2 * dh * 2
        stats = batch * T * h * 4
        bytes_ += ((q_like + 2 * kv_like + out_like + stats)
                   + (2 * q_like + 4 * kv_like + 2 * out_like + 2 * stats))
    return flops, bytes_


def ssm_scan_kernel_cost(cfg, batch):
    """What the selective scans of one step need at the least, whole
    batch, the Mamba layers: (elementwise operations, HBM bytes).  The
    operations are the recurrence's own, no matrix product: a state
    element a step costs forward an exp and 5 of multiply and add,
    backward (the state made again, then its cotangent) about 3 times
    that; the peaks table prices no vector unit, so only the bytes bound
    a time.  Bytes: forward reads xs (bf16), delta (fp32), B and C (bf16)
    and writes s (bf16); backward reads them and ds and writes dxs,
    ddelta, dB, dC; A, D and their gradients once; each once, a rerun
    under remat not counted."""
    _, _, _, _, _, di = _sizes(cfg)
    n, T = cfg["mamba_d_state"], cfg["seq_len"]
    layers = sum(k == "mamba" for k in _kinds(cfg))
    ops = layers * batch * T * di * n * 6 * 4
    forward = batch * T * (di * (2 + 4 + 2) + 2 * n * 2) + di * (n + 1) * 4
    backward = (batch * T * (di * (2 + 4 + 2 + 2 + 4) + 4 * n * 2)
                + 2 * di * (n + 1) * 4)
    return ops, layers * (forward + backward)
