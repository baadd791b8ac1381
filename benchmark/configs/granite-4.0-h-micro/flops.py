"""FLOPs one sample (a row of ``seq_len`` tokens) needs, from the shapes
alone.

Two per multiply-add; matrix products only: the projections and MLPs of
the kept layers, the tied head over every position, attention over the
live (query, key) pairs of the causal mask and no others (a score and a
product with the value: ``2 Dh`` multiply-adds a head and pair), and the
state-space recurrence as the model states it: a head's state takes
``delta_t x_t B_t^T`` (``P N`` multiply-adds a position) and gives ``S_t
C_t`` (``P N`` more).  The chunked form the program computes it in makes
more products than that; they are the kernel's, counted by
:func:`ssd_kernel_cost` and not here.  The convolution, norms, gates and
the decay are no matrix products and count nothing; embedding rows are
gathered; nothing recomputed under remat counts.  Backward is twice
forward.
"""


def _kinds(cfg):
    return [cfg["layer_types"][i] for i in cfg["kept_layers"]]


def _sizes(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hs, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return (d, cfg["shared_intermediate_size"], h, cfg["num_key_value_heads"],
            d // h, hs, p, cfg["mamba_n_groups"], cfg["mamba_d_state"])


def live_pairs(cfg):
    """Live (query, key) pairs of one row under the causal mask."""
    T = cfg["seq_len"]
    return T * (T + 1) // 2


def mixer_params(cfg, kind):
    """The mixer's matrices: what every position meets in a layer."""
    d, _, h, hkv, dh, hs, p, g, n = _sizes(cfg)
    if kind == "mamba":
        return d * (2 * hs * p + 2 * g * n + hs) + hs * p * d
    return d * (h + 2 * hkv) * dh + h * dh * d


def projection_macs(cfg):
    """Mixers' and MLPs' products and the head, all positions."""
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    per_position = sum(mixer_params(cfg, k) + 3 * d * f for k in _kinds(cfg))
    return cfg["seq_len"] * (per_position + d * cfg["vocab_size"])


def attention_macs(cfg):
    _, _, h, _, dh, *_ = _sizes(cfg)
    layers = sum(k == "attention" for k in _kinds(cfg))
    return layers * live_pairs(cfg) * h * 2 * dh


def recurrence_macs(cfg):
    """The state's update and its read-out, a position and head."""
    _, _, _, _, _, hs, p, _, n = _sizes(cfg)
    layers = sum(k == "mamba" for k in _kinds(cfg))
    return layers * cfg["seq_len"] * hs * 2 * p * n


def forward_macs(cfg):
    return projection_macs(cfg) + attention_macs(cfg) + recurrence_macs(cfg)


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def ssd_chunk_macs(cfg):
    """Multiply-adds the chunked form needs for one chunk of one layer,
    forward, at the least: ``C B^T`` once a group (``Q Q N``), and a head
    the masked product with ``X`` (``Q Q P``), the chunk's own state (``Q
    P N``) and the carried state's output (``Q N P``)."""
    _, _, _, _, _, hs, p, g, n = _sizes(cfg)
    q = cfg["mamba_chunk_size"]
    return g * q * q * n + hs * (q * q * p + 2 * q * p * n)


def ssd_kernel_cost(cfg, batch):
    """What the chunked scans of one step need at the least, whole batch,
    the mamba layers: (FLOPs, HBM bytes), from the mathematics and the
    sizes, whatever implements them.  FLOPs: the four products of a
    chunk forward, again where the layer is rerun under remat, and in the
    backward two products for each of them (a product's two operands'
    cotangents); what a backward makes again for itself counts nothing.
    Bytes, each once a pass: forward reads x, B, C (the compute dtype: 2
    bytes) and delta (fp32) and writes y and the state each chunk starts
    from (fp32); backward reads them and dy and writes dx, d delta, dB,
    dC; A, D and their gradients once."""
    _, _, _, _, _, hs, p, g, n = _sizes(cfg)
    T, q = cfg["seq_len"], cfg["mamba_chunk_size"]
    layers = sum(k == "mamba" for k in _kinds(cfg))
    forwards = 2 if cfg["remat"] else 1
    flops = 2 * ssd_chunk_macs(cfg) * (T // q) * (forwards + 2)
    x_like, bc_like = T * hs * p * 2, 2 * T * g * n * 2
    delta_like, states = T * hs * 4, (T // q) * hs * p * n * 4
    forward = x_like + bc_like + delta_like + x_like + states + 2 * hs * 4
    backward = (2 * x_like + bc_like + delta_like + states
                + x_like + delta_like + bc_like + 4 * hs * 4)
    return (layers * batch * flops,
            layers * batch * (forwards * forward + backward))
