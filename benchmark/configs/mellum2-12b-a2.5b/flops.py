"""FLOPs one sample (a row of ``seq_len`` tokens) needs, from the shapes
alone.

Two per multiply-add; matrix products only: the kept layers' attention
projections, the router, the routed experts over the (token, expert) pairs
sent to experts this chip holds, at the expected ``positions x
experts_per_tok x held / router_outputs`` for the model's FLOPs and at the
counted pairs for the grouped products' roofline, the untied head over
every position, and attention over the live (query, key) pairs of each
layer's own mask and no others: a sliding layer's window, a full layer's
causal triangle.  Norms, the rotation and the softmax are no matrix
products and count nothing; embedding rows are gathered; nothing
recomputed under remat counts.  Backward is twice forward.
"""


def _types(cfg):
    return [cfg["layer_types"][i] for i in cfg["kept_layers"]]


def live_pairs(cfg, layer_type):
    """Live (query, key) pairs of one row under the layer type's mask:
    row ``i`` sees ``min(i + 1, window)`` keys under the window, ``i + 1``
    without."""
    T = cfg["seq_len"]
    causal = T * (T + 1) // 2
    if layer_type != "sliding_attention":
        return causal
    w = min(cfg["sliding_window"], T)
    return w * (w + 1) // 2 + (T - w) * w


def attention_params(cfg):
    """q, k, v and o: what every position meets in a layer's attention."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (h + 2 * hkv) * dh + h * dh * d


def expert_params(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_pairs(cfg):
    """(token, expert) pairs one sample sends to the experts held, a
    layer, under even routing."""
    return (cfg["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / cfg["router_outputs"])


def projection_macs(cfg):
    """Attention's and the routers' products and the head, all positions."""
    d = cfg["hidden_size"]
    per_position = len(_types(cfg)) * (attention_params(cfg)
                                       + d * cfg["router_outputs"])
    return cfg["seq_len"] * (per_position + d * cfg["vocab_size"])


def expert_macs(cfg):
    return len(_types(cfg)) * expected_pairs(cfg) * expert_params(cfg)


def attention_macs(cfg):
    """QK^T and PV over each layer's live pairs, all heads."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return sum(2 * live_pairs(cfg, t) * width for t in _types(cfg))


def forward_macs(cfg):
    return projection_macs(cfg) + expert_macs(cfg) + attention_macs(cfg)


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def mask_flash_kernel_cost(cfg, batch):
    """What the masked attention kernels of one step need at the least,
    whole batch, the kept layers: (FLOPs, HBM bytes), as the sdar-30b-a3b
    configuration counts them.  FLOPs over each layer's live pairs only:
    forward QK^T and PV, once a layer (the kernels' out and lse are kept
    by name, so remat does not run them again); backward S (made again in
    each of ``dq`` and ``dkv``: the kernels' own, so it counts here and not
    in the model's FLOPs), dP twice, dV, dK, dQ: 2 + 7 products.  Bytes:
    forward reads q, k, v and writes o (bf16) and the row statistics
    (fp32); backward reads q, k, v, o, do and the statistics and writes
    dq, dk, dv, each once."""
    T = cfg["seq_len"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    pairs = sum(live_pairs(cfg, t) for t in _types(cfg))
    flops = 2 * (2 + 7) * pairs * h * dh * batch
    q_like = batch * T * h * dh * 2
    kv_like = batch * T * hkv * dh * 2
    stats = batch * T * h * 4
    bytes_ = len(_types(cfg)) * ((2 * q_like + 2 * kv_like + stats)
                                 + (4 * q_like + 4 * kv_like + 2 * stats))
    return flops, bytes_


def moe_kernel_cost(cfg, pairs):
    """What the grouped products of ``pairs`` routed (token, expert)
    pairs need at the least, one layer-step: (FLOPs, HBM bytes), as the
    sdar-30b-a3b configuration counts them: forward gate, up, down;
    backward gate and up made again, then two products for each of the
    three: 11 products of pairs x hidden x expert width; the held experts'
    weights read in bf16 forward and twice backward and their gradients
    written once, the rows read and written in bf16."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2 * 11 * pairs * d * f
    weights = cfg["num_experts"] * 3 * d * f * 2
    rows = pairs * (2 * d + 3 * f) * 2
    return flops, 4 * weights + 3 * rows
