"""FLOPs one sample (a sequence of ``seq_len`` tokens, read as [xt ; x0],
2 x seq_len positions) needs, from the shapes alone.

Two per multiply-add; matrix products only.  Attention counts the live
(query, key) pairs of the block-diffusion mask and no others; the experts
count the (token, expert) pairs routed to experts this chip holds, at the
expected ``positions x experts_per_tok x held / router_outputs`` for the
model's FLOPs and at the counted pairs for the grouped products' roofline;
the head counts the ``seq_len`` scored positions.  Embedding rows are
gathered and count nothing; nothing recomputed under remat counts.
Backward is twice forward.
"""


def live_pairs(cfg):
    """Live (query, key) pairs of one sample's [2L, 2L] mask: a token of
    xt sees its block (Bk) and the clean tokens of earlier blocks; a
    token of x0 sees its own and earlier blocks."""
    L, bk = cfg["seq_len"], cfg["block_length"]
    blocks = L // bk
    earlier = bk * bk * blocks * (blocks - 1) // 2      # sum over blocks b of b * Bk, x Bk rows
    return L * bk + earlier + (earlier + L * bk)


def attention_macs(cfg):
    """QK^T and PV over the live pairs, all heads and layers."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * live_pairs(cfg) * width


def projection_params(cfg):
    """q, k, v, o and the router: what every position meets in a layer."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * h * dh + 2 * d * hkv * dh + d * cfg["router_outputs"]


def expert_params(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_pairs(cfg):
    """(token, expert) pairs one sample sends to the experts held, a
    layer, under even routing."""
    return (2 * cfg["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / cfg["router_outputs"])


def forward_macs(cfg):
    positions = 2 * cfg["seq_len"]
    layers = cfg["num_hidden_layers"]
    head = cfg["seq_len"] * cfg["hidden_size"] * cfg["vocab_size"]
    return (layers * (positions * projection_params(cfg)
                      + expected_pairs(cfg) * expert_params(cfg))
            + attention_macs(cfg) + head)


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def tile_pairs(cfg, block=512):
    """(query, key) pairs inside the tiles the masked kernels visit, one
    sample and head: tiles of ``block`` that hold a live pair, counted
    whole (a mixed tile's products are made for all of it)."""
    L = cfg["seq_len"]
    n = L // block
    live_tiles = n + 2 * n * (n + 1) // 2   # xt's own; xt on x0; x0 on x0
    return live_tiles * block * block


def mask_flash_kernel_cost(cfg, batch):
    """What the masked attention kernels of one step need at the least,
    whole batch, all layers: (FLOPs, HBM bytes).  FLOPs over the live
    pairs only: forward QK^T and PV; backward S (made again in each of
    ``dq`` and ``dkv``: the kernels' own, so it counts here and not in
    the model's FLOPs), dP twice, dV, dK, dQ: 2 + 7 products.  Bytes:
    forward reads q, k, v and writes o (bf16) and the row statistics
    (fp32); backward reads q, k, v, o, do and the statistics and writes
    dq, dk, dv, each once."""
    positions = 2 * cfg["seq_len"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    flops = 2 * (2 + 7) * live_pairs(cfg) * h * dh * layers * batch
    q_like = batch * positions * h * dh * 2
    kv_like = batch * positions * hkv * dh * 2
    stats = batch * positions * h * 4
    bytes_ = layers * ((2 * q_like + 2 * kv_like + stats)
                       + (4 * q_like + 4 * kv_like + 2 * stats))
    return flops, bytes_


def moe_kernel_cost(cfg, pairs):
    """What the grouped products of ``pairs`` routed (token, expert)
    pairs need at the least, one layer-step: (FLOPs, HBM bytes).  Forward
    gate, up, down; backward gate and up made again, then two products
    for each of the three (inputs' and weights' gradients): 11 products
    of pairs x hidden x expert width.  Bytes: the held experts' weights
    read in bf16 forward and twice backward and their gradients written
    once, the rows read and written in bf16."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2 * 11 * pairs * d * f
    weights = cfg["num_experts"] * 3 * d * f * 2
    rows = pairs * (2 * d + 3 * f) * 2
    return flops, 4 * weights + 3 * rows
