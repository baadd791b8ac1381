"""FLOPs one sample (a row of ``seq_len`` tokens) needs, from the shapes
alone.

Two per multiply-add; matrix products only: the kept layers' latent
attention projections (``wq``, ``wkv_a``, ``wkv_b``, ``wo``), the dense
layer's feed-forward, the routers, the shared experts, the routed experts
over the (token, expert) pairs sent to experts this chip holds, at the
expected ``positions x experts_per_tok x held / router_outputs`` for the
model's FLOPs and at the counted pairs for the grouped products' roofline,
the untied head over every position, and attention over the causal (query,
key) pairs at the model's own widths: scores 192 wide (128 + 64), values
128.  Norms, the rotation and the softmax are no matrix products and count
nothing; embedding rows are gathered; nothing recomputed under remat
counts.  Backward is twice forward.
"""


def _layers(cfg):
    """(leading dense layers kept, routed layers kept)."""
    dense = sum(i < cfg["first_k_dense_replace"] for i in cfg["kept_layers"])
    return dense, len(cfg["kept_layers"]) - dense


def live_pairs(cfg):
    """Causal (query, key) pairs of one row: row ``i`` sees ``i + 1``."""
    T = cfg["seq_len"]
    return T * (T + 1) // 2


def attention_params(cfg):
    """wq, wkv_a, wkv_b and wo: what every position meets in a layer's
    attention."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_pairs(cfg):
    """(token, expert) pairs one sample sends to the experts held, a
    layer, under even routing."""
    return (cfg["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["router_outputs"])


def projection_macs(cfg):
    """Attention's, the dense feed-forward's, the routers' and the shared
    experts' products and the head, all positions."""
    d = cfg["hidden_size"]
    dense, routed = _layers(cfg)
    per_position = (
        (dense + routed) * attention_params(cfg)
        + dense * 3 * d * cfg["intermediate_size"]
        + routed * (d * cfg["router_outputs"]
                    + cfg["n_shared_experts"] * expert_params(cfg)))
    return cfg["seq_len"] * (per_position + d * cfg["vocab_size"])


def expert_macs(cfg):
    return _layers(cfg)[1] * expected_pairs(cfg) * expert_params(cfg)


def attention_macs(cfg):
    """QK^T at 192 and PV at 128 over the causal pairs, all heads, every
    kept layer."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return (len(cfg["kept_layers"]) * live_pairs(cfg)
            * cfg["num_attention_heads"] * width)


def forward_macs(cfg):
    return projection_macs(cfg) + expert_macs(cfg) + attention_macs(cfg)


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def mla_flash_kernel_cost(cfg, batch):
    """What latent attention's kernels of one step need at the least,
    whole batch, the kept layers: (FLOPs, HBM bytes).  **The least any
    implementation needs**, not what the two-kernel backward computes:
    forward ``S`` and ``PV`` once (``2 x pairs x 32 x (192 + 128)``),
    backward ``S``, ``dP``, ``dV``, ``dQ``, ``dK`` once each (``2 x pairs x
    32 x (192 + 128 + 128 + 192 + 192)``), at the model's 192 with no
    padded lane, no second ``S`` or ``dP`` of a backward in two kernels.
    This differs from ``mask_flash_kernel_cost``'s 2 + 7 products of the
    other configurations (ROADMAP.md D3), which counts what those kernels
    compute: here a later fused backward, or the form whose rotary part is
    64 lanes and not 128, cannot read over 100%.  Bytes: forward reads q
    (192 a head), k_nope and v (128 a head), k_pe as ONE head of 64, and
    writes o (bf16) and the row statistics (fp32); backward reads those, o,
    do and the statistics and writes dq, dk_nope, dv and dk_pe (one head),
    each once."""
    T, h = cfg["seq_len"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    layers = len(cfg["kept_layers"])
    pairs = live_pairs(cfg) * h * batch * layers
    qk = dn + dr
    flops = 2 * pairs * ((qk + dv) + (qk + dv + dv + qk + qk))
    rows = batch * T * 2                       # bf16 bytes a column
    q, kn, v, kpe = rows * h * qk, rows * h * dn, rows * h * dv, rows * dr
    stats = batch * T * h * 4
    forward = q + kn + kpe + v + v + stats                  # ... o, lse
    backward = (q + kn + kpe + v) + 2 * v + 2 * stats + (q + kn + kpe + v)
    return flops, layers * (forward + backward)


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
    weights = cfg["n_routed_experts"] * 3 * d * f * 2
    rows = pairs * (2 * d + 3 * f) * 2
    return flops, 4 * weights + 3 * rows
