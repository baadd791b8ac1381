"""FLOPs one sample (a row of ``seq_len`` tokens) needs, from the shapes
alone.

Two per multiply-add; matrix products only: the kept layers' mixers (a
convolution layer's ``in_proj`` and ``out_proj``, the attention layer's
four projections), the dense layer's feed-forward, the routers, the routed
experts over the (token, expert) pairs sent to experts this chip holds, at
the expected ``positions x experts_per_tok x held / router_outputs`` for
the model's FLOPs and at the counted pairs for the grouped products'
roofline, the tied head over every position, and attention over the live
(query, key) pairs of the causal triangle.  The convolution's three taps a
channel, its two gates, norms, the rotation and the softmax are no matrix
products and count nothing; embedding rows are gathered; nothing
recomputed under remat counts.  Backward is twice forward.
"""


def _types(cfg):
    return [cfg["layer_types"][i] for i in cfg["kept_layers"]]


def routed_layers(cfg):
    """How many of the kept layers have routed experts."""
    return len(cfg["kept_layers"]) - cfg["num_dense_layers"]


def live_pairs(cfg):
    """Live (query, key) pairs of one row under the causal mask."""
    T = cfg["seq_len"]
    return T * (T + 1) // 2


def conv_params(cfg):
    """``in_proj`` and ``out_proj``: what every position meets in a
    convolution layer's mixer as matrix products."""
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def attention_params(cfg):
    """q, k, v and o: what every position meets in the attention layer."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (h + 2 * hkv) * dh + h * dh * d


def dense_params(cfg):
    """A leading layer's SwiGLU: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_pairs(cfg):
    """(token, expert) pairs one sample sends to the experts held, a
    layer, under even routing."""
    return (cfg["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / cfg["router_outputs"])


def projection_macs(cfg):
    """The mixers' products, the dense feed-forward, the routers and the
    head, all positions."""
    d = cfg["hidden_size"]
    mixers = sum(conv_params(cfg) if t == "conv" else attention_params(cfg)
                 for t in _types(cfg))
    per_position = (mixers + cfg["num_dense_layers"] * dense_params(cfg)
                    + routed_layers(cfg) * d * cfg["router_outputs"]
                    + d * cfg["vocab_size"])
    return cfg["seq_len"] * per_position


def expert_macs(cfg):
    return routed_layers(cfg) * expected_pairs(cfg) * expert_params(cfg)


def attention_macs(cfg):
    """QK^T and PV over the live pairs, all heads, the attention layers."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    layers = sum(t == "full_attention" for t in _types(cfg))
    return layers * 2 * live_pairs(cfg) * width


def forward_macs(cfg):
    return projection_macs(cfg) + expert_macs(cfg) + attention_macs(cfg)


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def mask_flash_kernel_cost(cfg, batch):
    """What the masked attention kernels of one step need at the least,
    whole batch, the attention layers: (FLOPs, HBM bytes).  The least any
    implementation needs, as the kanana-2-30b-a3b configuration counts its
    kernels: forward ``S`` and ``PV`` once a layer (the kernels' out and
    lse are kept by name, so remat does not run them again), backward
    ``S``, ``dP``, ``dV``, ``dK``, ``dQ`` once each: 2 + 5 products over
    the live pairs, no second ``S`` or ``dP`` of a backward in two kernels,
    so that whichever kernels run cannot read over 100%.  Bytes: forward
    reads q, k, v and writes o (bf16) and the row statistics (fp32);
    backward reads q, k, v, o, do and the statistics and writes dq, dk, dv,
    each once."""
    T = cfg["seq_len"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    layers = sum(t == "full_attention" for t in _types(cfg))
    flops = 2 * (2 + 5) * live_pairs(cfg) * h * dh * batch * layers
    q_like = batch * T * h * dh * 2
    kv_like = batch * T * hkv * dh * 2
    stats = batch * T * h * 4
    bytes_ = layers * ((2 * q_like + 2 * kv_like + stats)
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


def gated_conv_bytes(cfg, batch):
    """HBM bytes the gated convolution's elementwise chain needs at the
    least, one layer, one forward pass, whole batch: ``B``, ``C`` and ``x``
    read and ``C * conv(B * x)`` written, each once in bf16 (the taps are
    6 KB)."""
    return (3 + 1) * batch * cfg["seq_len"] * cfg["hidden_size"] * 2
