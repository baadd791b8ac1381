"""FLOPs one sample (a row of ``seq_len`` tokens) needs, from the shapes
alone.

Two per multiply-add; matrix products only: the projections of the kept
layers' mixers (the chip's share of the heads), the router, the shared
expert, the routed experts over the (token, expert) pairs sent to experts
this chip holds, at the expected ``positions x experts_per_tok x held /
router_outputs`` for the model's FLOPs, the untied head over every
position, attention over the live (query, key) pairs of the causal mask
and no others, and the delta rule as the model states it: a head's state
is read by the key (``K V`` multiply-adds a position), takes the rank-one
update (``K V``) and is read by the query (``K V``).  The chunked form the
program computes it in makes other products than that; they are the
kernel's, counted by :func:`kda_kernel_cost` and not here.  The
convolution, norms, gates and the decay are no matrix products and count
nothing; embedding rows are gathered; nothing recomputed under remat
counts.  Backward is twice forward.
"""


def _kinds(cfg):
    return ["attention" if i in cfg["gqa_layers"] else "kda"
            for i in cfg["kept_layers"]]


def _kda(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def live_pairs(cfg):
    """Live (query, key) pairs of one row under the causal mask."""
    T = cfg["seq_len"]
    return T * (T + 1) // 2


def mixer_params(cfg, kind):
    """The mixer's matrices: what every position meets in a layer."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    if kind == "kda":
        hs, dk = _kda(cfg)
        return (d * 3 * hs * dk + hs * dk * d          # q, k, v and o
                + 2 * (d * dk + dk * hs * dk)          # the two low-rank pairs
                + d * hs)                              # beta
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (h + 2 * hkv) * dh + 2 * h * dh * d     # q, k, v; gate and o


def expert_params(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_pairs(cfg):
    """(token, expert) pairs one sample sends to the experts held, a
    layer, under even routing."""
    return (cfg["seq_len"] * cfg["num_experts_per_tok"]
            * cfg["n_routed_experts"] / cfg["router_outputs"])


def projection_macs(cfg):
    """Mixers', routers' and shared experts' products and the head, all
    positions."""
    d = cfg["hidden_size"]
    every_layer = (d * cfg["router_outputs"]
                   + cfg["n_shared_experts"] * expert_params(cfg))
    per_position = sum(mixer_params(cfg, k) + every_layer for k in _kinds(cfg))
    return cfg["seq_len"] * (per_position + d * cfg["vocab_size"])


def expert_macs(cfg):
    return len(_kinds(cfg)) * expected_pairs(cfg) * expert_params(cfg)


def attention_macs(cfg):
    layers = sum(k == "attention" for k in _kinds(cfg))
    return (layers * live_pairs(cfg) * cfg["num_attention_heads"]
            * 2 * cfg["head_dim"])


def recurrence_macs(cfg):
    """The state read by the key, updated, read by the query: a position
    and head."""
    hs, dk = _kda(cfg)
    layers = sum(k == "kda" for k in _kinds(cfg))
    return layers * cfg["seq_len"] * hs * 3 * dk * dk


def forward_macs(cfg):
    return (projection_macs(cfg) + expert_macs(cfg) + attention_macs(cfg)
            + recurrence_macs(cfg))


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def kda_chunk_macs(cfg):
    """Multiply-adds the chunked form needs for one chunk of one head,
    forward, at the least: the two ``[C, C]`` score tiles (``k k^T`` and ``q
    k^T`` under the decay, ``C C K`` each), the pseudo-values ``U`` (``C C
    V``), one more product of a ``[C, C]`` tile (the WY form's ``W = T (K
    exp(G))``, or in its place ``Aqk U``, as ops/kda_scan.py has it: ``C C
    K`` and ``C C V`` are the same number here), and the three products
    with the state (``C K V`` each): into the pseudo-values, into the
    output, and the chunk's own into the next state.  The triangular
    inverse is no matrix product and counts nothing."""
    _, dk = _kda(cfg)
    c = cfg["kda_chunk_size"]
    return 2 * c * c * dk + 2 * c * c * dk + 3 * c * dk * dk


def kda_kernel_cost(cfg, batch):
    """What the chunked scans of one step need at the least, whole batch,
    the kda layers: (FLOPs, HBM bytes), from the mathematics and the
    sizes, whatever implements them.  FLOPs: a chunk's products forward,
    again where the layer is rerun under remat, and in the backward two
    products for each of them (a product's two operands' cotangents); what
    a backward makes again for itself counts nothing.  Bytes, each once a
    pass: forward reads q, k, v (the compute dtype: 2 bytes), g and beta
    (fp32) and writes o and the state each chunk starts from (fp32);
    backward reads them and do and writes dq, dk, dv, dg, dbeta."""
    hs, dk = _kda(cfg)
    T, c = cfg["seq_len"], cfg["kda_chunk_size"]
    layers = sum(k == "kda" for k in _kinds(cfg))
    forwards = 2 if cfg["remat"] else 1
    flops = 2 * kda_chunk_macs(cfg) * hs * (T // c) * (forwards + 2)
    wide, g_like = T * hs * dk * 2, T * hs * dk * 4
    beta_like, states = T * hs * 4, (T // c) * hs * dk * dk * 4
    forward = 3 * wide + g_like + beta_like + wide + states
    backward = (4 * wide + g_like + beta_like + states
                + 3 * wide + g_like + beta_like)
    return (layers * batch * flops,
            layers * batch * (forwards * forward + backward))


def moe_kernel_cost(cfg, pairs):
    """What the grouped products of ``pairs`` routed (token, expert)
    pairs need at the least, one layer-step: (FLOPs, HBM bytes), as the
    sdar-30b-a3b configuration counts them: 11 products of pairs x hidden x
    expert width; the held experts' weights read in bf16 forward and twice
    backward and their gradients written once, the rows read and written
    in bf16."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2 * 11 * pairs * d * f
    weights = cfg["n_routed_experts"] * 3 * d * f * 2
    rows = pairs * (2 * d + 3 * f) * 2
    return flops, 4 * weights + 3 * rows
