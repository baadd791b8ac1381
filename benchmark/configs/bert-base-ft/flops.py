"""FLOPs one sequence needs, from the shapes alone.

Two per multiply-add; the matrix multiplications of each layer, the
attention scores and their product with the values, the pooler and the
classifier.  Embedding rows are gathered, not multiplied, and count
nothing; nothing recomputed under remat counts.  Backward is twice
forward: the embeddings are trained, so the first layer's input gradient
is needed too.
"""


def matmul_params(cfg):
    """Parameters that meet every token in a matrix multiplication."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f)


def attention_macs(cfg, seq_len=None):
    """QK^T and PV of one sequence, all layers: T*T*D each."""
    t = seq_len or cfg["seq_len"]
    return cfg["num_hidden_layers"] * 2 * t * t * cfg["hidden_size"]


def forward_macs(cfg, seq_len=None):
    t = seq_len or cfg["seq_len"]
    d = cfg["hidden_size"]
    head = d * d + d * cfg["num_labels"]          # pooler, classifier: [CLS] only
    return t * matmul_params(cfg) + attention_macs(cfg, t) + head


def train_flops_per_sample(cfg):
    return 2 * 3 * forward_macs(cfg)


def flash_kernel_cost(cfg, batch, seq_len=None):
    """What the attention kernels of one step need at the least, whole
    batch, all layers: (FLOPs, HBM bytes).

    Forward reads q, k, v and writes o (bf16) and the row statistics
    (fp32); backward reads q, k, v, o, do and the statistics and writes
    dq, dk, dv.  FLOPs: forward QK^T and PV; backward S, dP, dV, dK, dQ,
    five products of the same size, S recomputed once because the
    algorithm never stores it (that recompute is the kernel's own, so it
    counts here and not in the model's FLOPs).
    """
    t = seq_len or cfg["seq_len"]
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    heads = cfg["num_attention_heads"]
    one = t * t * d                               # one T x T x D product, in MACs
    flops = 2 * (2 + 5) * one * layers * batch
    act = batch * t * d * 2                       # one bf16 [B, T, D] array
    stats = batch * heads * t * 4
    bytes_ = layers * ((4 * act + stats) + (8 * act + 2 * stats))
    return flops, bytes_
