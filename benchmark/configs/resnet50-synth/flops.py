"""FLOPs one image needs, from the shapes alone.

Two per multiply-add; convolutions and the final matrix multiplication
only (batch norm, ReLU, pooling and the loss are not counted); nothing
recomputed.  Backward is twice forward (one product for the input's
gradient, one for the weight's), less the stem's input gradient, which
nobody needs: the images are not trained.
"""


def conv_shapes(cfg):
    """Every convolution as (name, out_hw, k, cin, cout), then the head."""
    hw = -(-cfg["image_size"] // 2)          # stem, stride 2, SAME
    out = [("stem", hw, 7, cfg["in_channels"], cfg["width"])]
    hw = -(-hw // 2)                         # 3x3 max-pool, stride 2
    cin = cfg["width"]
    bottleneck = cfg["block"] == "bottleneck"
    expand = cfg["expansion"] if bottleneck else 1
    for i, n_blocks in enumerate(cfg["stage_blocks"]):
        cmid = cfg["width"] * 2 ** i
        cout = cmid * expand
        for b in range(n_blocks):
            stride = 2 if (b == 0 and i > 0) else 1
            hw_out = -(-hw // stride)
            name = f"s{i}.b{b}"
            if bottleneck:
                out += [(f"{name}.conv0", hw, 1, cin, cmid),
                        (f"{name}.conv1", hw_out, 3, cmid, cmid),
                        (f"{name}.conv2", hw_out, 1, cmid, cout)]
            else:
                out += [(f"{name}.conv0", hw_out, 3, cin, cmid),
                        (f"{name}.conv1", hw_out, 3, cmid, cout)]
            if b == 0 and (cin != cout or i > 0):
                out.append((f"{name}.proj", hw_out, 1, cin, cout))
            hw, cin = hw_out, cout
    return out, cin


def forward_macs(cfg):
    convs, c_last = conv_shapes(cfg)
    macs = sum(hw * hw * k * k * cin * cout for _, hw, k, cin, cout in convs)
    return macs + c_last * cfg["num_classes"]


def train_flops_per_sample(cfg):
    convs, _ = conv_shapes(cfg)
    _, hw, k, cin, cout = convs[0]
    stem_macs = hw * hw * k * k * cin * cout
    return 2 * (3 * forward_macs(cfg) - stem_macs)
