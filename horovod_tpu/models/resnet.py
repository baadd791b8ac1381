"""ResNet v1.5 family — the reference's headline benchmark model.

Reference parity: ``examples/pytorch/pytorch_synthetic_benchmark.py`` and
the published scaling-efficiency table (SURVEY.md §6) benchmark ResNet-50
data-parallel; this is the TPU-native counterpart.  Design choices for the
MXU/HBM (not a torchvision translation):

  * NHWC layout — the TPU-native convolution layout (channels minor, lane
    dimension 128), vs. torch's NCHW.
  * bf16 activations/compute, fp32 parameters and batch-norm statistics.
  * SyncBatchNorm over the dp axis is the default in distributed training
    (one fused psum of all [sum, sq_sum] pairs per block — the reference
    ships it as an opt-in module; here cross-shard stats are a flag).
  * Zero-init of each residual block's last BN scale (the standard
    large-batch recipe the reference's examples rely on externally).

Params and BN running stats are separate pytrees with identical structure
(``init() -> (params, state)``); ``forward`` is pure and returns the
updated state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.sync_batch_norm import sync_batch_norm
from ..scopes import SCOPE_HEAD, SCOPE_STAGE, SCOPE_STEM

# variant → (block counts per stage, bottleneck?)
VARIANTS = {
    18: ((2, 2, 2, 2), False),
    34: ((3, 4, 6, 3), False),
    50: ((3, 4, 6, 3), True),
    101: ((3, 4, 23, 3), True),
    152: ((3, 8, 36, 3), True),
}


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    variant: int = 50
    num_classes: int = 1000
    width: int = 64              # stem channels; stages use width * 2^i
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    dtype: Any = jnp.bfloat16    # activation/compute dtype (MXU-native)

    @property
    def stage_blocks(self):
        return VARIANTS[self.variant][0]

    @property
    def bottleneck(self) -> bool:
        return VARIANTS[self.variant][1]


def _conv(x, w, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn_init(c, zero_scale=False):
    params = {"scale": jnp.zeros(c, jnp.float32) if zero_scale
              else jnp.ones(c, jnp.float32),
              "bias": jnp.zeros(c, jnp.float32)}
    state = {"mean": jnp.zeros(c, jnp.float32),
             "var": jnp.ones(c, jnp.float32)}
    return params, state


def _conv_init(rng, kh, kw, cin, cout):
    # He-normal, fan_out (matches the reference examples' init recipe)
    std = (2.0 / (kh * kw * cout)) ** 0.5
    return jax.random.normal(rng, (kh, kw, cin, cout), jnp.float32) * std


def _block_init(rng, cin, cmid, cout, bottleneck, project):
    keys = jax.random.split(rng, 4)
    p, s = {}, {}
    if bottleneck:
        convs = [(1, cin, cmid), (3, cmid, cmid), (1, cmid, cout)]
    else:
        convs = [(3, cin, cmid), (3, cmid, cout)]
    for i, (k, ci, co) in enumerate(convs):
        p[f"conv{i}"] = _conv_init(keys[i], k, k, ci, co)
        p[f"bn{i}"], s[f"bn{i}"] = _bn_init(co, zero_scale=(i == len(convs) - 1))
    if project:
        p["proj"] = _conv_init(keys[3], 1, 1, cin, cout)
        p["proj_bn"], s["proj_bn"] = _bn_init(cout)
    return p, s


def init(cfg: ResNetConfig, rng) -> Tuple[dict, dict]:
    """Build the (params, batch_stats) pytree pair."""
    n_stages = len(cfg.stage_blocks)
    keys = jax.random.split(rng, 2 + n_stages)
    params: dict = {"stem": _conv_init(keys[0], 7, 7, 3, cfg.width)}
    state: dict = {}
    params["stem_bn"], state["stem_bn"] = _bn_init(cfg.width)
    cin = cfg.width
    expand = 4 if cfg.bottleneck else 1
    for i, n_blocks in enumerate(cfg.stage_blocks):
        cmid = cfg.width * (2 ** i)
        cout = cmid * expand
        bkeys = jax.random.split(keys[2 + i], n_blocks)
        blocks_p, blocks_s = [], []
        for b in range(n_blocks):
            project = b == 0 and (cin != cout or i > 0)
            bp, bs = _block_init(bkeys[b], cin, cmid, cout, cfg.bottleneck,
                                 project)
            blocks_p.append(bp)
            blocks_s.append(bs)
            cin = cout
        params[f"stage{i}"] = blocks_p
        state[f"stage{i}"] = blocks_s
    fc_std = cin ** -0.5
    params["fc"] = {
        "w": jax.random.normal(keys[1], (cin, cfg.num_classes),
                               jnp.float32) * fc_std,
        "b": jnp.zeros(cfg.num_classes, jnp.float32)}
    return params, state


def _bn(x, p, s, cfg, train, axis_name):
    y, mean, var = sync_batch_norm(
        x, p["scale"], p["bias"], s["mean"], s["var"], axis_name=axis_name,
        train=train, momentum=cfg.bn_momentum, eps=cfg.bn_eps)
    return y, {"mean": mean, "var": var}


def _block(x, p, s, cfg, stride, train, axis_name):
    ns = {}
    shortcut = x
    if "proj" in p:
        shortcut = _conv(x, p["proj"], stride)
        shortcut, ns["proj_bn"] = _bn(shortcut, p["proj_bn"], s["proj_bn"],
                                      cfg, train, axis_name)
    y = x
    n_convs = 3 if cfg.bottleneck else 2
    for i in range(n_convs):
        # v1.5: the stride sits on the 3x3 conv (index 1 for bottleneck,
        # index 0 for basic blocks)
        st = stride if i == (1 if cfg.bottleneck else 0) else 1
        y = _conv(y, p[f"conv{i}"], st)
        y, ns[f"bn{i}"] = _bn(y, p[f"bn{i}"], s[f"bn{i}"], cfg, train,
                              axis_name)
        if i < n_convs - 1:
            y = jax.nn.relu(y)
    return jax.nn.relu(y + shortcut), ns


def forward(params, state, images, cfg: ResNetConfig, train: bool = True,
            axis_name: Optional[str] = None):
    """images: [B, H, W, 3] (any float dtype) → (logits fp32 [B, classes],
    new_state).  ``axis_name``: dp axis for synchronized batch norm."""
    new_state = {}
    with jax.named_scope(SCOPE_STEM):
        x = images.astype(cfg.dtype)
        x = _conv(x, params["stem"], 2)
        x, new_state["stem_bn"] = _bn(x, params["stem_bn"], state["stem_bn"],
                                      cfg, train, axis_name)
        x = jax.nn.relu(x)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for i in range(len(cfg.stage_blocks)):
        blocks_ns = []
        with jax.named_scope(SCOPE_STAGE.format(i)):
            for b, (bp, bs) in enumerate(zip(params[f"stage{i}"],
                                             state[f"stage{i}"])):
                stride = 2 if (b == 0 and i > 0) else 1
                x, bns = _block(x, bp, bs, cfg, stride, train, axis_name)
                blocks_ns.append(bns)
        new_state[f"stage{i}"] = blocks_ns
    with jax.named_scope(SCOPE_HEAD):
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        logits = x @ params["fc"]["w"] + params["fc"]["b"]
    return logits, new_state


def num_params(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
