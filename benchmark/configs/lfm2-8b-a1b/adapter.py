"""The configuration through the program's normal path: hvd.init() ->
ParallelMesh(dp=n) -> training.make_llama_train_step with next-token
cross-entropy.  This file maps the published keys onto the program's
mechanisms (a trunk whose layers are of the kinds the kept layers'
``layer_types`` give: ``conv``, the gated short convolution ``conv_L_cache``
taps wide, and ``attention``, plain grouped-query attention with q/k norm
under a plain rotary table; ``num_dense_layers`` leading layers with the
dense feed-forward, the rest dropless routed experts of which the chip
holds a share, scored by a sigmoid, chosen with a bias and weighed over
their sum plus ``router_eps``; RMSNorm; the head tied to the embedding over
the ids held); the benchmark supplies the weights (reference.make_weights)
and reads the state back under the reference's names.

**Leaves.**  The reference keeps ``transformers``' matrices apart: ``wq``,
``wk``, ``wv`` of the attention layer and ``w1`` (gate), ``w3`` (up) of the
dense feed-forward.  The program holds each group as the columns of one
matrix (``wqkv``; its fused gate/up ``w1``): ``_JOINED`` joins them on the
way in and cuts them apart on the way out, exact both ways.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import training
from horovod_tpu.models import hybrid, llama, moe
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

_TOP = {"embed": "embed", "final_norm": "final_norm_w"}   # the program's: the reference's
KINDS = {"conv": "conv", "full_attention": "attention"}    # the config's: the program's
# the program's leaf: the reference's leaves that are its columns, in order
_JOINED = {"wqkv": ("wq", "wk", "wv"), "w1": ("w1", "w3")}
# a step's routing statistics are recorded this many steps later, when its
# arrays are long ready: fetching them then does not stall the queue
_STATS_LAG = 8


def kept_kinds(cfg):
    kinds = tuple(KINDS[cfg["layer_types"][i]] for i in cfg["kept_layers"])
    missing = sorted(set(kinds) - set(hybrid.KINDS))
    if missing:
        raise ValueError(f"this program's trunk of several kinds has no "
                         f"{missing}: it knows {hybrid.KINDS}")
    return kinds


def program_config(cfg):
    kinds = kept_kinds(cfg)
    if (not cfg["norm_topk_prob"] or cfg["conv_bias"]
            or not cfg["use_expert_bias"] or not cfg["tie_word_embeddings"]
            or cfg["head_dim"] * cfg["num_attention_heads"]
            != cfg["hidden_size"]):
        raise ValueError("this adapter maps the published lfm2_moe keys: no "
                         "bias in the convolution, a selection bias in the "
                         "router, weights normalised over the chosen, a tied "
                         "head, heads of hidden_size / num_attention_heads")
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=len(kinds), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        first_dense_layers=cfg["num_dense_layers"], norm_eps=cfg["norm_eps"],
        max_seq_len=cfg["seq_len"], tie_embeddings=cfg["tie_word_embeddings"],
        layer_kinds=kinds, layer_ids=tuple(cfg["kept_layers"]),
        trunk_norm="rmsnorm", ssm_conv=cfg["conv_L_cache"],
        qk_norm=cfg["qk_norm"],
        rope_tables=(("attention", llama.RopeTable(theta=cfg["rope_theta"])),),
        n_experts=cfg["router_outputs"],
        expert_top_k=cfg["num_experts_per_tok"], moe_dispatch="dropless",
        experts_held=cfg["num_experts"], experts_first=cfg["experts_first"],
        router_score="sigmoid",
        routed_scaling_factor=cfg["routed_scaling_factor"],
        router_eps=cfg["router_eps"],
        loss_chunk=cfg["loss_chunk"], remat=cfg["remat"],
        remat_policy=cfg["remat_policy"],
        dtype=jnp.dtype(cfg["dtype"]["compute"]),
        param_dtype=jnp.dtype(cfg["dtype"]["params"]))


def _cuts(cfg):
    """{the program's joined leaf: the columns at which its parts end}."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return {"wqkv": (h * dh, (h + hkv) * dh),
            "w1": (cfg["intermediate_size"],)}


def _places(cfg):
    """[(position in the cut, its stack in the program's tree, its kind,
    whether it is a leading dense layer, its place in the stack)]."""
    seen, out = {}, []
    for n, kind in enumerate(kept_kinds(cfg)):
        dense = n < cfg["num_dense_layers"]
        stack = ("dense_" if dense else "") + kind
        out.append((n, stack, kind, dense, seen.get(stack, 0)))
        seen[stack] = seen.get(stack, 0) + 1
    return out


def _to_program(flat, cfg):
    params = {ours: flat[theirs] for ours, theirs in _TOP.items()}
    layers, lcfg = {}, program_config(cfg)
    for n, stack, kind, dense, _ in _places(cfg):
        for name in hybrid.layer_shapes(lcfg, kind, dense):
            w = (jnp.concatenate([flat[f"l{n}.{part}"]
                                  for part in _JOINED[name]], axis=1)
                 if name in _JOINED
                 else flat[f"l{n}.{name}"])
            layers.setdefault(stack, {}).setdefault(name, []).append(w)
    params["layers"] = {stack: {name: jnp.stack(ws)
                                for name, ws in tree.items()}
                        for stack, tree in layers.items()}
    return params


def _to_flat(params, cfg):
    flat = {theirs: params[ours] for ours, theirs in _TOP.items()}
    cuts = _cuts(cfg)
    for n, stack, _, _, at in _places(cfg):
        for name, stacked in params["layers"][stack].items():
            if name in _JOINED:
                for part, w in zip(_JOINED[name],
                                   jnp.split(stacked[at], cuts[name], axis=1)):
                    flat[f"l{n}.{part}"] = w
            else:
                flat[f"l{n}.{name}"] = stacked[at]
    return flat


def _find(tree, attr):
    """The first node of an optimizer state that has ``attr``."""
    if hasattr(tree, attr):
        return getattr(tree, attr)
    if isinstance(tree, (tuple, list)):
        for child in tree:
            found = _find(child, attr)
            if found is not None:
                return found
    return None


class Program:
    """``init(key)`` makes the state on the device from the seed in one
    jitted call; ``step(state, batch)`` is the program's compiled step."""

    def __init__(self, cfg, reference, devices, per_chip_batch):
        pmesh = ParallelMesh(MeshConfig(dp=len(devices)), devices=devices)
        self.cfg, self.chips, self.mesh = cfg, len(devices), pmesh.mesh
        self.global_batch = per_chip_batch * self.chips
        o = cfg["optimizer"]
        opt = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"])

        def objective(params, batch, lcfg, par):
            tokens, targets = batch
            return llama.loss_fn(params, tokens, targets, lcfg, par,
                                 with_stats=True)

        self._step = training.make_llama_train_step(
            program_config(cfg), pmesh, opt, objective=objective).step_fn
        self._data = NamedSharding(self.mesh, P("dp"))
        self._stats = collections.deque()

        def make(k):
            params = _to_program(reference.make_weights(cfg, k), cfg)
            return params, opt.init(params)

        self.init = jax.jit(make, out_shardings=NamedSharding(self.mesh, P()))

    def place(self, samples):
        return tuple(jax.device_put(a, self._data) for a in samples)

    def step(self, state, batch):
        params, opt_state, loss, stats = self._step(*state, batch)
        self._stats.append(stats)
        if len(self._stats) > _STATS_LAG:
            moe.record_routing(np.asarray(self._stats.popleft()))
        return (params, opt_state), loss

    def params(self, state):
        return _to_flat(state[0], self.cfg)

    def first_gradient(self, state):
        """Adam's first moment after one step from zero is (1 - b1) g."""
        mu = _find(state[1], "mu")
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["b1"])
        return {k: v * scale for k, v in _to_flat(mu, self.cfg).items()}

    def compiled(self, state, batch):
        return self._step.lower(*state, batch).compile()


def build(cfg, reference, devices, per_chip_batch):
    return Program(cfg, reference, devices, per_chip_batch)
