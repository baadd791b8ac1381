"""The configuration through the program's normal path: hvd.init() ->
ParallelMesh(dp=n) -> training.make_llama_train_step with next-token
cross-entropy.  This file maps the published keys onto the program's
mechanisms (a trunk whose layers are of the kinds the kept layers'
``layer_types`` give: ``swa``, plain grouped-query attention under
``sliding_window``, and ``attention``, the same layer causal; a rotary
table a kind from ``rope_parameters``; RMSNorm; an untied head over the ids
held; dropless routed experts of which the chip holds a share, scored by a
softmax over all the router's outputs); the benchmark supplies the weights
(reference.make_weights) and reads the state back under the reference's
names, which are the program's.
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

_TOP = {"embed": "embed", "final_norm": "final_norm_w", "head": "head"}   # the program's: the reference's
KINDS = {"sliding_attention": "swa", "full_attention": "attention"}     # the config's: the program's
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


def rope_tables(cfg):
    """``LlamaConfig.rope_tables`` of the kinds kept: the config's
    ``rope_parameters`` a layer type under the program's names."""
    names, kept = {"rope_theta": "theta"}, kept_kinds(cfg)
    return tuple(
        (KINDS[layer_type], llama.RopeTable(
            **{names.get(k, k): v for k, v in parameters.items()}))
        for layer_type, parameters in sorted(cfg["rope_parameters"].items())
        if KINDS[layer_type] in kept)


def program_config(cfg):
    kinds = kept_kinds(cfg)
    if (not cfg["norm_topk_prob"] or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu" or not cfg["use_sliding_window"]
            or any(cfg["mlp_layer_types"][i] != "sparse"
                   for i in cfg["kept_layers"])):
        raise ValueError("this adapter maps the published mellum keys: no "
                         "bias, silu, sliding layers under sliding_window, "
                         "every kept layer sparse, weights normalised over "
                         "the chosen")
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=len(kinds), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["seq_len"], tie_embeddings=cfg["tie_word_embeddings"],
        layer_kinds=kinds, layer_ids=tuple(cfg["kept_layers"]),
        trunk_norm="rmsnorm", sliding_window=cfg["sliding_window"],
        rope_tables=rope_tables(cfg),
        n_experts=cfg["router_outputs"],
        expert_top_k=cfg["num_experts_per_tok"], moe_dispatch="dropless",
        experts_held=cfg["num_experts"], experts_first=cfg["experts_first"],
        router_score="softmax",
        loss_chunk=cfg["loss_chunk"], remat=cfg["remat"],
        remat_policy=cfg["remat_policy"],
        dtype=jnp.dtype(cfg["dtype"]["compute"]),
        param_dtype=jnp.dtype(cfg["dtype"]["params"]))


def _places(cfg):
    """[(position in the cut, kind, place in the kind's stack)]."""
    seen, out = {}, []
    for n, kind in enumerate(kept_kinds(cfg)):
        out.append((n, kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _to_program(flat, cfg):
    params = {ours: flat[theirs] for ours, theirs in _TOP.items()}
    layers, lcfg = {}, program_config(cfg)
    for n, kind, _ in _places(cfg):
        for name in hybrid.layer_shapes(lcfg, kind):
            layers.setdefault(kind, {}).setdefault(name, []).append(
                flat[f"l{n}.{name}"])
    params["layers"] = {kind: {name: jnp.stack(ws) for name, ws in tree.items()}
                        for kind, tree in layers.items()}
    return params


def _to_flat(params, cfg):
    flat = {theirs: params[ours] for ours, theirs in _TOP.items()}
    for n, kind, at in _places(cfg):
        for name, stacked in params["layers"][kind].items():
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
