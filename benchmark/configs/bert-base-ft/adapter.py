"""BERT fine-tune through the program's normal path, as
examples/bert_finetune.py drives it: hvd.mesh() ->
hvd.DistributedOptimizer(optax.adamw) -> bert.make_dp_finetune_step.  The
benchmark supplies the weights (reference.make_weights) and reads the
state back under the reference's names.
"""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import bert

_LAYER_NAMES = {"attn_ln.w": "attn_norm_w", "attn_ln.b": "attn_norm_b",
                "mlp_ln.w": "mlp_norm_w", "mlp_ln.b": "mlp_norm_b"}
_TOP = {"embed.word": "word_embed", "embed.pos": "pos_embed",
        "embed.type": "type_embed", "embed.ln.w": "embed_norm_w",
        "embed.ln.b": "embed_norm_b", "pooler.w": "pooler_w",
        "pooler.b": "pooler_b", "cls.w": "cls_w", "cls.b": "cls_b"}


def _program_config(cfg):
    return bert.BertConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"], norm_eps=cfg["layer_norm_eps"],
        num_labels=cfg["num_labels"], dtype=jnp.dtype(cfg["dtype"]["compute"]),
        param_dtype=jnp.dtype(cfg["dtype"]["params"]), remat=cfg["remat"])


def _layer_keys(flat):
    return sorted({k.split(".", 1)[1] for k in flat if k.startswith("l0.")})


def _to_program(flat, cfg):
    params = {prog: flat[ref] for ref, prog in _TOP.items()}
    params["layers"] = {
        _LAYER_NAMES.get(n, n): jnp.stack(
            [flat[f"l{i}.{n}"] for i in range(cfg["num_hidden_layers"])])
        for n in _layer_keys(flat)}
    return params


def _to_flat(params, cfg):
    flat = {ref: params[prog] for ref, prog in _TOP.items()}
    back = {v: k for k, v in _LAYER_NAMES.items()}
    for prog, stacked in params["layers"].items():
        for i in range(cfg["num_hidden_layers"]):
            flat[f"l{i}.{back.get(prog, prog)}"] = stacked[i]
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
        mesh, axis = hvd.mesh(), hvd.worker_axis()
        if list(mesh.devices.flat) != list(devices):
            raise ValueError("bert.make_dp_finetune_step runs on hvd.mesh(), "
                             "which is every device of the process")
        self.cfg, self.chips, self.mesh = cfg, len(devices), mesh
        self.global_batch = per_chip_batch * self.chips
        bcfg = _program_config(cfg)
        o = cfg["optimizer"]
        opt = hvd.DistributedOptimizer(
            optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                        weight_decay=o["weight_decay"]), axis_name=axis)
        self._step = bert.make_dp_finetune_step(bcfg, mesh, axis, opt)
        self._data = NamedSharding(mesh, P(axis))

        def make(k):
            params = _to_program(reference.make_weights(cfg, k), cfg)
            return params, opt.init(params)

        self.init = jax.jit(make, out_shardings=NamedSharding(mesh, P()))

    def place(self, samples):
        return tuple(jax.device_put(a, self._data) for a in samples)

    def step(self, state, batch):
        params, opt_state, loss = self._step(*state, *batch)
        return (params, opt_state), loss

    def params(self, state):
        return _to_flat(state[0], self.cfg)

    def first_gradient(self, state):
        """Adam's first moment after one step from zero is (1 - b1) g."""
        mu = _find(state[1], "mu")
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["b1"])
        return {k: v * scale for k, v in _to_flat(mu, self.cfg).items()}

    def compiled(self, state, batch):
        return self._step.lower(*state, *batch).compile()


def build(cfg, reference, devices, per_chip_batch):
    return Program(cfg, reference, devices, per_chip_batch)
