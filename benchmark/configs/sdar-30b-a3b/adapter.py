"""The configuration through the program's normal path: hvd.init() ->
ParallelMesh(dp=n) -> training.make_llama_train_step with the
block-diffusion objective.  This file maps the published keys onto the
program's mechanisms (a head width of its own, q/k norm, an untied head,
dropless routed experts of which the chip holds a share, a mask by key
ranges, positions per token, weights per scored position); the benchmark
supplies the weights (reference.make_weights) and reads the state back
under the reference's names.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import training
from horovod_tpu.models import llama, moe
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

_TOP = ("embed", "final_norm", "head")
# a step's routing statistics are recorded this many steps later, when its
# arrays are long ready: fetching them then does not stall the queue
_STATS_LAG = 8


def mask_ranges(L, bk):
    """``[2L, 4]`` key ranges of [xt ; x0] (ops/flash_attention.py): a
    token of xt sees xt in its own block and x0 in earlier blocks; a token
    of x0 sees x0 in its own and earlier blocks."""
    block = np.arange(L) // bk
    r = np.zeros((2 * L, 4), np.int32)
    r[:L, 0], r[:L, 1] = block * bk, (block + 1) * bk
    r[:L, 2], r[:L, 3] = L, L + block * bk
    r[L:, 0], r[L:, 1] = L, L + (block + 1) * bk
    return r


def program_config(cfg):
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], max_seq_len=2 * cfg["seq_len"],
        qk_norm=True, tie_embeddings=cfg["tie_word_embeddings"],
        n_experts=cfg["router_outputs"],
        expert_top_k=cfg["num_experts_per_tok"], moe_dispatch="dropless",
        experts_held=cfg["num_experts"], experts_first=cfg["experts_first"],
        loss_chunk=cfg["loss_chunk"], remat=cfg["remat"],
        remat_policy=cfg["remat_policy"],
        dtype=jnp.dtype(cfg["dtype"]["compute"]),
        param_dtype=jnp.dtype(cfg["dtype"]["params"]))


def _to_program(flat, cfg):
    names = sorted({k.split(".", 1)[1] for k in flat if k.startswith("l0.")})
    params = {n: flat[n] for n in _TOP}
    params["layers"] = {
        n: jnp.stack([flat[f"l{i}.{n}"]
                      for i in range(cfg["num_hidden_layers"])])
        for n in names}
    return params


def _to_flat(params, cfg):
    flat = {n: params[n] for n in _TOP}
    for n, stacked in params["layers"].items():
        for i in range(cfg["num_hidden_layers"]):
            flat[f"l{i}.{n}"] = stacked[i]
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
        mask = mask_ranges(cfg["seq_len"], cfg["block_length"])

        def objective(params, batch, lcfg, par):
            tokens, positions, targets, weights = batch
            return llama.loss_fn(params, tokens, targets, lcfg, par,
                                 positions=positions, mask=mask,
                                 weights=weights, with_stats=True)

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
