"""The configuration through the program's normal path: hvd.init() ->
ParallelMesh(dp=n) -> training.make_llama_train_step with next-token
cross-entropy.  This file maps the published keys onto the program's
mechanisms (a trunk whose layers are of the kinds the model's rule gives
the kept layers, each with its published index; a window; the
state-space sizes; LayerNorm's eps; a tied head over the ids held); the
benchmark supplies the weights (reference.make_weights) and reads the
state back under the reference's names.
"""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import training
from horovod_tpu.models import hybrid, llama
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

_TOP = {"embed": "embed", "final_norm": "final_norm_w",
        "final_norm_bias": "final_norm_b"}      # the program's: the reference's


def kept_kinds(cfg):
    published = hybrid.published_kinds(cfg["published"]["num_hidden_layers"])
    return tuple(published[i] for i in cfg["kept_layers"])


def program_config(cfg):
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        norm_eps=cfg["layer_norm_eps"], max_seq_len=cfg["seq_len"],
        tie_embeddings=cfg["tie_word_embeddings"],
        layer_kinds=kept_kinds(cfg), layer_ids=tuple(cfg["kept_layers"]),
        sliding_window=cfg["sliding_window"],
        ssm_inner=cfg["mamba_expand"] * cfg["hidden_size"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_dt_rank=cfg["mamba_dt_rank"],
        loss_chunk=cfg["loss_chunk"], remat=cfg["remat"],
        remat_policy=cfg["remat_policy"],
        dtype=jnp.dtype(cfg["dtype"]["compute"]),
        param_dtype=jnp.dtype(cfg["dtype"]["params"]))


def key_ranges(cfg, kind, T):
    """The key ranges the program's attention of ``kind`` hands the
    kernels at ``T`` positions."""
    return hybrid.key_ranges(kind, T, program_config(cfg))


def _places(cfg):
    """[(position in the cut, kind, place in the kind's stack)]."""
    seen, out = {}, []
    for n, kind in enumerate(kept_kinds(cfg)):
        out.append((n, kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _to_program(flat, cfg):
    params = {ours: flat[theirs] for ours, theirs in _TOP.items()}
    layers = {}
    for n, kind, _ in _places(cfg):
        for name in hybrid.layer_shapes(program_config(cfg), kind):
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

        def make(k):
            params = _to_program(reference.make_weights(cfg, k), cfg)
            return params, opt.init(params)

        self.init = jax.jit(make, out_shardings=NamedSharding(self.mesh, P()))

    def place(self, samples):
        return tuple(jax.device_put(a, self._data) for a in samples)

    def step(self, state, batch):
        params, opt_state, loss, _ = self._step(*state, batch)
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
