"""The configuration through the program's normal path: hvd.init() ->
ParallelMesh(dp=n) -> training.make_llama_train_step with next-token
cross-entropy.  This file maps the published keys onto the program's
mechanisms (a trunk whose layers are of the kinds ``layer_types`` gives
the kept layers: ``mamba`` is the program's ``mamba2``; RMSNorm; the four
multipliers; the Mamba-2 sizes; a tied head over the ids held); the
benchmark supplies the weights (reference.make_weights) and reads the
state back under the reference's names.

**A guard of this file's own, outside ``correct``.**  The harness compares
a loss and norms of leaves, and those do not move when the chunked scan's
running sums or exponentials run in bfloat16 (PERF.md section 2).  So
``Program.init`` hands one Mamba-2 layer's scan, at the timed sizes and on
the seed's own weights, the operands that the reference's position-by-
position walk gets, prints the relative distance between the two results
and stops the run where it is over ``reference.SCAN_Y_GAP``.  A
``benchmark`` PR can make it a number of ``harness/check.py``.
"""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import training
from horovod_tpu.models import hybrid, llama
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

_TOP = {"embed": "embed", "final_norm": "final_norm_w"}   # the program's: the reference's
_KINDS = {"mamba": "mamba2", "attention": "attention"}    # the source's: the program's


def kept_kinds(cfg):
    kinds = tuple(_KINDS[cfg["layer_types"][i]] for i in cfg["kept_layers"])
    missing = sorted(set(kinds) - set(hybrid.KINDS))
    if missing:
        raise ValueError(f"this program's trunk of several kinds has no "
                         f"{missing}: it knows {hybrid.KINDS}")
    return kinds


def program_config(cfg):
    kinds = kept_kinds(cfg)
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["shared_intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["seq_len"], tie_embeddings=cfg["tie_word_embeddings"],
        layer_kinds=kinds, layer_ids=tuple(cfg["kept_layers"]),
        trunk_norm=cfg["normalization_function"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        ssm_inner=cfg["mamba_n_heads"] * cfg["mamba_d_head"],
        ssm_heads=cfg["mamba_n_heads"], ssm_groups=cfg["mamba_n_groups"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"],
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
        self.cfg, self.reference = cfg, reference
        self.chips, self.mesh = len(devices), pmesh.mesh
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

        self._init = jax.jit(make, out_shardings=NamedSharding(self.mesh, P()))
        self._scan_gap = jax.jit(self._scan_y_gap)

    def init(self, key):
        state = self._init(key)
        gap = float(self._scan_gap(state[0], key))
        limit = self.reference.SCAN_Y_GAP
        print(f"check main scan_y_gap: {gap:.6g} (limit {limit:g}; the "
              "adapter's own guard, outside `correct`)", flush=True)
        if not gap <= limit:        # a NaN too
            raise SystemExit(
                f"the chunked scan is {gap:.6g} of its result away from the "
                f"reference's walk of the recurrence (limit {limit:g})")
        return state

    def _scan_y_gap(self, params, key):
        """``|ssd_scan(...) - recurrence(...)| / |recurrence(...)|`` over
        one row of the first Mamba-2 layer's ``S_t C_t``: the operands are
        the reference's own (float32, from the seed's weights and a row of
        seeded ids), handed to the scan as the trunk hands them (``x``,
        ``B``, ``C`` in the compute dtype; ``delta``, ``A`` in float32).
        ``D`` is 0 on both sides: ``D x`` is most of ``y`` at seeded
        weights and no part of the recurrence, and ``D``'s own gradient is
        a leaf that the harness compares."""
        from horovod_tpu.ops.ssd_scan import ssd_scan
        cfg, ref = self.cfg, self.reference
        n = kept_kinds(cfg).index("mamba2")
        flat = _to_flat(params, cfg)
        lw = {name: flat[f"l{n}.{name}"] for name in ref.LEAVES["mamba"]}
        tokens = jax.random.randint(key, (1, cfg["seq_len"]), 0,
                                    cfg["vocab_size"])
        u = ref.rms_norm(cfg["embedding_multiplier"] * flat["embed"][tokens],
                         lw["norm1_w"], cfg["rms_norm_eps"])
        _, x, delta, A, Bm, Cm, D = ref.scan_operands(u, lw, cfg)
        D = jnp.zeros_like(D)
        want = ref.recurrence(x, delta, A, Bm, Cm, D)
        low = lambda a: a.astype(cfg["dtype"]["compute"])
        got = ssd_scan(low(x), delta, A, low(Bm), low(Cm), D,
                       cfg["mamba_chunk_size"]).astype(jnp.float32)
        return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

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
