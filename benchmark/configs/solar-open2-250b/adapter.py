"""The configuration through the program's normal path: hvd.init() ->
ParallelMesh(dp=n) -> training.make_llama_train_step with next-token
cross-entropy.  This file maps the published keys onto the program's
mechanisms (a trunk whose layers are of the kinds ``gqa_layers`` gives the
kept layers: ``attention`` with its gate, else ``kda``; RMSNorm; an untied
head over the ids held; dropless routed experts of which the chip holds a
share, scored by a sigmoid and chosen with a bias, beside a shared one;
heads as the chip's share); the benchmark supplies the weights
(reference.make_weights) and reads the state back under the reference's
names, which are the program's.

**A guard of this file's own, outside ``correct``.**  The harness compares
a loss and norms of leaves, and those do not move when a recurrence's
running sums or exponentials run in bfloat16 (PERF.md section 2, question
21).  So ``Program.init`` hands one KDA layer's scan, at the timed sizes
and on the seed's own weights, the operands that the reference's position-
by-position walk gets, prints the relative distance between the two
results (``kda_o_gap``) and stops the run where it is over
``reference.KDA_O_GAP``.  A ``benchmark`` PR can make it a number of
``harness/check.py``.
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
# a step's routing statistics are recorded this many steps later, when its
# arrays are long ready: fetching them then does not stall the queue
_STATS_LAG = 8


def kept_kinds(cfg):
    kinds = tuple("attention" if i in cfg["gqa_layers"] else "kda"
                  for i in cfg["kept_layers"])
    missing = sorted(set(kinds) - set(hybrid.KINDS))
    if missing:
        raise ValueError(f"this program's trunk of several kinds has no "
                         f"{missing}: it knows {hybrid.KINDS}")
    return kinds


def program_config(cfg):
    kinds = kept_kinds(cfg)
    lin = cfg["linear_attn_config"]
    if (not cfg["use_gqa_gate"] or cfg["use_rope"] or not cfg["norm_topk_prob"]
            or cfg["routed_scaling_factor"] != 1 or cfg["first_k_dense_replace"]
            or not cfg["kda_allow_neg_eigval"] or cfg["kda_use_full_proj"]):
        raise ValueError("this adapter maps the published solar_open2 keys: "
                         "a gated position-free GQA layer, beta in (0, 2), "
                         "low-rank decay and gate projections, every layer "
                         "sparse, weights normalised over the chosen, scale 1")
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["seq_len"], tie_embeddings=cfg["tie_word_embeddings"],
        layer_kinds=kinds, layer_ids=tuple(cfg["kept_layers"]),
        trunk_norm="rmsnorm", attn_gate=True,
        ssm_heads=lin["num_heads"], ssm_state=lin["head_dim"],
        ssm_inner=lin["num_heads"] * lin["head_dim"],
        ssm_conv=lin["short_conv_kernel_size"], ssm_chunk=cfg["kda_chunk_size"],
        n_experts=cfg["router_outputs"],
        expert_top_k=cfg["num_experts_per_tok"], moe_dispatch="dropless",
        experts_held=cfg["n_routed_experts"], experts_first=cfg["experts_first"],
        router_score="sigmoid", n_shared_experts=cfg["n_shared_experts"],
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
        self._stats = collections.deque()

        def make(k):
            params = _to_program(reference.make_weights(cfg, k), cfg)
            return params, opt.init(params)

        self._init = jax.jit(make, out_shardings=NamedSharding(self.mesh, P()))
        self._gap = jax.jit(self._kda_o_gap)

    def init(self, key):
        state = self._init(key)
        gap = float(self._gap(state[0], key))
        limit = self.reference.KDA_O_GAP
        print(f"check main kda_o_gap: {gap:.6g} (limit {limit:g}; the "
              "adapter's own guard, outside `correct`)", flush=True)
        if not gap <= limit:        # a NaN too
            raise SystemExit(
                f"the chunked delta-rule scan is {gap:.6g} of its result "
                f"away from the reference's walk of the recurrence (limit "
                f"{limit:g})")
        return state

    def _kda_o_gap(self, params, key):
        """``|kda_scan(...) - recurrence(...)| / |recurrence(...)|`` over
        one row of the first KDA layer: the operands are the reference's
        own (float32, from the seed's weights and a row of seeded ids),
        handed to the scan as the trunk hands them (``q``, ``k``, ``v`` in
        the compute dtype; ``g``, ``beta`` in float32)."""
        from horovod_tpu.ops.kda_scan import kda_scan
        cfg, ref = self.cfg, self.reference
        n = kept_kinds(cfg).index("kda")
        flat = _to_flat(params, cfg)
        lw = {name: flat[f"l{n}.{name}"] for name in ref.LEAVES["kda"]}
        tokens = jax.random.randint(key, (1, cfg["seq_len"]), 0,
                                    cfg["vocab_size"])
        u = ref.rms_norm(flat["embed"][tokens], lw["norm1_w"],
                         cfg["rms_norm_eps"])
        q, k, v, g, beta = ref.kda_operands(u, lw, cfg)
        want = ref.recurrence(q, k, v, g, beta)
        low = lambda a: a.astype(cfg["dtype"]["compute"])
        got = kda_scan(low(q), low(k), low(v), g, beta,
                       cfg["kda_chunk_size"]).astype(jnp.float32)
        return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

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
