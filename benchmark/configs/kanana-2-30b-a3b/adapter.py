"""The configuration through the program's normal path: hvd.init() ->
ParallelMesh(dp=n) -> training.make_llama_train_step with next-token
cross-entropy.  This file maps the published keys onto the program's
mechanisms (a trunk whose layers are all of the kind ``mla``, multi-head
latent attention under the kind's rotary table; ``first_k_dense_replace``
leading layers with the dense feed-forward, the rest dropless routed
experts of which the chip holds a share, scored by a sigmoid, chosen with
a bias and weighed times ``routed_scaling_factor``, beside the shared
experts; RMSNorm; an untied head over the ids held); the benchmark supplies
the weights (reference.make_weights) and reads the state back under the
reference's names, which are the program's.

**Columns.**  The reference keeps ``wq``'s, ``wkv_a``'s and ``wkv_b``'s
columns as published: a query head's 128 columns without positions and
then its 64 rotary ones in pairs, the latent's 512 and then the rotary
key's pairs, a head's ``k_nope`` and then its ``v``.  The program holds
every head's part without positions first, then every head's rotary part
in halves (``[evens ; odds]``, turned by halves), and every head's
``k_nope`` before every head's ``v``.  ``_COLUMNS`` is that permutation:
applied on the way in, inverted on the way out, exact both ways.

**A guard of this file's own, outside ``correct``.**  The harness compares
a loss and norms of leaves, and at seeded weights those hardly move with
what attention computes (PERF.md section 2, question 21).  So
``Program.init`` hands one layer's latent attention, at the timed sizes and
on the seed's own weights, the normed rows that the reference's gets,
prints the relative distance between the two results (``mla_o_gap``) and
stops the run where it is over ``reference.MLA_O_GAP``.  It is a program of
its own on one layer, not the timed step, it ends a run without a result
line (no ``correct: false``), and its reference half is paid inside
``setup_s`` (the seconds are on its line).  A ``benchmark`` PR can make it a
number of ``harness/check.py``, computed after the window beside the
reference's steps (PERF.md question 21).
"""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import training
from horovod_tpu.models import hybrid, llama, moe
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh

_TOP = {"embed": "embed", "final_norm": "final_norm_w", "head": "head"}   # the program's: the reference's
KIND = "mla"
# a step's routing statistics are recorded this many steps later, when its
# arrays are long ready: fetching them then does not stall the queue
_STATS_LAG = 8


def kept_kinds(cfg):
    kinds = (KIND,) * len(cfg["kept_layers"])
    if KIND not in hybrid.KINDS:
        raise ValueError(f"this program's trunk of several kinds has no "
                         f"{KIND!r}: it knows {hybrid.KINDS}")
    return kinds


def _dense_layers(cfg):
    return sum(i < cfg["first_k_dense_replace"] for i in cfg["kept_layers"])


def program_config(cfg):
    kinds = kept_kinds(cfg)
    if (not cfg["norm_topk_prob"] or cfg["attention_bias"]
            or cfg["hidden_act"] != "silu" or cfg["q_lora_rank"] is not None
            or cfg["rope_scaling"] is not None or not cfg["rope_interleave"]
            or cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["moe_layer_freq"] != 1
            or cfg["kept_layers"] != list(range(len(kinds)))):
        raise ValueError("this adapter maps the published kanana keys: no "
                         "bias, silu, no query latent, plain interleaved "
                         "RoPE, a sigmoid router over one group with weights "
                         "normalised over the chosen, every layer past the "
                         "leading dense ones routed, the leading layers kept")
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=len(kinds), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        first_dense_layers=_dense_layers(cfg),
        norm_eps=cfg["rms_norm_eps"], max_seq_len=cfg["seq_len"],
        tie_embeddings=cfg["tie_word_embeddings"],
        layer_kinds=kinds, layer_ids=tuple(cfg["kept_layers"]),
        trunk_norm="rmsnorm",
        rope_tables=((KIND, llama.RopeTable(theta=cfg["rope_theta"])),),
        n_experts=cfg["router_outputs"],
        expert_top_k=cfg["num_experts_per_tok"], moe_dispatch="dropless",
        experts_held=cfg["n_routed_experts"],
        experts_first=cfg["experts_first"], router_score="sigmoid",
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        loss_chunk=cfg["loss_chunk"], remat=cfg["remat"],
        remat_policy=cfg["remat_policy"],
        dtype=jnp.dtype(cfg["dtype"]["compute"]),
        param_dtype=jnp.dtype(cfg["dtype"]["params"]))


def _columns(cfg):
    """{leaf: the reference's column that lies at each of the program's}."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    halves = lambda pairs: np.concatenate(
        [pairs[..., 0::2], pairs[..., 1::2]], -1).reshape(-1)
    q = np.arange(h * (dn + dr)).reshape(h, dn + dr)
    kv = np.arange(h * (dn + dv)).reshape(h, dn + dv)
    return {
        "wq": np.concatenate([q[:, :dn].reshape(-1), halves(q[:, dn:])]),
        "wkv_a": np.concatenate([np.arange(r), halves(r + np.arange(dr))]),
        "wkv_b": np.concatenate([kv[:, :dn].reshape(-1),
                                 kv[:, dn:].reshape(-1)])}


def _places(cfg):
    """[(position in the cut, its stack in the program's tree, whether it
    is a leading dense layer, its place in the stack)]."""
    seen, out = {}, []
    for n in range(len(cfg["kept_layers"])):
        dense = n < _dense_layers(cfg)
        stack = ("dense_" if dense else "") + KIND
        out.append((n, stack, dense, seen.get(stack, 0)))
        seen[stack] = seen.get(stack, 0) + 1
    return out


def _to_program(flat, cfg):
    params = {ours: flat[theirs] for ours, theirs in _TOP.items()}
    layers, lcfg, columns = {}, program_config(cfg), _columns(cfg)
    for n, stack, dense, _ in _places(cfg):
        for name in hybrid.layer_shapes(lcfg, KIND, dense):
            w = flat[f"l{n}.{name}"]
            if name in columns:
                w = w[:, columns[name]]
            layers.setdefault(stack, {}).setdefault(name, []).append(w)
    params["layers"] = {stack: {name: jnp.stack(ws)
                                for name, ws in tree.items()}
                        for stack, tree in layers.items()}
    return params


def _to_flat(params, cfg):
    flat = {theirs: params[ours] for ours, theirs in _TOP.items()}
    back = {name: np.argsort(c) for name, c in _columns(cfg).items()}
    for n, stack, _, at in _places(cfg):
        for name, stacked in params["layers"][stack].items():
            w = stacked[at]
            flat[f"l{n}.{name}"] = w[:, back[name]] if name in back else w
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
        self._gap = jax.jit(self.mla_o_gap, static_argnums=2)

    def init(self, key):
        state = jax.block_until_ready(self._init(key))
        t0 = time.perf_counter()
        gap = float(self._gap(state[0], key, None))
        limit = self.reference.MLA_O_GAP
        print(f"check main mla_o_gap: {gap:.6g} (limit {limit:g}; the "
              "adapter's own guard, outside `correct`; "
              f"{time.perf_counter() - t0:.2f} s of set-up)", flush=True)
        if not gap <= limit:        # a NaN too
            raise SystemExit(
                f"the program's latent attention is {gap:.6g} of its result "
                f"away from the reference's (limit {limit:g})")
        return state

    def mla_o_gap(self, params, key, control=None):
        """``|program - reference| / |reference|`` of the last kept
        layer's attention sublayer output (through ``wo``) over one row:
        both get the reference's normed rows (float32, from the seed's
        embedding and a row of seeded ids), the program's in the compute
        dtype as the trunk hands them, under the kind's rotary table as
        ``layer_stack`` makes it.  ``control``: the reference's
        (``reference.CONTROLS``), for the builder's readings."""
        cfg, ref, lcfg = self.cfg, self.reference, program_config(self.cfg)
        n, stack, dense, at = _places(cfg)[-1]
        flat = _to_flat(params, cfg)
        lw = {name: flat[f"l{n}.{name}"] for name in ref.leaves(cfg, n)}
        tokens = jax.random.randint(key, (1, cfg["seq_len"]), 0,
                                    cfg["vocab_size"])
        u = ref.rms_norm(flat["embed"][tokens], lw["norm1_w"],
                         cfg["rms_norm_eps"])
        with jax.default_matmul_precision("highest"):
            want = ref.attention(u, lw, cfg, control=control)
        lp = {name: w[at].astype(lcfg.dtype)
              for name, w in params["layers"][stack].items()
              if name in ("wq", "wkv_a", "wkv_b", "wo", "kv_norm")}
        rope = llama.rope_table(dict(lcfg.rope_tables)[KIND],
                                lcfg.qk_rope_head_dim, cfg["seq_len"])
        got = hybrid.latent_attention(u.astype(lcfg.dtype), lp, rope,
                                      lcfg).astype(jnp.float32)
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
