"""Mesh-axis-aware gradient plane (ISSUE 14): spec-aware buckets,
mesh-context resolution, 2-D (data x model) parity with the replicated
path, and the negotiation-token back-compat contract.

The real-mesh checks run nested ``jax.pmap`` (outer ``data``, inner
``model``) over the 8 virtual CPU devices — mesh shapes 2x2 AND 4x2 —
with the bf16-moment AdamW from ``optim/precision.py``,
``backward_passes_per_step=2``, and deliberately awkward leaf sizes so
the data-axis ZeRO tiling needs padding.
"""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from _helpers import mesh_map
from horovod_tpu.ops.engine import TensorTableEntry
from horovod_tpu.ops.fusion import (EntrySig, canonicalize_spec,
                                    plan_fusion, spec_axes, spec_shift)
from horovod_tpu.optim.distributed import (DistributedGradientTransform,
                                           DistributedOptimizer,
                                           fused_reduce_tree,
                                           make_spec_plan,
                                           sharded_tile_layout)
from horovod_tpu.optim.precision import adamw_lp, tree_nbytes

DATA, MODEL = "fdata", "fmodel"


# ---------------------------------------------------------------------------
# canonical specs
# ---------------------------------------------------------------------------

def test_canonicalize_spec():
    assert canonicalize_spec(None) == "replicated"
    assert canonicalize_spec(P()) == "replicated"
    assert canonicalize_spec(P(None, None)) == "replicated"
    assert canonicalize_spec(P("model")) == "0:model"
    assert canonicalize_spec(P(None, "model")) == "1:model"
    assert canonicalize_spec(P(("data", "model"))) == "0:data+model"
    assert canonicalize_spec(P("a", "b")) == "0:a,1:b"
    # idempotent on canonical strings; bare axis name = dim 0
    assert canonicalize_spec("1:model") == "1:model"
    assert canonicalize_spec("replicated") == "replicated"
    assert canonicalize_spec("model") == "0:model"


def test_spec_axes_and_shift():
    assert spec_axes("replicated") == ()
    assert spec_axes("1:model") == ("model",)
    assert spec_axes("0:a+b,2:a") == ("a", "b")
    assert spec_shift("1:model") == "0:model"
    assert spec_shift("replicated") == "replicated"
    with pytest.raises(ValueError, match="leading"):
        spec_shift("0:model")


def test_make_spec_plan_infers_model_axes_and_env(monkeypatch):
    plan = make_spec_plan({"w": P(MODEL), "n": P()}, DATA)
    assert plan.model_axes == (MODEL,)
    assert plan.by_name["['w']"] == f"0:{MODEL}"
    assert plan.reduce_axes(f"0:{MODEL}") == (DATA,)
    assert plan.reduce_axes("replicated") == (DATA, MODEL)
    # a spec naming the data axis: that axis drops from the reduction
    assert plan.reduce_axes(f"0:{DATA}") == (MODEL,)
    # all-replicated spec trees can still name the mesh's model axes
    # via the validated env knob
    monkeypatch.setenv("HOROVOD_MODEL_AXES", MODEL)
    from horovod_tpu import runtime
    state = runtime._state()
    if state.config is not None:    # an earlier test of this worker called
        import dataclasses          # hvd.init(): the knob is its config's
        monkeypatch.setattr(state, "config", dataclasses.replace(
            state.config, model_axes=MODEL))
    plan2 = make_spec_plan({"n": P()}, DATA)
    assert plan2.model_axes == (MODEL,)
    with pytest.raises(ValueError, match="data axis"):
        make_spec_plan({"w": P(MODEL)}, DATA, model_axes=(DATA,))


def test_config_model_axes_validation(monkeypatch):
    from horovod_tpu.config import Config
    monkeypatch.setenv("HOROVOD_MODEL_AXES", "model")
    assert Config.from_env().model_axes == "model"
    monkeypatch.setenv("HOROVOD_MODEL_AXES", "mo del,x")
    with pytest.raises(ValueError, match="HOROVOD_MODEL_AXES"):
        Config.from_env()


# ---------------------------------------------------------------------------
# planner: mixed-spec buckets never fuse (python + native parity)
# ---------------------------------------------------------------------------

def _sig(name, spec, dtype="float32"):
    return EntrySig(name=name, op_type="allreduce", reduce_op="average",
                    dtype=dtype, shape=(8,), process_set_id=0,
                    stacked=False, spec=spec)


def test_mixed_spec_buckets_never_fuse():
    sigs = [_sig("a", "0:m"), _sig("b", "replicated"), _sig("c", "0:m"),
            _sig("d", "1:m")]
    buckets = plan_fusion(sigs, 1 << 20)
    by_spec = [{sigs[i].spec for i in b} for b in buckets]
    assert all(len(s) == 1 for s in by_spec), by_spec
    assert sorted(next(iter(s)) for s in by_spec) == [
        "0:m", "1:m", "replicated"]


def test_native_planner_spec_parity():
    from horovod_tpu.native import loader
    core = loader.load()
    if core is None:
        pytest.skip("native core not built")
    sigs = [_sig(f"t{i}", spec)
            for i, spec in enumerate(
                ["replicated", "0:m", "replicated", "1:m", "0:m"])]
    assert core.plan_fusion_sigs(sigs, 1 << 20) == \
        plan_fusion(sigs, 1 << 20)
    # spec is part of the native cache key: a flip must miss
    cache = core.ResponseCache(16)
    plan = plan_fusion(sigs, 1 << 20)
    cache.put(sigs, plan)
    assert cache.get(sigs) == plan
    flipped = sigs[:1] + [_sig("t1", "replicated")] + sigs[2:]
    assert cache.get(flipped) is None


# ---------------------------------------------------------------------------
# negotiation token: field 12 + old-token back-compat
# ---------------------------------------------------------------------------

def test_entry_token_carries_spec_as_field_12():
    from horovod_tpu.ops.controller import entry_token
    ps = types.SimpleNamespace(process_set_id=0)
    e = TensorTableEntry("t", "allreduce", [np.zeros((4,), np.float32)],
                         ps, stacked=False, spec="0:model")
    tok = json.loads(entry_token(e))
    assert tok["s"][0][11] == "strict"       # field 11: tail_policy
    assert tok["s"][0][12] == "0:model"      # field 12: spec
    e2 = TensorTableEntry("t", "allreduce", [np.zeros((4,), np.float32)],
                          ps, stacked=False)
    assert json.loads(entry_token(e2))["s"][0][12] == "replicated"


def test_synthesize_tolerates_old_12_field_tokens(hvd):
    """A peer running the previous release emits 12-field sig rows
    (no spec): the joined process must synthesize spec='replicated'."""
    from horovod_tpu import runtime
    eng = runtime._state().engine
    base = ["t_spec_syn", "allreduce", "average", "float32", [3], 0,
            False, -1, None, None, "none", "strict"]
    old = json.dumps({"s": [base], "r": 0, "sp": None},
                     separators=(",", ":"), sort_keys=True)
    entry = eng._synthesize(old)
    assert entry.spec == "replicated"
    new = json.dumps({"s": [base + ["0:model"]], "r": 0, "sp": None},
                     separators=(",", ":"), sort_keys=True)
    assert eng._synthesize(new).spec == "0:model"


# ---------------------------------------------------------------------------
# transform guards
# ---------------------------------------------------------------------------

def test_param_specs_requires_axis_name():
    with pytest.raises(ValueError, match="param_specs requires"):
        DistributedGradientTransform(optax.adam(1e-3),
                                     param_specs={"w": P("m")})


def test_param_specs_refuses_health_and_data_axis_zero():
    with pytest.raises(ValueError, match="health.*param_specs"):
        DistributedGradientTransform(
            optax.adam(1e-3), axis_name=DATA, health=True,
            param_specs={"w": P(MODEL)})
    with pytest.raises(ValueError, match="data axis"):
        DistributedGradientTransform(
            optax.adam(1e-3), axis_name=DATA, sharded_update=True,
            param_specs={"w": P(DATA)})


def test_mesh_context_supplies_param_specs(hvd):
    """A transform built inside `with pmesh.with_param_specs(...)` is
    spec-aware without explicit plumbing (and plans buckets by spec)."""
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
    from horovod_tpu.parallel import mesh as mesh_mod
    pmesh = ParallelMesh(MeshConfig(dp=2))
    specs = {"w": P(MODEL), "n": P()}
    with pmesh.with_param_specs(specs):
        assert mesh_mod.current_mesh() is pmesh
        # in-jit spec resolution: trace under an abstract 2-D axis env
        def step(g):
            return fused_reduce_tree(
                g, DATA, op="average", threshold_bytes=1 << 20,
                spec_plan=make_spec_plan(specs, DATA))
        jaxpr = jax.make_jaxpr(
            step, axis_env=[(DATA, 2), (MODEL, 2)])(
            {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
             "n": jax.ShapeDtypeStruct((3,), jnp.float32)})
        text = str(jaxpr)
        # two psums: shard bucket over data only, replicated over both
        assert f"axes=('{DATA}',)" in text
        assert (f"axes=('{DATA}', '{MODEL}')" in text
                or f"axes=('{MODEL}', '{DATA}')" in text)
    assert mesh_mod.current_mesh() is None


def test_transform_reads_specs_from_mesh_context(hvd):
    """DistributedGradientTransform(param_specs=None) inside the mesh
    context picks the tree up — pinned by the guard firing for a
    context whose specs name the data axis under sharded_update."""
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
    pmesh = ParallelMesh(MeshConfig(dp=2))
    with pmesh.with_param_specs({"w": P(DATA)}):
        with pytest.raises(ValueError, match="data axis"):
            DistributedGradientTransform(
                optax.adam(1e-3), axis_name=DATA, sharded_update=True)


# ---------------------------------------------------------------------------
# 2-D mesh parity: spec-aware vs replicated (mesh 2x2 AND 4x2)
# ---------------------------------------------------------------------------

M = 2
# awkward sizes: the sharded kernel's local shard is (4, 5) = 20
# elements (pads to 24 at data=4 under ZeRO tiling), the replicated
# bias is 3 elements (pads at every data size)
_FULL = {"w": (8, 5), "b": (3,), "n": (6,)}
_SPECS = {"w": P(MODEL), "b": P(), "n": P()}


def _full_params():
    rng = np.random.default_rng(7)
    return {k: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
            for k, s in _FULL.items()}


def _full_grads(n_steps, n_dev):
    rng = np.random.default_rng(11)
    return [{k: jnp.asarray(
        rng.standard_normal((n_dev,) + s, dtype=np.float64) * 1e-2,
        jnp.float32) for k, s in _FULL.items()}
        for _ in range(n_steps)]


def _run_spec(D, sharded, grads_steps, k=2):
    """Spec-aware trajectory on a (data=D, model=M) mesh; returns
    (params at replica (0,0), per-chip inner-state bytes)."""
    tx = DistributedOptimizer(adamw_lp(1e-2),
                              axis_name=DATA, threshold_bytes=64,
                              backward_passes_per_step=k,
                              sharded_update=sharded,
                              param_specs=_SPECS, model_axes=(MODEL,))
    params = _full_params()

    def prog(gs):
        idx = jax.lax.axis_index(MODEL)
        p = dict(params)
        p["w"] = jax.lax.dynamic_slice_in_dim(
            params["w"], idx * (8 // M), 8 // M, axis=0)
        s = tx.init(p)
        for g in gs:
            gw = jax.lax.psum(g["w"], MODEL)   # the model's transpose
            g = {"w": jax.lax.dynamic_slice_in_dim(
                gw, idx * (8 // M), 8 // M, axis=0),
                "b": g["b"], "n": g["n"]}
            u, s = tx.update(g, s, p)
            p = jax.tree_util.tree_map(lambda a, b: a + b, p, u)
        return p, tree_nbytes(s.inner)

    stacked = [
        {kk: g[kk].reshape((D, M) + g[kk].shape[1:]) for kk in g}
        for g in grads_steps]
    f = mesh_map(prog, (D, M), (DATA, MODEL), in_axes=(0,))
    p_out, nb = f(stacked)
    return (jax.tree_util.tree_map(lambda a: np.asarray(a)[0, 0], p_out),
            int(np.asarray(nb)[0, 0]))


def _run_replicated(n_dev, grads_steps, k=2):
    tx = DistributedOptimizer(adamw_lp(1e-2), axis_name="frep",
                              threshold_bytes=64,
                              backward_passes_per_step=k)
    params = _full_params()

    def prog(gs):
        s = tx.init(params)
        p = params
        for g in gs:
            u, s = tx.update(g, s, p)
            p = jax.tree_util.tree_map(lambda a, b: a + b, p, u)
        return p

    f = jax.pmap(prog, axis_name="frep", in_axes=(0,))
    p_out = f(grads_steps)
    return jax.tree_util.tree_map(lambda a: a[0], p_out)


@pytest.mark.parametrize("D", [2, 4])
@pytest.mark.parametrize("sharded", [False, True])
def test_spec_vs_replicated_parity_2d(hvd, D, sharded):
    """adamw_lp (bf16 moments) + k=2 accumulation + padding: the 2-D
    spec-aware trajectory equals the flat replicated one on D*M
    devices, plain and ZeRO alike; ZeRO's per-chip state sits at the
    exact planner tile bytes."""
    grads = _full_grads(4, D * M)
    p_ref = _run_replicated(D * M, grads)
    p_spec, state_bytes = _run_spec(D, sharded, grads)
    ref_shard = dict(p_ref)
    ref_shard["w"] = p_ref["w"][: 8 // M]
    for kk in sorted(_FULL):
        np.testing.assert_allclose(
            np.asarray(p_spec[kk]), np.asarray(ref_shard[kk]),
            rtol=2e-5, atol=2e-6, err_msg=f"leaf {kk} D={D}")
    if sharded:
        # exact tile accounting (adamw_lp: bf16 mu+nu on the tiles +
        # int32 count): total/(model*data) + planner padding
        local = {"w": jax.ShapeDtypeStruct((8 // M, 5), jnp.float32),
                 "b": jax.ShapeDtypeStruct((3,), jnp.float32),
                 "n": jax.ShapeDtypeStruct((6,), jnp.float32)}
        layout = sharded_tile_layout(
            local, D, threshold_bytes=64,
            spec_plan=make_spec_plan(_SPECS, DATA, (MODEL,)))
        tiles = sum(bl.shard_numel for bl in layout.buckets)
        assert state_bytes == 2 * tiles * 2 + 4, (
            state_bytes, tiles)


def test_zero_state_smaller_than_plain_2d(hvd):
    grads = _full_grads(2, 2 * M)
    _p, plain_bytes = _run_spec(2, False, grads)
    _p2, zero_bytes = _run_spec(2, True, grads)
    assert zero_bytes < plain_bytes


# ---------------------------------------------------------------------------
# overlap tap-spec resolution
# ---------------------------------------------------------------------------

def test_overlap_tap_specs_shift_and_collide():
    from horovod_tpu.optim import overlap as ov
    sp = make_spec_plan(
        {"embed": P(), "layers": {"w": P(None, MODEL), "b": P()}},
        DATA, (MODEL,))
    plan = ov.OverlapPlan(axis_name=DATA, op="average",
                          threshold_bytes=None, prescale=1.0,
                          postscale=1.0, sharded=False, fmt=None, k=1,
                          spec_plan=sp)
    taps = plan.tap_specs()
    assert taps["['w']"] == f"0:{MODEL}"     # shifted past the scan dim
    assert taps["['embed']"] == "replicated"
    sp_bad = make_spec_plan(
        {"w": P(MODEL), "layers": {"w": P(None, None)}}, DATA, (MODEL,))
    plan_bad = ov.OverlapPlan(axis_name=DATA, op="average",
                              threshold_bytes=None, prescale=1.0,
                              postscale=1.0, sharded=False, fmt=None,
                              k=1, spec_plan=sp_bad)
    with pytest.raises(ValueError, match="ambiguous"):
        plan_bad.tap_specs()


def test_with_param_specs_is_scoped(hvd):
    """Review fix (pinned): specs attached via with_param_specs clear
    on __exit__ — a later unrelated `with pmesh:` block must not
    silently inherit them (direct assignment stays persistent)."""
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
    pmesh = ParallelMesh(MeshConfig(dp=2))
    with pmesh.with_param_specs({"w": P(MODEL)}):
        assert pmesh.param_specs is not None
    assert pmesh.param_specs is None
    pmesh.param_specs = {"w": P(MODEL)}     # persistent form
    with pmesh:
        pass
    assert pmesh.param_specs is not None


def test_model_axes_env_tolerates_trailing_comma(monkeypatch):
    """Review fix (pinned): 'tp, ' validates (the consumer ignores
    whitespace segments, so the validator must too)."""
    from horovod_tpu.config import Config
    monkeypatch.setenv("HOROVOD_MODEL_AXES", "tp, ")
    assert Config.from_env().model_axes == "tp,"
