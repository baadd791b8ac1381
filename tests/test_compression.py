"""Block-scaled quantized collectives (compression.py wire formats +
ops/collectives.py staging + optim/distributed.py error feedback).

The quantized reduction is a schedule rewrite — quantize blocks →
exchange int8/fp8 tiles + fp32 scales → dequantize-accumulate in fp32 —
negotiated per fusion bucket (``EntrySig.wire_format``).  Numerics run
on a REAL mapped CPU mesh at sizes 2 and 4 (``jax.pmap``, the same XLA
collective lowering as ICI), including non-divisible block sizes
(padding), overflow-range sums, sharded-update composition, and
error-feedback parity against the full-width path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd_mod
from horovod_tpu.compression import (DEFAULT_BLOCK_SIZE, WIRE_FORMATS,
                                     WireFormat, dequantize_blocks,
                                     quantizable, quantize_blocks,
                                     resolve_wire_format)
from horovod_tpu.ops.fusion import (EntrySig, ResponseCache, dtype_nbytes,
                                    plan_bucket_layouts, plan_fusion)
from horovod_tpu.optim.distributed import (DistributedGradientTransform,
                                           DistributedOptimizer, _DistState,
                                           fused_reduce_scatter_tree,
                                           fused_reduce_tree,
                                           state_partition_specs)

AXIS = "qw"

# deliberately awkward sizes (the test_zero convention): 35 and 3
# elements with block 16 → every bucket pads, at mesh 4 the padded
# buffer is not an even block multiple per worker without align
PARAMS = {"a": np.linspace(-1.0, 1.0, 35).reshape(7, 5).astype(np.float32),
          "b": np.arange(3, dtype=np.float32)}
THRESHOLD = 64   # bytes → "a" and "b" land in separate buckets
BLOCK = 16

INT8 = resolve_wire_format("int8", BLOCK)


def _grad_stack(n):
    return {
        "a": np.stack([np.sin(np.arange(35, dtype=np.float32) + r)
                       .reshape(7, 5) for r in range(n)]),
        "b": np.stack([np.full((3,), float(r + 1), np.float32)
                       for r in range(n)]),
    }


# ---------------------------------------------------------------------------
# the math: quantize/dequantize + format registry
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_bounded_error():
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(128) * 10).astype(np.float32)
    q, s = quantize_blocks(jnp.asarray(x), INT8)
    assert q.dtype == jnp.int8 and q.shape == (128,)
    assert s.dtype == jnp.float32 and s.shape == (128 // BLOCK,)
    d = np.asarray(dequantize_blocks(q, s, INT8))
    # per block the error is <= scale/2 = blockmax/254
    for blk in range(128 // BLOCK):
        sl = slice(blk * BLOCK, (blk + 1) * BLOCK)
        assert np.abs(d[sl] - x[sl]).max() <= \
            np.abs(x[sl]).max() / 254 + 1e-7


def test_quantize_zero_blocks_exact():
    x = jnp.zeros((2 * BLOCK,), jnp.float32)
    q, s = quantize_blocks(x, INT8)
    np.testing.assert_array_equal(np.asarray(s), np.ones(2, np.float32))
    np.testing.assert_array_equal(
        np.asarray(dequantize_blocks(q, s, INT8)), np.asarray(x))


def test_fp8_formats_quantize():
    for name in ("fp8_e4m3", "fp8_e5m2"):
        fmt = resolve_wire_format(name, BLOCK)
        x = (np.random.default_rng(1).standard_normal(BLOCK) * 3
             ).astype(np.float32)
        q, s = quantize_blocks(jnp.asarray(x), fmt)
        d = np.asarray(dequantize_blocks(q, s, fmt))
        assert np.abs(d - x).max() <= np.abs(x).max() / 8  # e5m2: 2 mantissa


def test_resolve_wire_format():
    assert resolve_wire_format(None) is None
    assert resolve_wire_format("none") is None
    assert resolve_wire_format("") is None
    fmt = resolve_wire_format("int8")
    assert fmt.block_size == DEFAULT_BLOCK_SIZE and fmt.qmax == 127.0
    assert resolve_wire_format(fmt) is fmt
    assert resolve_wire_format(fmt, 32).block_size == 32
    assert "int8" in WIRE_FORMATS
    with pytest.raises(ValueError, match="unknown wire format"):
        resolve_wire_format("int4")
    with pytest.raises(ValueError, match="positive"):
        resolve_wire_format("int8", 0)


def test_wire_nbytes_accounting():
    fmt = resolve_wire_format("int8", 256)
    # 512 elements = 2 blocks: 512 lanes + 2 fp32 scales
    assert fmt.wire_nbytes(512) == 512 + 8
    # 513 elements pad to 3 blocks
    assert fmt.wire_nbytes(513) == 768 + 12
    assert quantizable("float32") and quantizable("bfloat16")
    assert not quantizable("int32") and not quantizable("float64")


# ---------------------------------------------------------------------------
# satellite: _DTYPE_BYTES fp8 entries + unknown raises
# ---------------------------------------------------------------------------

def test_dtype_nbytes_fp8_and_unknown():
    assert dtype_nbytes("float8_e4m3fn") == 1
    assert dtype_nbytes("float8_e5m2") == 1
    assert dtype_nbytes("complex64") == 8
    with pytest.raises(ValueError, match="unknown dtype"):
        dtype_nbytes("galactic128")
    # an EntrySig with an fp8 dtype plans as 1 byte/element
    sig = EntrySig(name="t", op_type="allreduce", reduce_op="sum",
                   dtype="float8_e5m2", shape=(100,), process_set_id=0,
                   stacked=False)
    assert sig.nbytes == 100


# ---------------------------------------------------------------------------
# planner: wire_format is a fusion dimension and a cache-key dimension
# ---------------------------------------------------------------------------

def _sig(name, wire="none", dtype="float32"):
    return EntrySig(name=name, op_type="allreduce", reduce_op="sum",
                    dtype=dtype, shape=(8,), process_set_id=0,
                    stacked=False, wire_format=wire)


def test_mixed_wire_formats_never_fuse():
    sigs = [_sig("a", "int8"), _sig("b", "none"), _sig("c", "int8")]
    buckets = plan_fusion(sigs, 1 << 20)
    by_fmt = [{sigs[i].wire_format for i in b} for b in buckets]
    assert all(len(s) == 1 for s in by_fmt)
    assert len(buckets) == 2
    # same formats fuse as before
    assert plan_fusion([_sig("a", "int8"), _sig("b", "int8")],
                       1 << 20) == [[0, 1]]


def test_response_cache_key_includes_wire_format():
    cache = ResponseCache(capacity=8)
    sigs_none = [_sig("a", "none")]
    sigs_q = [_sig("a", "int8")]
    cache.put(sigs_none, [[0]])
    assert cache.get(sigs_none) == [[0]]
    # a format flip is a plan-identity change: the cached plan must miss
    assert cache.get(sigs_q) is None


def test_native_planner_parity_with_wire_formats():
    from horovod_tpu.native import loader
    core = loader.load()
    if core is None:
        pytest.skip("native core unavailable")
    sigs = [_sig("a", "int8"), _sig("b", "none"), _sig("c", "int8"),
            _sig("d", "int8", dtype="bfloat16")]
    assert core.plan_fusion_sigs(sigs, 1 << 20) == \
        plan_fusion(sigs, 1 << 20)


def test_bucket_layout_block_alignment():
    sigs = [_sig("a"), _sig("b")]
    layouts = plan_bucket_layouts(sigs, [[0, 1]], 4, align=16)
    # 16 elements pad to 4*16=64 so each worker's tile is one block
    assert layouts[0].padded_numel == 64 and layouts[0].shard_numel == 16


# ---------------------------------------------------------------------------
# the staging: quantized allreduce on a real mapped mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4])
def test_quantized_allreduce_sum_no_overflow(n):
    from horovod_tpu.ops.collectives import quantized_allreduce_p
    # per-worker magnitude ~1000: the true sum is ~25x beyond the int8
    # lane, so a naive int8 psum would wrap — the staging accumulates
    # dequantized fp32 and must be exact up to quantization error
    vals = np.stack([np.linspace(900.0, 1100.0, 37).astype(np.float32)
                     * (r + 1) for r in range(n)])
    want = vals.sum(0)

    def f(v):
        out, _ = quantized_allreduce_p(v, AXIS, INT8, op=hvd_mod.Sum)
        return out

    got = jax.pmap(f, axis_name=AXIS, devices=jax.devices()[:n])(vals)
    for r in range(n):
        np.testing.assert_allclose(got[r], want, rtol=0.02)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[-1]))


def test_quantized_allreduce_average_and_residual():
    from horovod_tpu.ops.collectives import quantized_allreduce_p
    n = 4
    vals = np.stack([np.sin(np.arange(21, dtype=np.float32) + r)
                     for r in range(n)])

    def f(v):
        out, res = quantized_allreduce_p(v, AXIS, INT8,
                                         op=hvd_mod.Average,
                                         error_feedback=True)
        return out, res

    out, res = jax.pmap(f, axis_name=AXIS, devices=jax.devices()[:n])(vals)
    np.testing.assert_allclose(out[0], vals.mean(0), rtol=0.05, atol=5e-3)
    # the residual is THIS worker's own quantization error: adding it to
    # a requantized contribution must shrink, not grow — bounded by one
    # quantization step of the contribution
    assert res.shape == vals.shape
    assert float(np.abs(np.asarray(res)).max()) <= \
        float(np.abs(vals).max()) / 254 + 1e-7


def test_quantized_allreduce_rejects_bad_op():
    from horovod_tpu.ops.collectives import quantized_allreduce_p
    with pytest.raises(ValueError, match="Sum/Average"):
        quantized_allreduce_p(jnp.ones(4), AXIS, INT8, op=hvd_mod.Min)


# ---------------------------------------------------------------------------
# optimizer: error-feedback parity vs the full-width path (mesh 2 and 4)
# ---------------------------------------------------------------------------

def _run_steps(n, wire="none", sharded=False, k=1, steps=4, block=BLOCK):
    devs = jax.devices()[:n]
    opt = DistributedOptimizer(optax.adam(1e-2), axis_name=AXIS,
                               threshold_bytes=THRESHOLD,
                               backward_passes_per_step=k,
                               sharded_update=sharded,
                               wire_format=wire, wire_block_size=block)
    st = jax.pmap(lambda p, _: opt.init(p), axis_name=AXIS,
                  in_axes=(None, 0), devices=devs)(PARAMS, np.zeros(n))

    def step(p, s, g):
        u, ns = opt.update(g, s, p)
        return optax.apply_updates(p, u), ns

    f = jax.pmap(step, axis_name=AXIS, in_axes=(None, 0, 0), devices=devs)
    gs = _grad_stack(n)
    p = PARAMS
    for i in range(steps):
        gi = jax.tree_util.tree_map(lambda x: x * (1.0 + 0.25 * i), gs)
        pstack, st = f(p, st, gi)
        # the quantized wire must keep replicas BIT-identical: everyone
        # applies the same dequantized tiles, own tile included
        jax.tree_util.tree_map(
            lambda x: np.testing.assert_array_equal(
                np.asarray(x[0]), np.asarray(x[-1])), pstack)
        p = jax.tree_util.tree_map(lambda x: x[0], pstack)
    return p, st


@pytest.mark.parametrize("n", [2, 4])
def test_quantized_parity_vs_full_width(n):
    """int8 + error feedback tracks the full-width trajectory within the
    documented bound (docs/performance.md) — at a block size that does
    NOT divide either bucket (35 and 3 elements, block 16: padding)."""
    p_q, _ = _run_steps(n, wire="int8")
    p_f, _ = _run_steps(n, wire="none")
    for key in PARAMS:
        np.testing.assert_allclose(p_q[key], p_f[key], rtol=5e-2,
                                   atol=2e-3)


@pytest.mark.parametrize("n", [2, 4])
def test_quantized_sharded_update_composes(n):
    """wire_format + sharded_update: quantized gradient reduce-scatter,
    full-width updates all-gather, same parity bound."""
    p_q, _ = _run_steps(n, wire="int8", sharded=True)
    p_f, _ = _run_steps(n, wire="none", sharded=False)
    for key in PARAMS:
        np.testing.assert_allclose(p_q[key], p_f[key], rtol=5e-2,
                                   atol=2e-3)


def test_quantized_backward_passes_per_step():
    p_q, _ = _run_steps(4, wire="int8", k=2, steps=4)
    p_f, _ = _run_steps(4, wire="none", k=2, steps=4)
    for key in PARAMS:
        np.testing.assert_allclose(p_q[key], p_f[key], rtol=5e-2,
                                   atol=2e-3)


def test_error_feedback_residual_carried_in_state():
    _, st = _run_steps(2, wire="int8", steps=2)
    res = st.residual
    assert res is not None
    # grads-shaped fp32 tree, one per worker (stacked by pmap)
    assert set(res.keys()) == {"a", "b"}
    assert res["a"].shape == (2, 7, 5) and res["a"].dtype == jnp.float32
    # after a quantized step the carried error is nonzero somewhere
    assert float(np.abs(np.asarray(res["a"])).max()) > 0
    # full-width transforms carry no residual at all
    _, st_f = _run_steps(2, wire="none", steps=1)
    assert st_f.residual is None


def test_state_partition_specs_residual_varies_over_workers():
    from jax.sharding import PartitionSpec as P
    state = _DistState(
        inner=(jax.ShapeDtypeStruct((20,), jnp.float32),),
        acc=None, count=jax.ShapeDtypeStruct((), jnp.int32),
        residual={"a": jax.ShapeDtypeStruct((7, 5), jnp.float32)})
    specs = state_partition_specs(state, AXIS)
    assert specs.residual["a"] == P(AXIS)
    assert specs.count == P()
    # and a residual-less state keeps the old shape
    specs0 = state_partition_specs(
        _DistState(inner=(), acc=None,
                   count=jax.ShapeDtypeStruct((), jnp.int32)), AXIS)
    assert specs0.residual is None


def test_residual_state_crosses_mapped_boundary():
    """The residual crosses separate mapped step calls exactly like the
    accumulator: carried per worker (in_axes=0), and the carried value —
    not a fresh zero — feeds the next quantization.  (This container's
    jax lacks jax.shard_map; pmap exercises the same boundary.)"""
    n = 2
    devs = jax.devices()[:n]
    opt = DistributedOptimizer(optax.adam(1e-2), axis_name=AXIS,
                               threshold_bytes=THRESHOLD,
                               wire_format="int8", wire_block_size=BLOCK)
    st = jax.pmap(lambda p, _: opt.init(p), axis_name=AXIS,
                  in_axes=(None, 0), devices=devs)(PARAMS, np.zeros(n))
    specs = state_partition_specs(
        jax.tree_util.tree_map(lambda x: x[0] if hasattr(x, "shape")
                               else x, st), AXIS)
    from jax.sharding import PartitionSpec as P
    # the spec rule says the residual is per-worker data
    assert all(s == P(AXIS)
               for s in jax.tree_util.tree_leaves(specs.residual))

    def step(p, s, g):
        u, ns = opt.update(g, s, p)
        return optax.apply_updates(p, u), ns

    f = jax.pmap(step, axis_name=AXIS, in_axes=(None, 0, 0), devices=devs)
    gs = _grad_stack(n)
    # two separate mapped calls: state (incl. residual) round-trips the
    # host boundary between them
    p1, st1 = f(PARAMS, st, gs)
    res1 = np.asarray(st1.residual["a"])
    p1 = jax.tree_util.tree_map(lambda x: x[0], p1)
    _p2, st2 = f(p1, st1, gs)
    res2 = np.asarray(st2.residual["a"])
    assert res1.shape == res2.shape == (n, 7, 5)
    # feeding the carried residual back changes the next step's error
    assert not np.array_equal(res1, res2)


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_adasum_with_wire_format_raises():
    with pytest.raises(ValueError, match="Average/Sum"):
        DistributedGradientTransform(optax.adam(1e-3), axis_name=AXIS,
                                     op=hvd_mod.Adasum, wire_format="int8")
    with pytest.raises(ValueError, match="Adasum"):
        fused_reduce_tree({"w": jnp.ones(4)}, AXIS, op=hvd_mod.Adasum,
                          wire_format="int8")


def test_wire_format_requires_axis_name():
    with pytest.raises(ValueError, match="axis_name"):
        DistributedGradientTransform(optax.adam(1e-3), wire_format="int8")
    # explicit "none" on the eager path stays fine
    DistributedGradientTransform(optax.adam(1e-3), wire_format="none")


def test_wire_format_and_cast_compression_conflict():
    from horovod_tpu.compression import Compression
    with pytest.raises(ValueError, match="not both"):
        DistributedGradientTransform(optax.adam(1e-3), axis_name=AXIS,
                                     compression=Compression.bf16,
                                     wire_format="int8")
    with pytest.raises(ValueError, match="not both"):
        fused_reduce_scatter_tree({"w": jnp.ones(4)}, AXIS,
                                  compression=Compression.fp16,
                                  wire_format="int8")


def test_config_parses_compression_env(monkeypatch):
    from horovod_tpu.config import Config
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    monkeypatch.setenv("HOROVOD_COMPRESSION_BLOCK_SIZE", "128")
    monkeypatch.setenv("HOROVOD_COMPRESSION_DCN_ONLY", "0")
    c = Config.from_env()
    assert c.compression == "int8"
    assert c.compression_block_size == 128
    assert c.compression_dcn_only is False
    monkeypatch.setenv("HOROVOD_COMPRESSION", "zip")
    with pytest.raises(ValueError, match="HOROVOD_COMPRESSION"):
        Config.from_env()
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    monkeypatch.setenv("HOROVOD_COMPRESSION_BLOCK_SIZE", "-1")
    with pytest.raises(ValueError, match="BLOCK_SIZE"):
        Config.from_env()


def test_env_default_enables_wire_format(monkeypatch):
    """HOROVOD_COMPRESSION flips the in-jit default for axis_name
    callers: the state grows an error-feedback residual."""
    from horovod_tpu import runtime
    st = runtime._state()
    if getattr(st, "config", None) is not None:
        monkeypatch.setattr(st.config, "compression", "int8")
        monkeypatch.setattr(st.config, "compression_block_size", 16)
    else:
        monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
        monkeypatch.setenv("HOROVOD_COMPRESSION_BLOCK_SIZE", "16")
    tx = DistributedGradientTransform(optax.adam(1e-3), axis_name=AXIS)
    spec = {"a": jax.ShapeDtypeStruct((5,), jnp.float32)}
    _, state_shape = jax.make_jaxpr(tx.init, axis_env=[(AXIS, 2)],
                                    return_shape=True)(spec)
    assert state_shape.residual is not None
    # eager callers are untouched by the env default (no mesh axis)
    eager = DistributedGradientTransform(optax.adam(1e-3))
    assert eager is not None


# ---------------------------------------------------------------------------
# schedule: the quantized plan is a pinned, mesh-consistent artifact
# ---------------------------------------------------------------------------

def test_quantized_schedule_snapshot_and_consistency():
    from horovod_tpu.analysis.schedule import (builtin_schedule,
                                               check_builtin_consistency,
                                               check_builtin_snapshots)
    assert check_builtin_snapshots(
        entries=["quantized_distopt_step"]) == []
    # HVD210: identical canonical schedule at mesh 2 and 4
    assert check_builtin_consistency(
        entries=["quantized_distopt_step"]) == []
    s = builtin_schedule("quantized_distopt_step")
    prims = [r.prim for r in s.records]
    # per bucket: int8 tiles + fp32 scales exchanged, then gathered —
    # and NEVER a full-width psum
    assert "psum" not in prims
    assert prims.count("all_to_all") == prims.count("all_gather")
    int8_records = [r for r in s.records
                    if any(i.startswith("int8[") for i in r.inputs)]
    assert int8_records, "wire dtype lost: no int8 operands in the plan"
    # every record is attributed to its fusion bucket
    assert all(r.bucket is not None for r in s.records)


def test_distopt_snapshot_independent_of_compression_env(monkeypatch):
    # the committed full-width snapshot must not flip when the operator
    # exports HOROVOD_COMPRESSION=int8 (wire_format="none" is pinned)
    from horovod_tpu import runtime
    from horovod_tpu.analysis.schedule import builtin_schedule
    st = runtime._state()
    if getattr(st, "config", None) is not None:
        monkeypatch.setattr(st.config, "compression", "int8")
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    s = builtin_schedule("distopt_step")
    assert [r.prim for r in s.records] == ["psum"] * len(s.records)


def test_hierarchical_dcn_stage_quantized():
    """hierarchical_allreduce_p(wire_format=...): the local (ICI) stages
    stay full-width psum_scatter/all_gather; only the cross (DCN) stage
    carries int8."""
    from horovod_tpu.analysis.schedule import trace_schedule
    from horovod_tpu.ops.collectives import hierarchical_allreduce_p

    def step(x):
        return hierarchical_allreduce_p(x, "hc", "hl", op="average",
                                        wire_format=INT8)

    s = trace_schedule(step, (jax.ShapeDtypeStruct((96,), jnp.float32),),
                       axis_env=[("hc", 2), ("hl", 2)], entry="hier_q")
    cross = [r for r in s.records if "hc" in r.axes]
    local = [r for r in s.records if "hl" in r.axes]
    assert cross and local
    assert all(r.prim != "psum" for r in cross)
    assert any(any(i.startswith("int8[") for i in r.inputs)
               for r in cross)
    assert all(not any(i.startswith("int8[") for i in r.inputs)
               for r in local)


# ---------------------------------------------------------------------------
# eager engine: negotiated per-bucket wire format end to end
# ---------------------------------------------------------------------------

def test_engine_dispatches_quantized_bucket(hvd, monkeypatch):
    """With HOROVOD_COMPRESSION active (and DCN-only off: the 8-dev CPU
    mesh is flat), an eager allreduce rides the quantized staging: the
    result is quantization-close, the entry's signature carries the
    format, and hvd_wire_bytes_total accounts int8 bytes."""
    from horovod_tpu import runtime
    from horovod_tpu import metrics as _metrics
    eng = runtime._state().engine
    monkeypatch.setattr(eng.cfg, "compression", "int8")
    monkeypatch.setattr(eng.cfg, "compression_block_size", 32)
    monkeypatch.setattr(eng.cfg, "compression_dcn_only", False)
    n = hvd.size()
    x = hvd.worker_values(lambda r: np.linspace(1.0, 2.0, 40)
                          .astype(np.float32) * (r + 1))
    out = hvd.allreduce(x, op=hvd.Sum, name="wire_q_t")
    want = np.linspace(1.0, 2.0, 40) * sum(range(1, n + 1))
    np.testing.assert_allclose(np.asarray(out), want, rtol=0.02)
    if _metrics.ACTIVE:
        text = _metrics.render_prometheus()
        assert 'hvd_wire_bytes_total{format="int8"}' in text
        assert 'hvd_wire_compression_ratio{format="int8"}' in text


def test_engine_dcn_only_keeps_flat_mesh_full_width(hvd, monkeypatch):
    """The default DCN-only policy: on a flat mesh with no hierarchical
    stage the dispatch stays full-width even though the format is
    negotiated in the signatures (the bytes claim must be honest)."""
    from horovod_tpu import runtime
    eng = runtime._state().engine
    monkeypatch.setattr(eng.cfg, "compression", "int8")
    monkeypatch.setattr(eng.cfg, "compression_dcn_only", True)
    monkeypatch.setattr(eng.cfg, "hierarchical_allreduce", False)
    x = hvd.worker_values(lambda r: np.full((24,), float(r), np.float32))
    out = hvd.allreduce(x, op=hvd.Sum, name="wire_dcn_t")
    want = np.full((24,), float(sum(range(hvd.size()))))
    # full-width psum: exact
    np.testing.assert_array_equal(np.asarray(out), want)


def test_entry_sigs_carry_wire_format(hvd, monkeypatch):
    from horovod_tpu import runtime
    from horovod_tpu.ops.engine import TensorTableEntry
    eng = runtime._state().engine
    ps = runtime._get_global_process_set()
    e = TensorTableEntry(name="t", op_type="allreduce",
                         arrays=[np.ones((4,), np.float32),
                                 np.ones((4,), np.int32)],
                         process_set=ps, reduce_op=hvd_mod.Sum,
                         wire_format="int8")
    fmts = [s.wire_format for s in e.sigs()]
    assert fmts == ["int8", "none"]    # int32 is not quantizable
    # non-summable reductions never quantize
    e2 = TensorTableEntry(name="t2", op_type="allreduce",
                          arrays=[np.ones((4,), np.float32)],
                          process_set=ps, reduce_op=hvd_mod.Min,
                          wire_format="int8")
    assert e2.sigs()[0].wire_format == "none"


def test_bucket_wire_format_gating(hvd, monkeypatch):
    """The effective per-dispatch format: config opt-in AND (DCN-only →
    a hierarchical stage must exist) AND a real wire (stacked), all
    computed without dispatching."""
    from horovod_tpu import runtime
    eng = runtime._state().engine
    ps = runtime._get_global_process_set()
    import dataclasses
    sig_q = dataclasses.replace(_sig("t", "int8"), stacked=True)
    monkeypatch.setattr(eng.cfg, "compression", "int8")
    # flat mesh + DCN-only (default): no DCN stage to quantize → none
    monkeypatch.setattr(eng.cfg, "compression_dcn_only", True)
    monkeypatch.setattr(eng.cfg, "hierarchical_allreduce", False)
    assert eng._bucket_wire_format(sig_q, ps) == "none"
    # DCN-only off: the flat fused reduction quantizes
    monkeypatch.setattr(eng.cfg, "compression_dcn_only", False)
    assert eng._bucket_wire_format(sig_q, ps) == "int8"
    # hierarchical path available: DCN-only quantizes the cross stage
    monkeypatch.setattr(eng.cfg, "compression_dcn_only", True)
    monkeypatch.setattr(eng.cfg, "hierarchical_allreduce", True)
    monkeypatch.setattr(ps, "_hier_shape", (2, 4), raising=False)
    assert eng._bucket_wire_format(sig_q, ps) == "int8"
    # a bucket whose signature negotiated no format never quantizes
    assert eng._bucket_wire_format(
        dataclasses.replace(_sig("t", "none"), stacked=True), ps) == "none"
    # replicated single-process arrays move no bytes → none
    monkeypatch.setattr(eng.cfg, "compression_dcn_only", False)
    assert eng._bucket_wire_format(_sig("t", "int8"), ps) == "none"
    # config off switches everything off regardless of signatures
    monkeypatch.setattr(eng.cfg, "compression", "none")
    assert eng._bucket_wire_format(sig_q, ps) == "none"


def test_negotiation_token_carries_wire_format(hvd):
    from horovod_tpu import runtime
    from horovod_tpu.ops.controller import entry_token, token_fields
    from horovod_tpu.ops.engine import TensorTableEntry
    ps = runtime._get_global_process_set()
    e = TensorTableEntry(name="t", op_type="allreduce",
                         arrays=[np.ones((4,), np.float32)],
                         process_set=ps, reduce_op=hvd_mod.Sum,
                         wire_format="int8")
    tok = entry_token(e)
    assert token_fields(tok)["s"][0][10] == "int8"
    # two processes configured differently produce DIFFERENT tokens —
    # the negotiated-format property
    e.wire_format = "none"
    assert entry_token(e) != tok


# ---------------------------------------------------------------------------
# autotune: the compression dimension
# ---------------------------------------------------------------------------

def test_autotune_compression_dim_pinned_off_without_config():
    from horovod_tpu.autotune import ParameterManager
    from horovod_tpu.config import Config
    cfg = Config()
    cfg.autotune = True
    pm = ParameterManager(cfg)
    # no HOROVOD_COMPRESSION → the lossy dimension must not be explored
    assert pm.current_compression() is False
    assert all(p[4] == 0.0 for p in pm._grid)


def test_autotune_explores_compression_when_configured():
    from horovod_tpu.autotune import ParameterManager
    from horovod_tpu.config import Config
    cfg = Config()
    cfg.autotune = True
    cfg.compression = "int8"
    cfg.autotune_warmup_samples = 0
    cfg.autotune_steps_per_sample = 1
    cfg.autotune_max_samples = 60
    pm = ParameterManager(cfg)
    assert pm.current_compression() is True     # starts at the config
    assert {p[4] for p in pm._grid} == {0.0, 1.0}
    # a workload where compression-off scores higher converges off: the
    # tuner may DISABLE the lossy wire, never force it on
    for _ in range(800):
        if pm.tuned:
            break
        bps = 1e9 if not pm.current_compression() else 1e5
        pm.record_cycle(nbytes=int(bps), elapsed_s=1.0)
    assert pm.tuned and pm.current_compression() is False
