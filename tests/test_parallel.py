"""Parallelism-layer tests: ring attention, Ulysses, pipeline, mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _helpers import dense_reference, make_qkv, sp_sharded as _sharded
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh, factor_mesh
from horovod_tpu.parallel.pipeline import pipeline_apply
from horovod_tpu.parallel.ring_attention import ring_attention
from horovod_tpu.parallel.ulysses import ulysses_attention


def _qkv(B=2, T=64, H=8, D=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
                 for _ in range(3))


def test_ring_attention_matches_reference(sp_mesh):
    q, k, v = _qkv()
    ref = ring_attention(q, k, v, axis_name=None, causal=True)
    out = _sharded(sp_mesh, lambda q, k, v: ring_attention(
        q, k, v, "sp", causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_non_causal(sp_mesh):
    q, k, v = _qkv(seed=3)
    ref = ring_attention(q, k, v, axis_name=None, causal=False)
    out = _sharded(sp_mesh, lambda q, k, v: ring_attention(
        q, k, v, "sp", causal=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_causality(sp_mesh):
    """Changing a future token must not change past outputs."""
    q, k, v = _qkv(seed=1)
    f = _sharded(sp_mesh, lambda q, k, v: ring_attention(
        q, k, v, "sp", causal=True))
    out1 = np.asarray(f(q, k, v))
    k2 = k.at[:, -1].set(99.0)
    v2 = v.at[:, -1].set(99.0)
    out2 = np.asarray(f(q, k2, v2))
    np.testing.assert_allclose(out1[:, :-1], out2[:, :-1], atol=1e-5)
    assert np.abs(out1[:, -1] - out2[:, -1]).max() > 1e-3


def test_ring_attention_gradients(sp_mesh):
    """Autodiff through the ring (ppermute transpose) matches reference."""
    q, k, v = _qkv(B=1, T=32, H=4, D=8, seed=2)

    def ref_loss(q, k, v):
        return (ring_attention(q, k, v, None, causal=True) ** 2).sum()

    ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

    def ring_loss(q, k, v):
        # differentiate the LOCAL loss: under shard_map every shard seeds
        # its own block's cotangent and the reverse ring delivers each k/v
        # block the contributions from every shard's loss — psum'ing the
        # loss first would double-count by a factor of sp (psum transpose)
        o = ring_attention(q, k, v, "sp", causal=True)
        return (o ** 2).sum()

    g = jax.jit(jax.shard_map(
        jax.grad(ring_loss, argnums=(0, 1, 2)), mesh=sp_mesh,
        in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))(q, k, v)
    for got, want in zip(g, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


def test_ring_attention_gqa_matches_repeated_kv(sp_mesh):
    """GQA grouped path (Hkv < H circulating the ring) must equal the
    naive repeat-kv-to-H reference — with 1/4 the ring bytes."""
    q, _, _ = _qkv(H=8, seed=5)
    _, k, v = _qkv(H=2, seed=6)  # 2 kv heads, group size 4
    rep = jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2)
    ref = ring_attention(q, *rep, axis_name=None, causal=True)
    # single-shard grouped
    got0 = ring_attention(q, k, v, axis_name=None, causal=True)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(ref), atol=1e-5)
    # ring grouped: only the 2 kv heads rotate
    out = _sharded(sp_mesh, lambda q, k, v: ring_attention(
        q, k, v, "sp", causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_gqa_gradients(sp_mesh):
    q, _, _ = _qkv(B=1, T=32, H=4, D=8, seed=7)
    _, k, v = _qkv(B=1, T=32, H=2, D=8, seed=8)

    def ref_loss(q, k, v):
        return (ring_attention(q, jnp.repeat(k, 2, axis=2),
                               jnp.repeat(v, 2, axis=2), None,
                               causal=True) ** 2).sum()

    ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g = jax.jit(jax.shard_map(
        jax.grad(lambda q, k, v: (ring_attention(
            q, k, v, "sp", causal=True) ** 2).sum(), argnums=(0, 1, 2)),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)
    for got, want in zip(g, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


def test_ulysses_gqa_grouped(sp_mesh):
    """Ulysses with Hkv divisible by sp scatters only the kv heads."""
    q, _, _ = _qkv(H=16, seed=9)
    _, k, v = _qkv(H=8, seed=10)  # Hkv=8 divisible by sp=8 → grouped path
    ref = ring_attention(q, jnp.repeat(k, 2, axis=2),
                         jnp.repeat(v, 2, axis=2), None, causal=True)
    out = _sharded(sp_mesh, lambda q, k, v: ulysses_attention(
        q, k, v, "sp", causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_gqa_indivisible_kv_falls_back(sp_mesh):
    """Hkv=2 < sp=8: repeat path still gives exact results."""
    q, _, _ = _qkv(H=16, seed=11)
    _, k, v = _qkv(H=2, seed=12)
    ref = ring_attention(q, jnp.repeat(k, 8, axis=2),
                         jnp.repeat(v, 8, axis=2), None, causal=True)
    out = _sharded(sp_mesh, lambda q, k, v: ulysses_attention(
        q, k, v, "sp", causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_matches_reference(sp_mesh):
    q, k, v = _qkv(seed=4)
    ref = ring_attention(q, k, v, axis_name=None, causal=True)
    out = _sharded(sp_mesh, lambda q, k, v: ulysses_attention(
        q, k, v, "sp", causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_rejects_indivisible_heads(sp_mesh):
    q, k, v = _qkv(H=4)  # 4 heads, sp=8
    with pytest.raises(ValueError, match="not divisible"):
        _sharded(sp_mesh, lambda q, k, v: ulysses_attention(
            q, k, v, "sp"))(q, k, v)


def test_pipeline_matches_sequential(hvd):
    """GPipe schedule == sequential application of all stages."""
    mesh = jax.make_mesh((8,), ("pp",))
    n_stages = 8
    rng = np.random.RandomState(0)
    # per-stage affine params, stacked on dim 0
    w = jnp.asarray(rng.normal(size=(n_stages, 4, 4)) * 0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(n_stages, 4)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(6, 2, 4)), jnp.float32)  # 6 microbatches

    def stage_fn(p, xb):
        return jnp.tanh(xb @ p["w"] + p["b"])

    out = jax.jit(jax.shard_map(
        lambda p, x: pipeline_apply(
            lambda sp_, xb: stage_fn(
                {"w": sp_["w"][0], "b": sp_["b"][0]}, xb), p, x, "pp"),
        mesh=mesh, in_specs=({"w": P("pp"), "b": P("pp")}, P()),
        out_specs=P(), check_vma=False))({"w": w, "b": b}, x)

    want = x
    for s in range(n_stages):
        want = jax.vmap(lambda xb, s=s: stage_fn(
            {"w": w[s], "b": b[s]}, xb))(want)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_mesh_config_and_factor(hvd):
    mc = factor_mesh(8)
    assert mc.n_devices == 8
    assert mc.tp == 2 and mc.sp == 2 and mc.pp == 2
    mc16 = factor_mesh(16)
    assert mc16.n_devices == 16 and mc16.dp == 2
    pm = ParallelMesh(MeshConfig(dp=2, pp=2, sp=1, tp=2))
    assert pm.mesh.axis_names == ("dp", "pp", "sp", "tp")
    assert pm.axis_size("dp") == 2


def test_mesh_too_few_devices(hvd):
    with pytest.raises(ValueError, match="devices"):
        ParallelMesh(MeshConfig(dp=16, pp=1, sp=1, tp=1))


def test_dedicated_ep_axis():
    """MeshConfig.ep creates a real mesh axis usable by shard_map."""
    import jax
    from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
    pm = ParallelMesh(MeshConfig(dp=2, ep=2, tp=2))
    assert pm.ep_axis == "ep"
    assert "ep" in pm.mesh.axis_names
    assert pm.mesh.shape["ep"] == 2
    assert pm.axis_size("ep") == 2
    # aliased default: ep rides the dp axis
    pm2 = ParallelMesh(MeshConfig(dp=4, tp=2))
    assert pm2.ep_axis == "dp" and "ep" not in pm2.mesh.axis_names
    assert pm2.axis_size("ep") == 4


def test_ring_attention_memory_scales_linearly(sp_mesh):
    """VERDICT r2 #7 done-criterion: per-step ring tiles are blockwise,
    so compiled temp memory grows ~linearly in sequence length (the old
    monolithic [B,H,Tl,Tl] tile grew quadratically once Tl exceeded the
    block size)."""
    def f(q, k, v):
        return ring_attention(q, k, v, "sp", causal=True)

    def temp_bytes(T):
        q = jnp.zeros((1, T, 4, 32), jnp.float32)
        c = jax.jit(jax.shard_map(
            f, mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)
        ).lower(q, q, q).compile()
        ma = c.memory_analysis()
        if ma is None:  # backend without memory analysis: nothing to check
            pytest.skip("no memory analysis on this backend")
        return ma.temp_size_in_bytes

    # 4x the sequence (per-shard 512 -> 2048, both past the 512 block
    # cap) must cost ~4x temp memory, not ~16x
    ratio = temp_bytes(16384) / temp_bytes(4096)
    assert ratio < 6.0, ratio


# --- flash kernel inside the ring (VERDICT r2 #7) ---------------------------

@pytest.mark.parametrize("causal,Hkv", [(True, 2), (False, 2), (True, 1)])
def test_ring_attention_kernel_path_interpret(causal, Hkv, pallas_interpret,
                                              hvd):
    """The ring path routes each per-step tile through the Pallas kernel
    when shapes fit (O(Tl·blk) per step instead of a [B,H,Tl,Tl] tile);
    Hkv=1 exercises the GQA grouped tiles through the merge."""
    from horovod_tpu.parallel.ring_attention import ring_attention
    mesh = jax.make_mesh((2,), ("sp",))
    q, k, v = make_qkv(1, 256, 2, Hkv, 64, seed=5)  # 128 per shard

    # confirm the kernel path is taken per shard (supported in interpret)
    assert fa.supported(q[:, :128], k[:, :128], v[:, :128], causal)

    out = _sharded(mesh, lambda q, k, v: ring_attention(
        q, k, v, axis_name="sp", causal=causal))(q, k, v)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


def test_ring_attention_kernel_path_grads_interpret(pallas_interpret, hvd):
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.parallel.ring_attention import ring_attention
    mesh = jax.make_mesh((2,), ("sp",))
    q, k, v = make_qkv(1, 256, 2, 2, 64, seed=7)

    def ring_loss(q, k, v):
        # local loss per shard: the reverse ring delivers every shard's
        # cotangents to each k/v block (see test_parallel.py rationale)
        o = ring_attention(q, k, v, "sp", causal=True)
        return (o ** 2).sum()

    gr = jax.jit(jax.shard_map(
        jax.grad(ring_loss, argnums=(0, 1, 2)), mesh=mesh,
        in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))(q, k, v)

    def loss_dense(q, k, v):
        return (dense_reference(q, k, v, True) ** 2).sum()

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=1e-3)
