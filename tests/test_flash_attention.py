"""Flash-attention kernel + blockwise local attention correctness.

The Pallas kernels are validated in interpret mode on the CPU mesh (the
same kernel code compiles via Mosaic on TPU — see the on-hardware bench);
the XLA blockwise fallback is validated directly.  Reference is dense
softmax attention in fp32.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import sp_sharded as _ring_sharded
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel.ring_attention import local_attention


def dense_reference(q, k, v, causal=True):
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def make_qkv(B, T, H, Hkv, D, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, T, H, D), dtype)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), dtype)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), dtype)
    return q, k, v


# One-block shapes and the pack (bb, hb) each must take; (1, 1) is the
# masked path.  In the last two the pack the rule would prefer does not
# fit: all six heads under a smaller budget, all six rows under the real.
PACKED_CASES = [
    # (B, T, H, Hkv, D), causal, dtype, VMEM budget, pack
    ((4, 128, 12, 12, 64), False, jnp.float32, None, (1, 12)),   # BERT
    ((4, 128, 12, 12, 64), False, jnp.bfloat16, None, (2, 12)),
    ((2, 128, 4, 4, 64), True, jnp.float32, None, (2, 4)),
    ((1, 128, 8, 2, 128), True, jnp.float32, None, (1, 8)),      # GQA
    ((2, 128, 8, 2, 64), False, jnp.float32, None, (1, 1)),      # GQA, D=64
    ((3, 128, 6, 6, 64), True, jnp.float32, 2 << 20, (1, 2)),
    ((6, 128, 2, 2, 128), False, jnp.float32, None, (3, 2)),
]


def _budget(monkeypatch, budget):
    if budget is not None:
        monkeypatch.setattr(fa, "_VMEM_BUDGET", budget)


def _tol(dtype):
    return 2e-3 if dtype == jnp.float32 else 3e-2


@pytest.mark.parametrize("shape,causal", [
    ((1, 256, 2, 2, 64), True),
    ((2, 256, 4, 2, 64), True),     # GQA
    ((1, 256, 2, 2, 128), False),
])
def test_pallas_kernel_interpret(shape, causal, monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    B, T, H, Hkv, D = shape
    q, k, v = make_qkv(B, T, H, Hkv, D)
    assert fa.supported(q, k, v, causal)
    out = fa.flash_attention(q, k, v, causal=causal)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("shape,causal,dtype,budget,pack", PACKED_CASES)
def test_packed_kernel_interpret(shape, causal, dtype, budget, pack,
                                 monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    _budget(monkeypatch, budget)
    B, T, H, Hkv, D = shape
    q, k, v = make_qkv(B, T, H, Hkv, D, dtype)
    assert fa.supported(q, k, v, causal)
    assert fa._pack(B, H, Hkv, T, T, D, q.dtype.itemsize) == pack
    out = fa.flash_attention(q, k, v, causal=causal)
    assert out.dtype == dtype and out.shape == q.shape
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=_tol(dtype),
                               rtol=_tol(dtype))


@pytest.mark.parametrize("shape,causal,dtype,budget,block", [
    ((1, 256, 4, 2, 64), True, jnp.float32, None, None),
    ((1, 256, 4, 2, 64), False, jnp.float32, None, None),
    # several blocks a sequence: the masked kernels under ``causal=``
    ((1, 256, 4, 2, 64), True, jnp.float32, None, 128),
    ((1, 256, 4, 2, 64), True, jnp.bfloat16, None, 128),
    ((2, 256, 2, 2, 128), False, jnp.float32, None, 128),
] + [case[:4] + (None,) for case in PACKED_CASES])
def test_pallas_kernel_grads_interpret(shape, causal, dtype, budget, block,
                                       monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    _budget(monkeypatch, budget)
    if block is not None:
        monkeypatch.setattr(fa, "_BLOCK", block)
    q, k, v = make_qkv(*shape, dtype, seed=3)

    def loss_f(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_r(q, k, v):
        return (dense_reference(q, k, v, causal) ** 2).sum()

    gf = jax.grad(loss_f, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    tol = 5e-3 if dtype == jnp.float32 else 6e-2
    for a, b in zip(gf, gr):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol,
                                   rtol=tol)


@pytest.mark.parametrize("T,Hkv,blk", [
    (1024, 2, 256),   # evenly-divided scan path
    (1024, 4, 256),
    (768, 4, 512),    # 768 % 512 != 0 → largest-divisor fallback (384)
    (640, 2, 512),    # divisor search lands on 320
    (521, 2, 512),    # prime T: no divisor ≥ 64 → single checkpointed tile
])
def test_blockwise_local_attention(T, Hkv, blk):
    # CPU backend → supported() is False → exercises the XLA blockwise
    # scan path, including the non-divisible-block divisor fallback
    q, k, v = make_qkv(1, T, 4, Hkv, 32, seed=1)
    assert not fa.supported(q, k, v)
    out = local_attention(q, k, v, causal=True, block_size=blk)
    ref = dense_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_blockwise_local_attention_grad():
    q, k, v = make_qkv(1, 512, 2, 2, 32, seed=2)

    def loss_f(q, k, v):
        o = local_attention(q, k, v, causal=True, block_size=128)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_r(q, k, v):
        return (dense_reference(q, k, v, True) ** 2).sum()

    gf = jax.grad(loss_f, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), atol=5e-3, rtol=5e-3)


# --- lse-exposing entry point (ring-step tile merging) ----------------------

@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_lse_interpret(causal, monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q, k, v = make_qkv(1, 256, 2, 2, 64)
    out, lse = fa.flash_attention_lse(q, k, v, causal=causal)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # lse must equal the dense logsumexp of the (masked) scaled scores
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    if causal:
        T = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5)


@pytest.mark.parametrize("shape", [
    (1, 128, 2, 1, 64),      # masked: a GQA group at D=64
    (2, 128, 4, 4, 64),      # packed, two heads a lane tile
    (1, 128, 4, 2, 128),     # packed, GQA
])
def test_flash_attention_lse_grads_interpret(shape, monkeypatch):
    """Gradients flow through BOTH outputs (the lse cotangent folds into
    the backward kernels' delta term)."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q, k, v = make_qkv(*shape, seed=3)
    g = shape[2] // shape[3]

    def loss_kernel(q, k, v):
        out, lse = fa.flash_attention_lse(q, k, v, causal=True)
        return (out ** 2).sum() + 0.3 * (lse ** 2).sum()

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       jnp.repeat(k, g, 2).astype(jnp.float32)
                       ) * (q.shape[-1] ** -0.5)
        T = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None],
                      s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        out = jnp.einsum("bhqk,bkhd->bqhd", p,
                         jnp.repeat(v, g, 2).astype(jnp.float32))
        return (out ** 2).sum() + 0.3 * (lse ** 2).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=1e-3)


# --- flash kernel inside the ring (VERDICT r2 #7) ---------------------------

@pytest.mark.parametrize("causal,Hkv", [(True, 2), (False, 2), (True, 1)])
def test_ring_attention_kernel_path_interpret(causal, Hkv, monkeypatch,
                                              hvd):
    """The ring path routes each per-step tile through the Pallas kernel
    when shapes fit (O(Tl·blk) per step instead of a [B,H,Tl,Tl] tile);
    Hkv=1 exercises the GQA grouped tiles through the merge."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    from horovod_tpu.parallel.ring_attention import ring_attention
    mesh = jax.make_mesh((2,), ("sp",))
    q, k, v = make_qkv(1, 256, 2, Hkv, 64, seed=5)  # 128 per shard

    # confirm the kernel path is taken per shard (supported in interpret)
    assert fa.supported(q[:, :128], k[:, :128], v[:, :128], causal)

    out = _ring_sharded(mesh, lambda q, k, v: ring_attention(
        q, k, v, axis_name="sp", causal=causal))(q, k, v)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


def test_ring_attention_kernel_path_grads_interpret(monkeypatch, hvd):
    monkeypatch.setattr(fa, "_INTERPRET", True)
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


def test_block_size_is_a_module_constant(monkeypatch):
    """``_BLOCK`` sets the kernel grid; a value the sequence length cannot
    honor makes supported() fall back to XLA attention."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q, k, v = make_qkv(1, 256, 2, 2, 64)
    assert fa._block_sizes(1024, 1024) == (512, 512)

    monkeypatch.setattr(fa, "_BLOCK", 128)
    assert fa._block_sizes(256, 256) == (128, 128)
    assert fa.supported(q, k, v, True)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = dense_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=2e-3, rtol=2e-3)

    # 192 does not divide T=256 -> kernel unsupported, caller falls back
    monkeypatch.setattr(fa, "_BLOCK", 192)
    assert not fa.supported(q, k, v, True)


def test_refusal_on_a_tpu_backend_is_logged_once_per_shape(
        monkeypatch, caplog):
    """On a TPU the XLA path is a slower program than the one the caller
    named: supported() says which test refused the shape, once.  On any
    other backend the XLA path is the expected one and nothing is said."""
    import logging

    q = jax.ShapeDtypeStruct((1, 128, 4, 48), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 128, 2, 48), jnp.bfloat16)
    fa._warn_refused.cache_clear()
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        assert not fa.supported(q, kv, kv)
        assert not caplog.records
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for _ in range(2):
            assert not fa.supported(q, kv, kv)
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "falling back" in said[0]
    assert "flash_attention" in said[0] and "head_dim 48" in said[0]


# --- the pack rule, and which path a traced call took ------------------------

def _eqns(jaxpr, path=()):
    """Every equation under ``jaxpr`` with the primitives that enclose
    it: ``(path, eqn)``."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, path + (eqn.primitive.name,))


def _pallas_calls(fn, *args, scratch=False):
    """[(name, grid, [block shapes])] of every pallas_call ``fn`` traces;
    with ``scratch`` a fourth entry, [(scratch shape, dtype)]."""
    found = []
    for _, eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "pallas_call":
            gm = eqn.params["grid_mapping"]
            call = (
                eqn.params["name"], tuple(gm.grid),
                [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
                 for bm in gm.block_mappings])
            if scratch:
                invars = eqn.params["jaxpr"].invars
                held = invars[len(invars) - gm.num_scratch_operands:]
                call += ([(v.aval.shape, str(v.aval.dtype)) for v in held],)
            found.append(call)
    return found


def _kernel_counts():
    from horovod_tpu import metrics
    family = metrics.registry().to_dict().get("hvd_flash_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"],
             s["labels"]["layout"]): s["value"]
            for s in family.get("series", [])}


def _grew(before):
    """The series of ``hvd_flash_kernel_total`` that moved since
    ``before = _kernel_counts()``."""
    after = _kernel_counts()
    return {key for key in after if after[key] != before.get(key, 0)}


def _grad_all(q, k, v, causal):
    return jax.grad(lambda q, k, v: (fa.flash_attention(
        q, k, v, causal=causal).astype(jnp.float32) ** 2).sum(),
        (0, 1, 2))(q, k, v)


def test_pack_rule():
    # BERT-base as benchmarked: every head of a row in one grid step
    bb, hb = fa._pack(32, 12, 12, 128, 128, 64, 2)
    assert hb == 12 and (32 // bb) * (12 // hb) < 64
    # a long sequence is several blocks: the masked path
    assert fa._block_sizes(2048, 2048) == (512, 512)
    assert fa._pack(2, 16, 16, 2048, 2048, 128, 2) == (1, 1)
    assert fa._pack(2, 16, 4, 2048, 512, 128, 2) == (1, 1)
    # a head's scores at T=512 are 1 MB: a smaller pack, not none
    assert fa._pack(8, 16, 16, 512, 512, 128, 2) == (1, 2)
    # two heads share a lane tile at D=64: no tile may meet a GQA group
    assert fa._pack(4, 8, 2, 128, 128, 64, 2) == (1, 1)
    assert fa._pack(4, 3, 3, 128, 128, 64, 2) == (1, 1)   # odd head count
    assert fa._pack(2, 4, 4, 128, 128, 192, 2) == (1, 1)  # 192 lanes
    # nothing chosen is over the budget, and it divides (B, H) in whole
    # lane tiles and GQA groups
    for B in (1, 2, 3, 8, 32):
        for H, Hkv in ((2, 2), (6, 6), (12, 12), (16, 4), (16, 16), (64, 64)):
            for T in (128, 256, 512):
                for D in (64, 128, 256):
                    for itemsize in (2, 4):
                        bb, hb = fa._pack(B, H, Hkv, T, T, D, itemsize)
                        g = H // Hkv
                        assert B % bb == 0 and H % hb == 0
                        if (bb, hb) == (1, 1):
                            continue
                        assert hb % (g * max(1, 128 // D)) == 0
                        assert fa._packed_resident(
                            bb, hb, g, T, T, D, itemsize) <= fa._VMEM_BUDGET


def test_causal_over_several_blocks_builds_the_masked_kernels(monkeypatch):
    """``causal=True`` without ``mask=``: the masked family's three calls,
    grids and blocks as ``test_masked_forward_specs`` and
    ``test_masked_backward_specs`` pin them (both of a group's two heads a
    forward step and a ``dq`` step; ``dkv`` one step a live pair of
    tiles, holding one query tile of the group and its ranges a row a
    bound), on the tile classes of :func:`causal_ranges`; at ``head_dim``
    128 the blocks are cut from the caller's ``[B, T, H*D]``, a head a
    lane block."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    B, T, H, Hkv, D = 1, 2048, 4, 2, 128
    bq = bk = 512
    nq = T // bq
    g = H // Hkv
    classes = fa.tile_classes(fa.causal_ranges(T)[None], bq, bk, T)[0]
    assert [int((classes == c).sum()) for c in (1, 2, 0)] == [4, 6, 6]
    q, k, v = (jax.ShapeDtypeStruct((B, T, h, D), jnp.bfloat16)
               for h in (H, Hkv, Hkv))
    calls = _pallas_calls(lambda q, k, v: _grad_all(q, k, v, True), q, k, v)
    kvb, rng = (1, T, D), (1, bq, 4)
    grp, tile, rows = (1, bq, g * D), (1, bk, D), (1, g, nq, bq)
    assert calls == [
        ("hvd_flash_fwd", (B, H // g, nq), [grp, kvb, kvb, rng, grp, rows]),
        ("hvd_flash_dq", (B, H // g, nq),
         [grp, kvb, kvb, grp, rows, rows, rng, grp]),
        ("hvd_flash_dkv", (B, Hkv, 10),
         [grp, tile, tile, grp, rows, rows, (1, 4, bq), tile, tile]),
    ]


def test_packed_path_specs_and_counter(monkeypatch):
    """BERT's block: one forward and ONE backward kernel, all heads of
    two rows a grid step, in the caller's layout (no transposes); the
    counter says which path each traced call took."""
    from horovod_tpu import metrics
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(metrics, "ACTIVE", True)
    B, T, H, D = 32, 128, 12, 64
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    before = _kernel_counts()
    calls = _pallas_calls(lambda q, k, v: _grad_all(q, k, v, False), q, q, q)
    blk, row = (2, T, H * D), (2, H, 1, T)
    assert calls == [
        ("hvd_flash_fwd", (16, 1), [blk, blk, blk, blk, row]),
        ("hvd_flash_bwd", (16, 1), [blk, blk, blk, blk, row, row,
                                    blk, blk, blk]),
    ]
    jaxpr = str(jax.make_jaxpr(
        lambda q, k, v: _grad_all(q, k, v, False))(q, q, q))
    assert "transpose[permutation=(0, 2, 1, 3)]" not in jaxpr
    assert _grew(before) == {("fwd", "packed", "rows"),
                             ("bwd", "packed", "rows")}

    # several blocks: the masked kernels, at head_dim 128 on the caller's
    # layout too, at 64 (half a lane tile a head) transposed around them
    for D, layout in ((128, "rows"), (64, "heads")):
        before = _kernel_counts()
        long = jax.ShapeDtypeStruct((1, 1024, 2, D), jnp.bfloat16)
        jax.make_jaxpr(lambda q, k, v: _grad_all(q, k, v, True))(
            long, long, long)
        assert _grew(before) == {(kernel, "masked", layout)
                                 for kernel in ("fwd", "dq", "dkv")}


def _described_chip(monkeypatch):
    """The sharding of one chip of a v5e that is described, not attached,
    with jax told its backend is a TPU: what the ``*_lower_for_the_chip``
    tests compile for (interpret mode cannot see tiling or VMEM)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no TPU topology to compile for: {e}")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return SingleDeviceSharding(topo.devices[0])


def test_packed_kernels_lower_for_the_chip(monkeypatch):
    """Mosaic takes the packed kernels at the benchmark's shape and at a
    causal GQA one: compiled here for a v5e that is described, not
    attached (interpret mode cannot see tiling or VMEM)."""
    one_chip = _described_chip(monkeypatch)
    for (B, T, H, Hkv, D), causal in (((32, 128, 12, 12, 64), False),
                                      ((2, 256, 8, 2, 128), True)):
        assert fa._pack(B, H, Hkv, T, T, D, 2) != (1, 1)
        q, k = (jax.ShapeDtypeStruct((B, T, h, D), jnp.bfloat16,
                                     sharding=one_chip) for h in (H, Hkv))
        assert fa.supported(q, k, k, causal)
        text = jax.jit(lambda q, k, v: _grad_all(q, k, v, causal)).lower(
            q, k, k).compile().as_text()
        assert "hvd_flash_fwd" in text and "hvd_flash_bwd" in text
        assert "hvd_flash_dq" not in text


# ------------------------------------------------------ the masked path

def _block_diffusion_ranges(L, bk):
    """[xt ; x0]: xt sees its own block of xt and x0's earlier blocks; x0
    sees x0's own and earlier blocks."""
    block = np.arange(L) // bk
    r = np.zeros((2 * L, 4), np.int32)
    r[:L, 0], r[:L, 1] = block * bk, (block + 1) * bk
    r[:L, 2], r[:L, 3] = L, L + block * bk
    r[L:, 0], r[L:, 1] = L, L + (block + 1) * bk
    return r


MASKS = {
    "block-diffusion": lambda T: _block_diffusion_ranges(T // 2, 4),
    "causal": fa.causal_ranges,
    "window": lambda T: fa.window_ranges(T, 100),
}


def _packed_documents(T):
    """``[2, T, 4]``: causal inside documents, cut elsewhere in each batch
    row, off every tile's and sub-tile's edge."""
    rows = []
    for cuts in ((0, T // 3 + 7, T // 2 + 90, T), (0, T // 4 - 11, T)):
        r = fa.causal_ranges(T)
        for lo, hi in zip(cuts, cuts[1:]):
            r[lo:hi, 0] = lo
        rows.append(r)
    return np.stack(rows)


def _first_or_last(T):
    """``[T, 4]``: a row in three sees only a few of the first keys, so
    none in any later sub-tile its tile visits; the next only a few of
    the last, none before the last sub-tile visited; the third a stretch
    across every sub-tile, so that all of them are visited, masked."""
    i = np.arange(T)
    r = np.zeros((T, 4), np.int32)
    r[:, 0] = np.select([i % 3 == 0, i % 3 == 1], [0, T - 1 - i % 7], i % 50)
    r[:, 1] = np.select([i % 3 == 0, i % 3 == 1], [1 + i % 7, T], T - i % 60)
    return r


# the masks a tile of which is walked by sub-tiles (``tiles`` below)
SUB_MASKS = dict(MASKS, **{
    "window-of-a-tile": lambda T: fa.window_ranges(T, 256),   # as Phi's
    "packed-documents": _packed_documents,
    "first-or-last": _first_or_last})


def _tiles(monkeypatch, tiles):
    """``tiles = (block, sub)``: positions a tile and a sub-tile; ``sub``
    None leaves ``_SUB``, which no tile of 128 holds twice."""
    block, sub = tiles
    monkeypatch.setattr(fa, "_BLOCK", block)
    if sub:
        monkeypatch.setattr(fa, "_SUB", sub)
    return block, max(512, 2 * block)


def _dense_masked(q, k, v, live):
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(live[:, None] if live.ndim == 3 else live[None, None],
                  s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _dense_masked_lse(q, k, live):
    k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    return jax.nn.logsumexp(jnp.where(
        live[:, None] if live.ndim == 3 else live[None, None], s, -jnp.inf),
        -1)


# How many query heads a masked forward grid step takes, at every branch
# of ``_fwd_heads``: (H, Hkv, D), the step's VMEM budget, the heads.
HEAD_CASES = {
    "g8-whole-group": ((8, 1, 64), None, 8),
    "g8-split-group": ((8, 1, 64), 3 << 20, 4),
    "g8-one-head": ((8, 1, 64), 1 << 20, 1),      # not even two fit
    "g2-d128": ((4, 2, 128), None, 2),
    "g1-d128": ((2, 2, 128), None, 1),
    "g8-d128": ((8, 1, 128), None, 8),
}
WHOLE = (128, None)          # tiles of 128: a mixed tile is taken whole
MASKED_CASES = (
    [(mask, per_batch, "g8-whole-group", WHOLE) for mask in sorted(MASKS)
     for per_batch in (False, True)]
    + [("block-diffusion", per_batch, heads, WHOLE) for heads in HEAD_CASES
       if heads != "g8-whole-group" for per_batch in (False, True)]
    # a mixed tile by its sub-tiles: 2 x 2 of them, and the chip's 4 x 4
    + [(mask, False, "g2-d128", (256, 128))
       for mask in sorted(set(SUB_MASKS) - {"window"})]
    + [(mask, False, "g8-whole-group", (256, 128))
       for mask in ("causal", "first-or-last")]
    + [("block-diffusion", False, "g2-d128", (512, 128))])


def _case_id(mask, per_batch, heads, tiles):
    return (f"{mask}-{'mask-per-row' if per_batch else 'one-mask'}-{heads}"
            + ("" if tiles == WHOLE else "-tiles-of-%d-by-%d" % tiles))


@pytest.mark.parametrize("mask,per_batch,heads,tiles", MASKED_CASES,
                         ids=[_case_id(*case) for case in MASKED_CASES])
def test_masked_kernels_match_dense_masked_attention(mask, per_batch, heads,
                                                     tiles, monkeypatch):
    """Forward (out and lse, which ``dq`` and ``dkv`` read) and all three
    gradients, the mask known where the call is built (numpy) or traced
    per batch row, a forward step taking a whole GQA group, a part of
    one, or one head; at ``head_dim`` 64 transposed around the kernels
    (``heads``), at 128 on the caller's layout (``rows``: groups of 1, 2
    and 8); a mixed tile taken whole, or walked by its live sub-tiles
    (``tiles``) under every mask, two and eight heads a step."""
    from horovod_tpu import metrics
    monkeypatch.setattr(fa, "_INTERPRET", True)
    blk, T = _tiles(monkeypatch, tiles)
    monkeypatch.setattr(metrics, "ACTIVE", True)
    (H, Hkv, D), budget, hb = HEAD_CASES[heads]
    before = _kernel_counts()
    if budget is not None:
        monkeypatch.setattr(fa, "_MASKED_STEP_VMEM", budget)
    B = 2
    assert fa._fwd_heads(H // Hkv, blk, blk, D, T // blk, T, 4) == hb
    q, k, v = make_qkv(B, T, H, Hkv, D)
    ranges = SUB_MASKS[mask](T)
    live = jnp.asarray(fa.dense_mask(ranges, T))
    given = (jnp.asarray(np.stack([ranges] * B)) if per_batch else
             jnp.asarray(ranges) if ranges.ndim == 3 else ranges)
    assert fa.supported(q, k, v, False, given)
    if mask == "block-diffusion" and tiles == WHOLE:
        # a query tile whose live key tiles are all mixed, and one with a
        # single live tile
        classes = fa.tile_classes(ranges[None], 128, 128, T)[0]
        n_full, n_live = (classes == 2).sum(-1), (classes >= 1).sum(-1)
        assert ((n_full == 0) & (n_live >= 2)).any() and (n_live == 1).any()
    if tiles != WHOLE:
        _, classes, sub, *_ = fa._mask_plan(given, blk, blk, T)
        codes = np.asarray(fa.sub_codes(sub.words, np.prod(sub.grid)))
        mixed = codes[np.asarray(classes) == 1]
        assert (mixed == 1).any() and len(mixed)
        if mask == "first-or-last":        # every sub-tile visited, masked
            assert (codes == 1).all()
        else:                              # some skipped, some unmasked
            assert (mixed == 0).any() and (mixed == 2).any()

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v) ** 2).sum()

    out, lse = fa.flash_attention_lse(q, k, v, mask=given)
    np.testing.assert_allclose(out, _dense_masked(q, k, v, live),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, _dense_masked_lse(q, k, live),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=given)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _dense_masked(q, k, v, live)),
                    (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")
    assert _grew(before) == {
        (kernel, "masked", "rows" if D % 128 == 0 else "heads")
        for kernel in ("fwd", "dq", "dkv")}


# How many query heads a ``dq`` grid step takes, at every branch of
# ``_dq_heads``, on both layouts: (H, Hkv, D, Dv), the step's VMEM budget,
# the heads.  ``dkv`` takes the whole group a step whatever that says.
BACKWARD_CASES = {
    "g8-whole-group": ((8, 1, 64, 64), None, 8),
    "g8-split-group": ((8, 1, 64, 64), 4 << 20, 4),
    "g8-one-head": ((8, 1, 64, 64), 1 << 20, 1),      # not even two fit
    "g2-values-twice-as-wide": ((4, 2, 64, 128), None, 2),   # the Phi call
    "g1-d128": ((2, 2, 128, 128), None, 1),
    "g8-d128": ((8, 1, 128, 128), None, 8),
    "g4-d128-split-group": ((8, 2, 128, 128), 4 << 20, 2),
}
BACKWARD_MASKS = (
    [("block-diffusion", per_batch, heads, WHOLE) for heads in BACKWARD_CASES
     for per_batch in (False, True)]
    + [(mask, False, heads, WHOLE) for mask in ("causal", "window")
       for heads in ("g2-values-twice-as-wide", "g8-d128")]
    # a mixed tile by its sub-tiles, the ``lse`` cotangent folded in
    + [(mask, False, "g2-values-twice-as-wide", (256, 128))
       for mask in ("window-of-a-tile", "packed-documents", "first-or-last")]
    + [(mask, False, "g8-d128", (256, 128))
       for mask in ("causal", "block-diffusion")])


@pytest.mark.parametrize("mask,per_batch,heads,tiles", BACKWARD_MASKS,
                         ids=[_case_id(*case) for case in BACKWARD_MASKS])
def test_masked_backward_matches_dense_masked_attention(mask, per_batch,
                                                        heads, tiles,
                                                        monkeypatch):
    """``dq``, ``dk`` and ``dv`` of a loss on ``out`` AND on ``lse`` (whose
    cotangent folds into ``delta`` before the kernels) against dense masked
    attention: a ``dq`` step taking a whole GQA group, a part of one, or
    one head; values twice as wide as keys; transposed around the kernels
    at ``head_dim`` 64, on the caller's layout at 128; the mask known where
    the call is built or traced a batch row; a query tile whose live tiles
    are all mixed and one with a single live tile; a mixed tile taken
    whole, or walked by its live sub-tiles (``tiles``)."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    blk, T = _tiles(monkeypatch, tiles)
    (H, Hkv, D, Dv), budget, hb = BACKWARD_CASES[heads]
    if budget is not None:
        monkeypatch.setattr(fa, "_MASKED_STEP_VMEM", budget)
    B = 2
    assert fa._dq_heads(H // Hkv, blk, blk, D, T // blk, T, 4, Dv) == hb
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(B, T, h, d), jnp.float32)
               for h, d in ((H, D), (Hkv, D), (Hkv, Dv)))
    w_out = jnp.asarray(rng.randn(B, T, H, Dv), jnp.float32)
    w_lse = jnp.asarray(rng.randn(B, H, T), jnp.float32)
    ranges = SUB_MASKS[mask](T)
    live = jnp.asarray(fa.dense_mask(ranges, T))
    given = (jnp.asarray(np.stack([ranges] * B)) if per_batch else
             jnp.asarray(ranges) if ranges.ndim == 3 else ranges)
    if mask == "block-diffusion" and tiles == WHOLE:
        classes = fa.tile_classes(ranges[None], 128, 128, T)[0]
        n_full, n_live = (classes == 2).sum(-1), (classes >= 1).sum(-1)
        assert ((n_full == 0) & (n_live >= 2)).any() and (n_live == 1).any()

    def loss(attend):
        def of(q, k, v):
            out, lse = attend(q, k, v)
            return (out * w_out).sum() + (lse * w_lse).sum()
        return of

    got = jax.grad(loss(lambda q, k, v: fa.flash_attention_lse(
        q, k, v, mask=given)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: (
        _dense_masked(q, k, v, live), _dense_masked_lse(q, k, live))),
        (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")


def _masked_routes(q, k, v, mask, scale, w_out, w_lse):
    """``(out, lse, dq, dk, dv)`` of the masked kernels on the caller's
    layout (``rows``) and transposed around them (``heads``), each brought
    back to ``[B, T, H, D]`` / ``[B, H, nq, bq]``."""
    D, Dv = q.shape[3], v.shape[3]
    static = fa._StaticMask(mask) if isinstance(mask, np.ndarray) else None

    def run(widths, put, back):
        (out, lse), vjp = jax.vjp(
            lambda q, k, v: fa._masked_attention_lse(
                q, k, v, None if static else mask, static, scale, widths),
            put(q), put(k), put(v))
        dq, dk, dv = vjp((put(w_out), w_lse))
        return (back(out, Dv), lse, back(dq, D), back(dk, D), back(dv, Dv))

    swap = lambda x, d=None: x.transpose(0, 2, 1, 3)
    rows = run((D, Dv), lambda x: x.reshape(*x.shape[:2], -1),
               lambda x, d: x.reshape(*x.shape[:2], -1, d))
    return rows, run(None, swap, swap)


@pytest.mark.parametrize("H,Hkv,Dv,per_batch", [
    (4, 2, 128, False), (8, 1, 128, True), (2, 2, 256, False)],
    ids=["g2", "g8-mask-per-row", "g1-values-256"])
def test_rows_and_heads_routes_are_equal_to_the_bit(H, Hkv, Dv, per_batch,
                                                    monkeypatch):
    """One set of kernel bodies, two ways of building specs and slicing
    refs: for equal inputs ``out``, ``lse``, ``dq``, ``dk``, ``dv`` (the
    ``lse`` cotangent folded in) are the same bits on either route."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_BLOCK", 128)
    B, T, D = 2, 512, 128
    rng = np.random.RandomState(5)
    q, k, v, w_out = (jnp.asarray(rng.randn(B, T, h, d), jnp.bfloat16)
                      for h, d in ((H, D), (Hkv, D), (Hkv, Dv), (H, Dv)))
    w_lse = jnp.asarray(rng.randn(B, H, T // 128, 128), jnp.float32)
    ranges = _block_diffusion_ranges(T // 2, 4)
    mask = jnp.asarray(np.stack([ranges] * B)) if per_batch else ranges
    rows, heads = _masked_routes(q, k, v, mask, D ** -0.5, w_out, w_lse)
    for a, b, name in zip(rows, heads, ("out", "lse", "dq", "dk", "dv")):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=name)
        assert np.isfinite(np.asarray(a, np.float32)).all(), name


def _rank4_transposes(text):
    import re
    return re.findall(r"stablehlo\.transpose[^\n]*: \(tensor<(?:\d+x){4}",
                      text)


def test_rows_route_lowers_with_no_transpose_around_the_kernels(monkeypatch):
    """The jitted forward and backward at ``head_dim`` 128: the operands
    reach the kernels by reshapes, which are free, and no rank-4
    ``transpose`` is left in the lowered module; at 64 the transposed
    route has them (what the pattern finds)."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    ranges = _block_diffusion_ranges(512, 4)

    def lowered(D):
        q, k = (jax.ShapeDtypeStruct((2, 1024, h, D), jnp.bfloat16)
                for h in (8, 2))
        return jax.jit(lambda q, k, v: jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, mask=ranges).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v)).lower(q, k, k).as_text()

    assert not _rank4_transposes(lowered(128))
    assert _rank4_transposes(lowered(64))


def test_forward_heads_a_step_rule():
    # the benchmark's SDAR cell: four of a group's eight heads a step
    assert fa._fwd_heads(8, 512, 512, 128, 16, 8192, 2) == 4
    # one query head a kv head: nothing to share
    assert fa._fwd_heads(1, 512, 512, 128, 16, 8192, 2) == 1
    # short sequences: the whole group
    assert fa._fwd_heads(8, 128, 128, 64, 4, 512, 4) == 8
    assert fa._fwd_heads(6, 512, 512, 64, 4, 2048, 2) == 6
    # float32 at head_dim 256: a part of the group
    assert fa._fwd_heads(8, 512, 512, 256, 8, 4096, 4) == 2
    # whatever is chosen divides the group and fits, or is one head
    for g in (1, 2, 3, 4, 6, 8, 16):
        for bq in (128, 256, 512):
            for D in (64, 128, 256):
                for T in (1024, 8192, 32768):
                    for itemsize in (2, 4):
                        hb = fa._fwd_heads(g, bq, bq, D, T // bq, T, itemsize)
                        assert g % hb == 0
                        blocks, scratch, tiles = fa._fwd_step_bytes(
                            hb, bq, bq, D, T // bq, T, itemsize)
                        assert hb == 1 or (2 * blocks + scratch + tiles
                                           <= fa._MASKED_STEP_VMEM)


def test_backward_heads_a_step_rule():
    # the benchmark's SDAR cell: four of a group's eight heads a step
    assert fa._dq_heads(8, 512, 512, 128, 16, 8192, 2) == 4
    # the Phi cell's calls: both heads of a pair, values twice as wide
    assert fa._dq_heads(2, 512, 512, 64, 16, 8192, 2, 128) == 2
    # Llama-3-8B's heads under causal training at 4,096 positions
    assert fa._dq_heads(4, 512, 512, 128, 8, 4096, 2) == 4
    # one query head a kv head: nothing to share
    assert fa._dq_heads(1, 512, 512, 128, 16, 8192, 2) == 1
    # short sequences: the whole group
    assert fa._dq_heads(8, 128, 128, 64, 4, 512, 4) == 8
    # float32 at head_dim 256: a part of the group, as the forward
    assert fa._dq_heads(8, 512, 512, 256, 8, 4096, 4) == 2
    # its step holds do and dq too: at 32,768 positions of head_dim 64 two
    # heads where the forward takes four
    assert fa._dq_heads(8, 512, 512, 64, 64, 32768, 2) == 2
    assert fa._fwd_heads(8, 512, 512, 64, 64, 32768, 2) == 4
    # whatever is chosen divides the group and fits, or is one head
    for g in (1, 2, 3, 4, 6, 8, 16):
        for bq in (128, 256, 512):
            for D, Dv in ((64, 64), (64, 128), (128, 128), (256, 256)):
                for T in (1024, 8192, 32768):
                    for itemsize in (2, 4):
                        shapes = (bq, bq, D, T // bq, T, itemsize, Dv)
                        hb = fa._dq_heads(g, *shapes)
                        assert g % hb == 0
                        blocks, scratch, tiles = fa._dq_step_bytes(
                            hb, *shapes)
                        assert hb == 1 or (2 * blocks + scratch + tiles
                                           <= fa._MASKED_STEP_VMEM)
                        # never more than the forward, which holds less
                        assert hb <= fa._fwd_heads(g, *shapes)


def test_masked_forward_specs(monkeypatch):
    """A forward grid step's blocks: four of a group's eight query tiles
    on their kv head's whole keys and values.  At ``head_dim`` 128 every
    block is cut from the caller's layout: four heads are 512 lanes of
    ``[B, T, H*D]``, a kv head's keys 128 lanes of ``[B, T, Hkv*D]``."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    B, T, H, Hkv, D = 2, 2048, 16, 2, 128
    bq, nq, g = 512, 4, 4
    q, k = (jax.ShapeDtypeStruct((B, T, h, D), jnp.bfloat16)
            for h in (H, Hkv))
    ranges = _block_diffusion_ranges(T // 2, 4)
    calls = dict((name, (grid, blocks)) for name, grid, blocks in
                 _pallas_calls(lambda q, k, v: jax.grad(
                     lambda q, k, v: fa.flash_attention(
                         q, k, v, mask=ranges).astype(jnp.float32).sum(),
                     (0, 1, 2))(q, k, v), q, k, k))
    kvb = (1, T, D)
    assert calls["hvd_flash_fwd"] == ((B, H // g, nq), [
        (1, bq, g * D), kvb, kvb, (1, bq, 4), (1, bq, g * D),
        (1, g, nq, bq)])


@pytest.mark.parametrize("D,Dv", [(128, 128), (64, 128)],
                         ids=["rows", "heads-values-128"])
def test_masked_backward_specs(D, Dv, monkeypatch):
    """The backward's two calls.  ``dq``: the forward's grid, ``hb`` heads
    of a group a step on their kv head's whole keys and values, with
    ``do``, their rows of ``lse`` and of ``delta`` and the tile's ranges;
    in scratch the float32 accumulator ``[hb, bq, D]``, ``lse`` and
    ``delta`` as columns over the lanes and the ranges over the lanes.
    ``dkv``: a step a live pair of tiles, the group's query tiles on one
    key tile, the ranges a row a bound ``[1, 4, bq]``, two float32
    accumulators and, the mask cutting tiles that are walked by sub-tiles,
    the query tile's rows of ``lse`` and ``delta`` ``[2, g, 1, bq]``.  On
    the caller's layout at ``head_dim`` 128, transposed
    around the kernels at 64 (values 128 wide: the Phi call)."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    B, T, H, Hkv = 2, 2048, 16, 4
    bq, nq, g = 512, 4, 4
    hb = fa._dq_heads(g, bq, bq, D, nq, T, 2, Dv)
    assert hb == 4
    q, k, v = (jax.ShapeDtypeStruct((B, T, h, d), jnp.bfloat16)
               for h, d in ((H, D), (Hkv, D), (Hkv, Dv)))
    ranges = _block_diffusion_ranges(T // 2, 4)
    classes = fa.tile_classes(ranges[None], bq, bq, T)
    P = fa._pair_table(classes)[1]
    assert P == int((classes >= 1).sum())
    calls = {name: rest for name, *rest in _pallas_calls(
        lambda q, k, v: jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, mask=ranges).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v), q, k, v, scratch=True)}
    if D % 128 == 0:
        blk = lambda heads, n, d: (1, n, heads * d)
    else:
        blk = lambda heads, n, d: (1, heads, n, d)
    stats = lambda heads: (1, heads, nq, bq)
    f32 = lambda *shape: (shape, "float32")
    assert calls["hvd_flash_dq"] == [(B, H // hb, nq), [
        blk(hb, bq, D), blk(1, T, D), blk(1, T, Dv), blk(hb, bq, Dv),
        stats(hb), stats(hb), (1, bq, 4), blk(hb, bq, D)],
        [f32(hb, bq, D), f32(hb, bq, 128), f32(hb, bq, 128),
         ((4, bq, 128), "int32")]]
    assert calls["hvd_flash_dkv"] == [(B, Hkv, P), [
        blk(g, bq, D), blk(1, bq, D), blk(1, bq, Dv), blk(g, bq, Dv),
        stats(g), stats(g), (1, 4, bq), blk(1, bq, D), blk(1, bq, Dv)],
        [f32(bq, D), f32(bq, Dv), f32(2, g, 1, bq)]]


def test_causal_over_several_blocks_agrees_with_dense(monkeypatch):
    """``causal=True`` is :func:`causal_ranges`: values and the three
    gradients against the dense reference, over a GQA group."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_BLOCK", 128)
    q, k, v = make_qkv(1, 256, 4, 2, 64)
    np.testing.assert_allclose(fa.flash_attention(q, k, v, causal=True),
                               dense_reference(q, k, v, True),
                               atol=2e-5, rtol=2e-5)
    got = _grad_all(q, k, v, True)
    want = jax.grad(lambda q, k, v: (dense_reference(q, k, v, True)
                                     ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("bq,bk", [(128, 128), (64, 256)])
def test_tile_classes_against_a_brute_force_count(mask, bq, bk):
    T = 1024
    ranges = MASKS[mask](T)
    live = fa.dense_mask(ranges, T)
    tiles = live.reshape(T // bq, bq, T // bk, bk).transpose(0, 2, 1, 3)
    want = np.where(tiles.all((2, 3)), 2, np.where(tiles.any((2, 3)), 1, 0))
    got = fa.tile_classes(ranges[None], bq, bk, T)
    assert isinstance(got, np.ndarray) and (got[0] == want).all()
    # the same table from a traced mask
    traced = jax.jit(lambda r: fa.tile_classes(r, bq, bk, T))(ranges[None])
    assert (np.asarray(traced)[0] == want).all()
    # every live tile is walked once by each kernel's table, dead ones never
    idx, n_full, n_live = (np.asarray(a) for a in fa._row_tables(got))
    nq, nk = want.shape
    for i in range(nq):
        row = idx.reshape(nq, nk)[i]
        assert set(row[:n_full[i]]) == set(np.flatnonzero(want[i] == 2))
        assert set(row[n_full[i]:n_live[i]]) == set(np.flatnonzero(want[i] == 1))
    table, P = fa._pair_table(got)
    pairs = table.reshape(P, 4)
    visited = {(j, i) for j, i, c, _ in pairs if c}
    assert visited == {(j, i) for i, j in zip(*np.nonzero(want))}
    assert all(want[i, j] == c for j, i, c, _ in pairs if c)
    firsts = [j for j, _, _, f in pairs if f & 1]
    lasts = [j for j, _, _, f in pairs if f & 2]
    assert firsts == lasts == sorted(set(range(nk)))     # each key tile once


# the masks of the benchmark's cells at a quarter of their 8,192 positions
# (tiles of 512 as there), and documents packed otherwise in each batch row
SUB_CLASS_MASKS = {
    "causal": fa.causal_ranges,
    "window-512": lambda T: fa.window_ranges(T, 512),
    "block-diffusion": lambda T: _block_diffusion_ranges(T // 2, 4),
    "packed-documents-traced": _packed_documents,
}


@pytest.mark.parametrize("mask", sorted(SUB_CLASS_MASKS))
@pytest.mark.parametrize("sub", [256, 128])
def test_sub_tile_classes_against_the_dense_mask(mask, sub, monkeypatch):
    """The words of a mixed tile's sub-tile classes, from a mask known
    where the call is built and from a traced one: no live pair in a dead
    sub-tile, no masked pair in a full one, each of a mixed tile's ``sq x
    sk`` sub-tiles classed, and nothing but zeros for a tile that is not
    mixed; the same words ride both kernels' tables."""
    monkeypatch.setattr(fa, "_SUB", sub)
    T, blk = 2048, 512
    ranges = SUB_CLASS_MASKS[mask](T)
    ranges = ranges if ranges.ndim == 3 else ranges[None]
    traced = mask.endswith("traced")
    plan = jax.jit(lambda r: fa._mask_plan(r, blk, blk, T)[1:3]) if traced \
        else (lambda r: fa._mask_plan(r, blk, blk, T)[1:3])
    classes, found = plan(ranges)
    assert isinstance(found.words, jax.Array if traced else np.ndarray)
    classes, words, spans = (np.asarray(a) for a in
                             (classes, found.words, found.spans))
    n, S = T // blk, blk // sub
    assert found.grid == (S, S)
    assert words.dtype == spans.dtype == np.int32
    live = fa.dense_mask(ranges, T)
    for b in range(ranges.shape[0]):
        # [query tile, key tile, query sub-tile, key sub-tile, rows, keys]
        pairs = live[b].reshape(n, S, sub, n, S, sub).transpose(0, 3, 1, 4, 2, 5)
        want = np.where(pairs.all((4, 5)), 2,
                        np.where(pairs.any((4, 5)), 1, 0))
        got = fa.sub_codes(words[b], S * S).reshape(n, n, S, S)
        mixed = classes[b] == 1
        assert mixed.any() and (got[mixed] == want[mixed]).all()
        assert (want[mixed] == 1).any((-1, -2)).all()   # why a tile is mixed
        assert (words[b][~mixed] == 0).all() and (spans[b][~mixed] == 0).all()
        # a band's span: from its first live sub-tile to its last, nothing
        # live outside it
        for i, j in zip(*np.nonzero(mixed)):
            for r in range(S):
                field = (spans[b, i, j] >> (8 * r)) & 0xff
                count, first = field & 7, field >> 3
                at = np.flatnonzero(want[i, j, r])
                assert count == (at[-1] - at[0] + 1 if len(at) else 0)
                assert not count or first == at[0]
    # the tables: the spans a fourth for fwd / dq by (query tile, key
    # tile), the classes a fifth column of the pairs' for dkv
    tables = fa._row_tables(classes, found)
    assert len(tables) == 4 and (np.asarray(tables[3])
                                 == spans.reshape(-1)).all()
    table, P = fa._pair_table(classes, found)
    for b, rows in enumerate(np.asarray(table).reshape(-1, P, 5)):
        for j, i, c, _, word in rows:
            assert word == (words[b, i, j] if c == 1 else word)


@pytest.mark.parametrize("mask", ["full", "causal-by-whole-tiles", "one-tile"])
def test_a_mask_that_cuts_no_tile_builds_no_sub_tile_table(mask,
                                                           monkeypatch):
    """Every tile full or dead (or a tile no larger than a sub-tile): no
    words, three tables in SMEM as before the sub-tiles, the pairs' table
    four wide, no scratch for the sub-tiles' statistics and no series of
    ``hvd_flash_subtiles_total``."""
    from horovod_tpu import metrics
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(metrics, "ACTIVE", True)
    T = 1024
    if mask == "one-tile":
        monkeypatch.setattr(fa, "_BLOCK", fa._SUB)
        ranges = fa.causal_ranges(T)          # cuts tiles of one sub-tile
    elif mask == "full":
        ranges = fa.full_ranges(T, T)
    else:
        ranges = fa.causal_ranges(T)
        ranges[:, 1] = (np.arange(T) // 512 + 1) * 512
    blk = fa._BLOCK
    ranges_b, classes, sub, *_ = fa._mask_plan(ranges, blk, blk, T)
    assert sub is None and ((classes == 1).any() == (mask == "one-tile"))
    # traced, a mask may cut a tile: the table is built unless a tile is
    # one sub-tile
    traced = jax.eval_shape(
        lambda r: fa._mask_plan(r, blk, blk, T)[2], jnp.asarray(ranges_b))
    assert (traced is None) == (mask == "one-tile")
    before = _subtile_counts()
    x = jax.ShapeDtypeStruct((1, T, 2, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, mask=ranges).sum(),
        (0, 1, 2))(q, k, v))(x, x, x)
    calls = {eqn.params["name"]: eqn.params["grid_mapping"]
             for _, eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert {name: (gm.num_index_operands, gm.num_scratch_operands)
            for name, gm in calls.items()} == {
        "hvd_flash_fwd": (3, 4), "hvd_flash_dq": (3, 4),
        "hvd_flash_dkv": (1, 2)}
    assert _subtile_counts() == before


def _state_counts(family):
    """``{(kernel, state): value}`` of ``hvd_flash_tiles_total`` or
    ``hvd_flash_subtiles_total``."""
    from horovod_tpu import metrics
    fam = metrics.registry().to_dict().get(family, {})
    return {(s["labels"]["kernel"], s["labels"]["state"]): s["value"]
            for s in fam.get("series", [])}


def _subtile_counts():
    return _state_counts("hvd_flash_subtiles_total")


# the issue's count (numpy, ``tile_classes`` at 512 and at the sub-tile's
# width): sub-tiles of the SDAR cell's 24 mixed tiles, full / mixed / dead
SDAR_SUB_TILES = {256: (16, 48, 32), 128: (96, 96, 192)}


@pytest.mark.parametrize("sub", sorted(SDAR_SUB_TILES))
def test_sdar_call_counts_its_tiles_and_sub_tiles(sub, monkeypatch):
    """At the SDAR cell's ranges (``[xt ; x0]`` of 8,192 positions, blocks
    of 4, tiles of 512) ``hvd_flash_tiles_total`` reads what it read
    before the sub-tiles, 56 / 24 / 176 a kernel, and
    ``hvd_flash_subtiles_total`` the sub-tiles of the 24 mixed tiles in
    its three states, for each of the three kernels."""
    from horovod_tpu import metrics
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_SUB", sub)
    monkeypatch.setattr(metrics, "ACTIVE", True)

    tiles = functools.partial(_state_counts, "hvd_flash_tiles_total")
    before_t, before_s = tiles(), _subtile_counts()
    ranges = _block_diffusion_ranges(4096, 4)
    q, k = (jax.ShapeDtypeStruct((1, 8192, h, 128), jnp.bfloat16)
            for h in (8, 1))
    jax.make_jaxpr(lambda q, k, v: jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, mask=ranges).astype(jnp.float32).sum(),
        (0, 1, 2))(q, k, v))(q, k, k)
    states = ("live", "masked", "skipped")
    for kernel in ("fwd", "dq", "dkv"):
        assert tuple(tiles()[kernel, s] - before_t.get((kernel, s), 0)
                     for s in states) == (56, 24, 176)
        assert tuple(_subtile_counts()[kernel, s]
                     - before_s.get((kernel, s), 0)
                     for s in states) == SDAR_SUB_TILES[sub]


def test_masked_call_counts_its_tiles_and_kernels(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fa, "_BLOCK", 128)
    tiles = functools.partial(_state_counts, "hvd_flash_tiles_total")
    before_t, before_k = tiles(), _kernel_counts()
    x = jax.ShapeDtypeStruct((1, 512, 8, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 512, 1, 64), jnp.float32)
    ranges = _block_diffusion_ranges(256, 4)
    jax.make_jaxpr(lambda q, k, v: jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, mask=ranges).sum(),
        (0, 1, 2))(q, k, v))(x, kv, kv)
    grew = {key: v - before_t.get(key, 0) for key, v in tiles().items()}
    # 4 x 4 tiles of 128: xt's own 2, xt on x0 1 full + 2 mixed, x0 on x0
    # 1 full + 2 mixed
    for kernel in ("fwd", "dq", "dkv"):
        assert (grew[kernel, "live"], grew[kernel, "masked"],
                grew[kernel, "skipped"]) == (2, 6, 8)
    assert _grew(before_k) == {("fwd", "masked", "heads"),
                               ("dq", "masked", "heads"),
                               ("dkv", "masked", "heads")}


@pytest.mark.parametrize("B,H,Hkv,given", [
    (1, 8, 1, True), (2, 32, 4, True), (1, 32, 8, False)],
    ids=["one-group", "sdar-cell", "llama3-8b-causal"])
def test_masked_kernels_lower_for_the_chip(B, H, Hkv, given, monkeypatch):
    """Mosaic takes the three masked kernels at 8,192 positions and
    head_dim 128: 8 query heads a kv head under a block-diffusion mask,
    for one group and at the benchmark's SDAR cell (32 query heads over 4,
    batch 2), and Llama-3-8B's heads (32 over 8) through ``causal=True``
    alone, where a ``dkv`` holding ``g x T x D`` was refused: compiled
    here for a v5e that is described, not attached.  Their blocks are cut
    from the caller's ``[B, T, H*D]``: XLA puts no rank-4 ``transpose``
    or ``copy`` beside them."""
    import re
    one_chip = _described_chip(monkeypatch)
    q, k = (jax.ShapeDtypeStruct((B, 8192, h, 128), jnp.bfloat16,
                                 sharding=one_chip) for h in (H, Hkv))
    ranges = _block_diffusion_ranges(4096, 4) if given else None
    assert fa.supported(q, k, k, True, ranges)
    text = jax.jit(lambda q, k, v: jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, mask=ranges).astype(jnp.float32).sum(),
        (0, 1, 2))(q, k, v)).lower(q, k, k).compile().as_text()
    for name in ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"):
        assert name in text
    shapes = "|".join(f"{B},{a},{b},128" for h in (H, Hkv)
                      for a, b in ((8192, h), (h, 8192)))
    assert not re.findall(
        rf"= \w+\[(?:{shapes})\]\S* (?:transpose|copy)\(", text)


# ------------------------------------- the grouped products' kernels
# (ops/grouped_matmul.py; their other tests are tests/test_grouped_matmul.py.
# This one is here because one file describes the chip: a second file can
# go to another worker, whose process cannot load the TPU's library too.)

@pytest.mark.parametrize("call", ["forward", "backward", "combine"])
def test_grouped_matmul_kernels_lower_for_the_chip(call, monkeypatch):
    """Mosaic takes the expert layer's grouped products at the benchmark's
    SDAR cell: a chunk of 24,576 rows of width 2,048 over 16 held experts
    of width 768 (gate and up from one read of a tile, 1,536 columns),
    bf16: ``gmm`` with the weights as stored and transposed, ``tgmm`` with
    a float32 ``[2048, 768]`` accumulator a group, in and out; and the
    combine of the chunk's float32 rows into 16,384 tokens (24,576 token
    ids in scalar memory, a ring of copies from HBM), with no scatter
    left beside it.  Compiled here for a v5e that is described, not
    attached."""
    _grouped_kernels_lower(call, monkeypatch, 24576, 2048, 768, 16, 16384)


@pytest.mark.parametrize("call", ["forward", "backward", "combine"])
def test_grouped_matmul_kernels_lower_at_a_hidden_size_of_4096(call, monkeypatch):
    """The same at the solar-open2-250b cell: a chunk of 2,560 rows of width
    4,096 over 8 held experts of width 1,280; ``tgmm`` walks its float32
    ``[4096, 1280]`` accumulator in two blocks of rows, since in and out and
    twice it is 84 MB and a grid step may hold 64."""
    from horovod_tpu.ops import grouped_matmul as gm
    assert gm._tgmm_split(4096, 1280, 2) == 2
    _grouped_kernels_lower(call, monkeypatch, 2560, 4096, 1280, 8, 8192)


def _grouped_kernels_lower(call, monkeypatch, R, D, F, E, tokens):
    from horovod_tpu.models import moe
    from horovod_tpu.ops import grouped_matmul as gm
    one_chip = _described_chip(monkeypatch)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    xs, wt, sizes = (sds((R, D), jnp.bfloat16), sds((R,), jnp.float32),
                     sds((E,), jnp.int32))
    wg, wu, wd = (sds(s, jnp.bfloat16) for s in ((E, D, F), (E, D, F),
                                                 (E, F, D)))
    assert gm.supported(xs, wg, wu, wd)
    if call == "forward":
        text = jax.jit(moe._expert_ffn).lower(
            xs, wg, wu, wd, wt, sizes).compile().as_text()
        names = ("hvd_moe_gmm_gate_up", "hvd_moe_gmm_down")
    elif call == "combine":
        text = jax.jit(lambda *a: gm.combine(*a, "out"),
                       donate_argnums=3).lower(
            sds((R, D), jnp.float32), sds((R,), jnp.int32), sizes,
            sds((tokens, D), jnp.float32)).compile().as_text()
        names = ("hvd_moe_combine_out",)
        assert "scatter" not in text
    else:
        held = [sds(w.shape, jnp.float32) for w in (wg, wu, wd)]
        text = jax.jit(moe._expert_ffn_grads, donate_argnums=(7, 8, 9)).lower(
            xs, wg, wu, wd, wt, sizes, sds((R, D), jnp.float32),
            *held).compile().as_text()
        names = ("hvd_moe_gmm_gate_up", "hvd_moe_gmm_dh", "hvd_moe_gmm_dx",
                 "hvd_moe_tgmm_gate", "hvd_moe_tgmm_up", "hvd_moe_tgmm_down")
    for name in names:
        assert name in text
    assert "ragged-dot" not in text


# ------------------------------------------ the residuals under remat
# The forward rules name ``out`` and ``lse`` (fa.OUT_NAME, fa.LSE_NAME) and
# models/llama.py::remat_policy saves what is named: a remat'd layer
# stack (the decoder trunk's, BERT's encoder) runs the forward kernel once
# a layer, not twice.

def _kernels_by_place(fn, *args):
    """``[(enclosing primitives, kernel name)]`` of every pallas_call."""
    return [(path, eqn.params["name"])
            for path, eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


# attention path -> (heads, kv heads, head_dim, tokens, the mask of a [T]
# sequence); ``rows``: the masked kernels on the caller's layout
REMAT_PATHS = {
    "masked-numpy-mask": (2, 1, 64, 256, lambda T: fa.window_ranges(T, 100)),
    "masked-traced-mask": (2, 1, 64, 256, lambda T: jnp.asarray(
        fa.window_ranges(T, 100))),
    "masked-rows": (2, 1, 128, 256, lambda T: fa.window_ranges(T, 100)),
    "packed": (2, 2, 64, 128, lambda T: None),
}


def _remat_stack(policy, path):
    """A two-layer remat'd stack at toy widths, bf16, through the
    interpreted kernels: ``(grads, (h, layers, mask), cfg, pos)`` with
    ``grads`` a FRESH function of the three (jax caches a traced function
    by identity, and two policies must not share a trace)."""
    from horovod_tpu.models import llama
    H, Hkv, Dh, T, make_mask = REMAT_PATHS[path]
    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=128, n_layers=2, n_heads=H, n_kv_heads=Hkv,
        head_dim=Dh, d_ff=128, max_seq_len=T, dtype=jnp.bfloat16,
        remat=True, remat_policy=policy)
    par = llama.ParallelSpec()
    layers = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]
    B = 2
    h = jnp.asarray(np.random.RandomState(1).randn(B, T, cfg.d_model),
                    cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = make_mask(T)

    def loss(h, layers, mask):
        out, _ = llama._layer_stack(h, layers, cfg, par, pos, mask)
        return (out.astype(jnp.float32) ** 2).mean()

    grads = jax.value_and_grad(loss, argnums=(0, 1))
    if isinstance(mask, jax.Array):           # traced: a jit argument
        return jax.jit(grads), (h, layers, mask), cfg, pos
    return (jax.jit(lambda h, layers, _: grads(h, layers, mask)),
            (h, layers, 0), cfg, pos)


@pytest.mark.parametrize("path", sorted(REMAT_PATHS))
@pytest.mark.parametrize("policy", ["full", "dots"])
def test_remat_policies_save_the_named_residuals(policy, path, monkeypatch):
    """Under either policy of ``_layer_stack`` the forward kernel stands in
    the forward scan alone and the backward scan's remat body holds only
    the backward kernels; the layer's saved residuals are the two named
    values, ``out`` in the compute dtype and ``lse`` in float32, as the
    kernel wrote them (``out`` as ``[B, T, H*D]`` on the packed path and
    on the masked one at ``head_dim`` 128, what the ``wo`` product reads;
    ``[B, H, T, D]`` at 64); loss and every gradient equal, to the bit, those
    of the same stack under the policy without the names (the program
    before the kernels' residuals were kept), which runs the forward
    kernel twice."""
    from jax._src.ad_checkpoint import saved_residuals
    from horovod_tpu.models import llama
    monkeypatch.setattr(fa, "_INTERPRET", True)
    grads, args, cfg, pos = _remat_stack(policy, path)
    backward = ({"hvd_flash_bwd"} if path == "packed"
                else {"hvd_flash_dq", "hvd_flash_dkv"})
    placed = _kernels_by_place(grads, *args)
    forward = [p for p, name in placed if name == "hvd_flash_fwd"]
    assert len(forward) == 1 and "scan" in forward[0]
    assert "remat2" not in forward[0]
    assert {name for p, name in placed if "remat2" in p} == backward
    assert len(placed) == 1 + len(backward)

    # one layer under the stack's own policy: what it keeps beside its
    # arguments are the two named values (jax puts a reduce_precision of
    # the value's own precision, a no-op, on a residual that the forward
    # also uses: ``out`` shows under that, ``lse`` under its name)
    h, layers, _ = args
    H, T = cfg.n_heads, h.shape[1]
    one = jax.tree_util.tree_map(lambda w: w[0].astype(cfg.dtype), layers)
    static = None if path == "packed" else fa.window_ranges(T, 100)
    layer = jax.checkpoint(
        lambda h, lp: llama.block(h, lp, cfg, llama.ParallelSpec(), pos,
                                  static)[0],
        policy=llama.remat_policy(policy))
    kept = [(aval, why) for aval, why in saved_residuals(layer, h, one)
            if "flash_attention.py" in why]
    D = cfg.head_dim
    out_shape = ((2, T, H * D) if path in ("packed", "masked-rows")
                 else (2, H, T, D))
    assert sorted((a.shape, str(a.dtype)) for a, _ in kept)[-1] == (
        out_shape, "bfloat16")
    assert [(a.shape, str(a.dtype)) for a, why in kept
            if f"named '{fa.LSE_NAME}'" in why] == [
        ((2, H, 1, T), "float32")]
    if policy == "full":
        assert len(kept) == 2
    traced = jax.make_jaxpr(jax.grad(lambda h: layer(h, one).astype(
        jnp.float32).sum()))(h)
    assert {eqn.params["name"] for _, eqn in _eqns(traced.jaxpr)
            if eqn.primitive.name == "name"} == {fa.OUT_NAME, fa.LSE_NAME}

    # the same stack, the names saved by no policy: the forward kernel a
    # second time in the remat body, and the same numbers to the bit
    got = grads(*args)
    cp = jax.checkpoint_policies
    monkeypatch.setattr(cp, "save_only_these_names",
                        lambda *names: cp.nothing_saveable)
    before, args, _, _ = _remat_stack(policy, path)
    placed = _kernels_by_place(before, *args)
    assert sorted(name for p, name in placed if "remat2" in p) == sorted(
        backward | {"hvd_flash_fwd"})
    want = before(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert np.isfinite(float(got[0])) and float(got[0]) > 0


def test_a_name_that_no_policy_saves_changes_nothing(monkeypatch):
    """A caller that remats around the op and saves dots only (BERT's
    stack, before it took ``remat_policy("dots")``): with the names in
    the forward rule and no policy that keeps them, the remat body still
    holds the forward kernel and every number is what it was without the
    names."""
    monkeypatch.setattr(fa, "_INTERPRET", True)
    q, k, v = make_qkv(4, 128, 12, 12, 64, jnp.bfloat16)    # BERT's block

    def grads():
        layer = jax.checkpoint(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=False),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.jit(jax.grad(lambda q, k, v: (layer(q, k, v).astype(
            jnp.float32) ** 2).sum(), (0, 1, 2)))

    named = grads()
    placed = _kernels_by_place(named, q, k, v)
    assert sorted(name for p, name in placed) == [
        "hvd_flash_bwd", "hvd_flash_fwd", "hvd_flash_fwd"]
    got = named(q, k, v)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    plain = grads()
    assert _kernels_by_place(plain, q, k, v) == placed
    for a, b in zip(got, plain(q, k, v)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_bert_stack_saves_the_packed_kernels_residuals(monkeypatch):
    """``models/bert.py``'s remat'd encoder keeps dots and the packed
    kernel's two named values: one forward kernel, in the forward scan,
    and the same loss and gradients, to the bit, as under dots alone
    (which reruns the kernel in the remat body)."""
    from horovod_tpu.models import bert, llama
    monkeypatch.setattr(fa, "_INTERPRET", True)
    cfg = bert.BertConfig(vocab_size=64, d_model=128, n_layers=2, n_heads=2,
                          d_ff=128, max_seq_len=128)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 128)))
    labels = jnp.asarray([0, 1])

    def grads():
        return jax.jit(jax.value_and_grad(lambda p: bert.loss_fn(
            p, tokens, labels, cfg, llama.ParallelSpec())))

    kept = grads()
    placed = _kernels_by_place(kept, params)
    assert sorted((name, "remat2" in p) for p, name in placed) == [
        ("hvd_flash_bwd", True), ("hvd_flash_fwd", False)]
    got = kept(params)
    cp = jax.checkpoint_policies
    monkeypatch.setattr(cp, "save_only_these_names",
                        lambda *names: cp.nothing_saveable)
    dots_only = grads()
    assert sorted((name, "remat2" in p) for p, name in _kernels_by_place(
        dots_only, params)) == [("hvd_flash_bwd", True),
                                ("hvd_flash_fwd", False),
                                ("hvd_flash_fwd", True)]
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(dots_only(params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layer_stack_counts_its_remat_policy(monkeypatch):
    """``hvd_remat_policy_total{policy, saves}``: one count per traced
    remat'd stack, none where ``remat`` is off."""
    from horovod_tpu import metrics
    from horovod_tpu.models import llama
    monkeypatch.setattr(metrics, "ACTIVE", True)

    def counts():
        family = metrics.registry().to_dict().get(
            "hvd_remat_policy_total", {})
        return {(s["labels"]["policy"], s["labels"]["saves"]): s["value"]
                for s in family.get("series", [])}

    def trace(**kw):
        cfg = llama.LlamaConfig(vocab_size=64, d_model=64, n_layers=2,
                                n_heads=2, n_kv_heads=2, d_ff=64,
                                max_seq_len=16, **kw)
        layers = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]
        h = jax.ShapeDtypeStruct((1, 16, 64), cfg.dtype)
        pos = jnp.arange(16)[None]
        jax.make_jaxpr(lambda h, ls: llama._layer_stack(
            h, ls, cfg, llama.ParallelSpec(), pos))(h, layers)

    before = counts()
    trace(remat_policy="full")
    trace(remat_policy="dots")
    trace(remat_policy="dots")
    trace(remat=False)
    after = counts()
    assert {key: after[key] - before.get(key, 0) for key in after} == {
        ("full", "flash"): 1, ("dots", "flash"): 2}
    with pytest.raises(ValueError, match="remat_policy"):
        trace(remat_policy="some")


# ------------------------- the hybrid trunk's kernels at published widths
# (models/hybrid.py: differential attention through the masked kernels with
# values twice as wide as queries and keys, and ops/selective_scan.py's two
# kernels; their other tests are tests/test_hybrid.py and
# tests/test_selective_scan.py.  Here for the same reason as the grouped
# products': one file describes the chip.)

@pytest.mark.parametrize("kind", ["window", "causal"])
def test_differential_attentions_kernels_lower_for_the_chip(kind, monkeypatch):
    """Mosaic takes the three masked kernels at the benchmark's
    phi4-mini-flash cell: 8,192 positions, 20 first heads of the query
    pairs over 10 of the key pairs at head_dim 64, the pairs' values 128
    wide, under the window of 512 and under the causal ranges."""
    one_chip = _described_chip(monkeypatch)
    T = 8192
    sds = lambda h, d: jax.ShapeDtypeStruct((1, T, h, d), jnp.bfloat16,
                                            sharding=one_chip)
    q, k, v = sds(20, 64), sds(10, 64), sds(10, 128)
    ranges = fa.window_ranges(T, 512) if kind == "window" else \
        fa.causal_ranges(T)
    assert fa.supported(q, k, v, True, ranges)
    text = jax.jit(lambda q, k, v: jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, mask=ranges).astype(jnp.float32).sum(),
        (0, 1, 2))(q, k, v)).lower(q, k, v).compile().as_text()
    for name in ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"):
        assert name in text


def test_selective_scan_kernels_lower_for_the_chip(monkeypatch):
    """Mosaic takes the selective scan forward and backward at the
    benchmark's phi4-mini-flash cell: 8,192 positions of 5,120 channels
    of 16 states, bf16 ``xs``, ``B`` and ``C`` beside a float32 step."""
    from horovod_tpu.ops import selective_scan as ss
    one_chip = _described_chip(monkeypatch)
    T, Ch, N = 8192, 5120, 16
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    operands = (sds((1, T, Ch), jnp.bfloat16), sds((1, T, Ch), jnp.float32),
                sds((Ch, N), jnp.float32), sds((1, T, N), jnp.bfloat16),
                sds((1, T, N), jnp.bfloat16), sds((Ch,), jnp.float32))
    assert ss.supported(*operands)
    text = jax.jit(jax.grad(
        lambda *a: ss.selective_scan(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(6)))).lower(*operands).compile().as_text()
    assert "hvd_ssm_scan_fwd" in text and "hvd_ssm_scan_bwd" in text


def test_ssd_scan_kernels_lower_for_the_chip(monkeypatch):
    """Mosaic takes the chunked state-space scan forward and backward at
    the benchmark's granite-4.0-h-micro cell: 8,192 positions of 64 heads
    of 64 channels over one group of 128 states in chunks of 256, bf16
    ``x``, ``B`` and ``C`` beside a float32 step; no ``[T, H, P, N]``
    array and no ``[Q, Q]`` tile a head in the compiled program."""
    from horovod_tpu.ops import ssd_scan as sd
    one_chip = _described_chip(monkeypatch)
    T, H, P, N = 8192, 64, 64, 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    operands = (sds((1, T, H, P), jnp.bfloat16), sds((1, T, H), jnp.float32),
                sds((H,), jnp.float32), sds((1, T, 1, N), jnp.bfloat16),
                sds((1, T, 1, N), jnp.bfloat16), sds((H,), jnp.float32))
    assert sd.supported(*operands, 256)
    text = jax.jit(jax.grad(
        lambda *a: sd.ssd_scan(*a, 256).astype(jnp.float32).sum(),
        argnums=tuple(range(6)))).lower(*operands).compile().as_text()
    assert "hvd_ssd_chunk_fwd" in text and "hvd_ssd_chunk_bwd" in text
    assert f"{T},{H},{P},{N}]" not in text and "64,256,256]" not in text


def test_kda_scan_kernels_lower_for_the_chip(monkeypatch):
    """Mosaic takes the chunked gated delta rule forward and backward at
    the benchmark's solar-open2-250b cell: 8,192 positions of 8 heads whose
    keys and values are 128 wide in chunks of 64, bf16 ``q``, ``k``, ``v``
    beside float32 decays and ``beta``: the two chunk kernels and, since PR
    43, the two tile kernels (the triangular inverse's lane gather and
    float32 products among what only this compile sees).  The per-channel
    decay of the diagonal blocks (``[.., 16, 16, 128]``: 537 MB in float32,
    one array) is no array any more: forward and backward together take
    under 0.4 GB of temporaries (0.27; 0.65 when XLA made the tiles)."""
    from horovod_tpu.ops import kda_scan as kd
    one_chip = _described_chip(monkeypatch)
    T, H, K = 8192, 8, 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    wide = sds((1, T, H, K), jnp.bfloat16)
    operands = (wide, wide, wide, sds((1, T, H, K), jnp.float32),
                sds((1, T, H), jnp.float32))
    assert kd.supported(*operands, 64)
    compiled = jax.jit(jax.grad(
        lambda *a: kd.kda_scan(*a, 64).astype(jnp.float32).sum(),
        argnums=tuple(range(5)))).lower(*operands).compile()
    text = compiled.as_text()
    assert "hvd_kda_chunk_fwd" in text and "hvd_kda_chunk_bwd" in text
    assert "hvd_kda_tiles_fwd" in text and "hvd_kda_tiles_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


@pytest.mark.parametrize("turned", [False, True], ids=["rows", "turned"])
def test_mamba2_mixer_kernels_lower_for_the_chip(turned, monkeypatch):
    """Mosaic takes the Mamba-2 mixer's four elementwise kernels at the
    benchmark's granite-4.0-h-micro cell: 8,192 positions, 4,352 convolved
    channels cut 4,096 / 128 / 128 under four taps, 4,096 gated ones, bf16
    operands beside float32 parameters; ``turned``, as the cell runs them,
    ``x`` written and ``y`` read ``[1, 4096, 8192]`` with the chunked scan
    on that layout between them and no transpose in the compiled chain."""
    from horovod_tpu.ops import mamba2_mixer as mm
    from horovod_tpu.ops import ssd_scan as sd
    one_chip = _described_chip(monkeypatch)
    T, sizes, H, N = 8192, (4096, 128, 128), 64, 128
    C, Di = sum(sizes), sizes[0]
    bf, f32 = jnp.bfloat16, jnp.float32
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    assert mm.supported(sds((1, T, C), bf), sizes, turned)

    def chain(xBC, conv_w, conv_b, z, gate_w, delta, A, D):
        x, B, Cm = mm.conv_silu_split(xBC, conv_w, conv_b, sizes, turned)
        scan, heads = ((sd.ssd_scan_turned, (1, H, Di // H, T)) if turned
                       else (sd.ssd_scan, (1, T, H, Di // H)))
        y = scan(x.reshape(heads), delta, A, B.reshape(1, T, 1, N),
                 Cm.reshape(1, T, 1, N), D, 256)
        return mm.gated_rmsnorm(y.reshape(x.shape), z, gate_w, 1e-5,
                                turned).astype(f32).sum()

    text = jax.jit(jax.value_and_grad(chain, argnums=tuple(range(8)))).lower(
        sds((1, T, C), bf), sds((4, C), f32), sds((C,), f32),
        sds((1, T, Di), bf), sds((Di,), f32), sds((1, T, H), f32),
        sds((H,), f32), sds((H,), f32)).compile().as_text()
    for name in ("hvd_conv_silu_fwd", "hvd_conv_silu_bwd",
                 "hvd_gated_norm_fwd", "hvd_gated_norm_bwd",
                 "hvd_ssd_chunk_fwd", "hvd_ssd_chunk_bwd"):
        assert name in text
    if turned:
        # x, y and their cotangents never change layout in HBM
        assert f"bf16[1,{T},{H},{Di // H}]" not in text
        assert not re.search(
            rf"bf16\[1,{T},{Di}\]\S* (copy|transpose)\(", text)
