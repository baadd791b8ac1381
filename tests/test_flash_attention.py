"""Flash-attention kernel + blockwise local attention correctness: the
packed kernels, the entry points and the pack rule (the masked kernels'
tests are tests/test_flash_masked.py).

The Pallas kernels are validated in interpret mode on the CPU mesh (the
same kernel code compiles via Mosaic on TPU — see the on-hardware bench);
the XLA blockwise fallback is validated directly.  Reference is dense
softmax attention in fp32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import (dense_reference, described_chip as _described_chip,
                      flash_grad_all as _grad_all, flash_grew as _grew,
                      flash_kernel_counts as _kernel_counts, make_qkv,
                      pallas_calls as _pallas_calls)
from horovod_tpu.ops import _pallas
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel.ring_attention import local_attention

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
def test_pallas_kernel_interpret(shape, causal, pallas_interpret):
    B, T, H, Hkv, D = shape
    q, k, v = make_qkv(B, T, H, Hkv, D)
    assert fa.supported(q, k, v, causal)
    out = fa.flash_attention(q, k, v, causal=causal)
    ref = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("shape,causal,dtype,budget,pack", PACKED_CASES)
def test_packed_kernel_interpret(shape, causal, dtype, budget, pack,
                                 monkeypatch, pallas_interpret):
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
                                       monkeypatch, pallas_interpret):
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
def test_flash_attention_lse_interpret(causal, pallas_interpret):
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
def test_flash_attention_lse_grads_interpret(shape, pallas_interpret):
    """Gradients flow through BOTH outputs (the lse cotangent folds into
    the backward kernels' delta term)."""
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


def test_block_size_is_a_module_constant(monkeypatch, pallas_interpret):
    """``_BLOCK`` sets the kernel grid; a value the sequence length cannot
    honor makes supported() fall back to XLA attention."""
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
    _pallas._warn_refused.cache_clear()
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


def test_causal_over_several_blocks_builds_the_masked_kernels(
        pallas_interpret):
    """``causal=True`` without ``mask=``: the masked family's three calls,
    grids and blocks as ``test_masked_forward_specs`` and
    ``test_masked_backward_specs`` pin them (both of a group's two heads a
    forward step and a ``dq`` step; ``dkv`` one step a live pair of
    tiles, holding one query tile of the group and its ranges a row a
    bound), on the tile classes of :func:`causal_ranges`; at ``head_dim``
    128 the blocks are cut from the caller's ``[B, T, H*D]``, a head a
    lane block."""
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


def test_packed_path_specs_and_counter(monkeypatch, pallas_interpret):
    """BERT's block: one forward and ONE backward kernel, all heads of
    two rows a grid step, in the caller's layout (no transposes); the
    counter says which path each traced call took."""
    from horovod_tpu import metrics
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
