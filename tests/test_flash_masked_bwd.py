"""The masked flash-attention kernels' backward (``hvd_flash_dq`` /
``hvd_flash_dkv``) against dense masked attention, the two layouts equal
to the bit, the backward's heads-a-step rule and block specs.  In
interpret mode on the CPU; the forward, the tables, tiles and sub-tiles
and what the calls count are tests/test_flash_masked.py, whose masks and
cases these share."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import (block_diffusion_ranges as _block_diffusion_ranges,
                      pallas_calls as _pallas_calls)
from horovod_tpu.ops import flash_attention as fa
from test_flash_masked import (SUB_MASKS, WHOLE, _case_id, _dense_masked,
                               _dense_masked_lse, _packed_documents, _tiles)


# How many query heads a ``dq`` grid step takes, at every branch of
# ``_dq_heads``, on both layouts: (H, Hkv, D, Dv), the step's VMEM budget,
# the heads.  ``dkv`` takes the whole group a step whatever that says.
BACKWARD_CASES = {
    # (groups of four at head_dim 64, as the forward's cases: groups of
    # eight run at 128, below)
    "g4-whole-group": ((4, 1, 64, 64), None, 4),
    "g4-split-group": ((4, 1, 64, 64), 2 << 20, 2),
    "g4-one-head": ((4, 1, 64, 64), 1 << 20, 1),      # not even two fit
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
def test_masked_backward_matches_dense_masked_attention(mask, per_batch, heads,
                                                        tiles, monkeypatch,
                                                        pallas_interpret):
    """``dq``, ``dk`` and ``dv`` of a loss on ``out`` AND on ``lse`` (whose
    cotangent folds into ``delta`` before the kernels) against dense masked
    attention: a ``dq`` step taking a whole GQA group, a part of one, or
    one head; values twice as wide as keys; transposed around the kernels
    at ``head_dim`` 64, on the caller's layout at 128; the mask known where
    the call is built or traced a batch row; a query tile whose live tiles
    are all mixed and one with a single live tile; a mixed tile taken
    whole, or walked by its live sub-tiles (``tiles``)."""
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
    (4, 2, 128, False), (4, 1, 128, True), (2, 2, 256, False)],
    ids=["g2", "g4-mask-per-row", "g1-values-256"])
def test_rows_and_heads_routes_are_equal_to_the_bit(H, Hkv, Dv, per_batch,
                                                    monkeypatch,
                                                    pallas_interpret):
    """One set of kernel bodies, two ways of building specs and slicing
    refs: for equal inputs ``out``, ``lse``, ``dq``, ``dk``, ``dv`` (the
    ``lse`` cotangent folded in) are the same bits on either route."""
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


def test_rows_route_lowers_with_no_transpose_around_the_kernels(
        pallas_interpret):
    """The jitted forward and backward at ``head_dim`` 128: the operands
    reach the kernels by reshapes, which are free, and no rank-4
    ``transpose`` is left in the lowered module; at 64 the transposed
    route has them (what the pattern finds)."""
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


def _assert_two_backward_calls(B, T, H, Hkv, D, Dv, ranges, hb):
    """An unpaired call's backward traces to ``hvd_flash_dq`` and
    ``hvd_flash_dkv`` with the specs :func:`test_masked_backward_specs`
    says; ``hb``: the heads a ``dq`` step takes."""
    bq = 512
    nq, g = T // bq, H // Hkv
    assert fa._dq_heads(g, bq, bq, D, nq, T, 2, Dv) == hb
    q, k, v = (jax.ShapeDtypeStruct((B, T, h, d), jnp.bfloat16)
               for h, d in ((H, D), (Hkv, D), (Hkv, Dv)))
    classes = fa.tile_classes(ranges[None], bq, bq, T)
    P = fa._pair_table(classes)[1]
    assert P == int((classes >= 1).sum())
    found = _pallas_calls(
        lambda q, k, v: jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, mask=ranges).astype(jnp.float32).sum(),
            (0, 1, 2))(q, k, v), q, k, v, scratch=True)
    assert [name for name, *_ in found] == [
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]
    calls = {name: rest for name, *rest in found}
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


@pytest.mark.parametrize("D,Dv", [(128, 128), (64, 128)],
                         ids=["rows", "heads-values-128"])
def test_masked_backward_specs(D, Dv, pallas_interpret):
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
    _assert_two_backward_calls(2, 2048, 16, 4, D, Dv,
                               _block_diffusion_ranges(1024, 4), hb=4)


# ----------------- a second query/key pair (latent attention's rotary
# part: tests/test_flash_masked.py has the forward and the chip's compile)

@pytest.mark.parametrize("H,Hkv,H2,tiles", [
    (2, 2, 1, (128, None)), (4, 2, 1, (256, 128)), (4, 4, 2, (256, 128))],
    ids=["mla-one-shared-key", "gqa-under-one-key-by-sub-tiles",
         "two-key-heads-by-sub-tiles"])
def test_split_and_joined_forms_agree_backward(H, Hkv, H2, tiles, monkeypatch,
                                               pallas_interpret):
    """All five gradients of the split form (``pair=``: the one backward,
    ``hvd_flash_dqkv``, writes ``dq`` and ``dq2`` of a kv head's group
    beside ``dk``, ``dv`` and the kv head's part of ``dk2``, added over the
    heads that share the key) against autodiff through dense attention
    over the joined query and key; a mixed tile whole and by its
    sub-tiles; and ``dk2`` of the one shared key is the sum over heads of
    what a key a head would get."""
    from test_flash_masked import (_grew, _joined_dense, _kernel_counts,
                                   _pair_operands)
    blk, T = _tiles(monkeypatch, tiles)
    q, k, v, q2, k2 = _pair_operands(1, T, H, Hkv, H2, seed=3)
    w = jax.random.normal(jax.random.key(9), v.shape[:2] + (H, v.shape[-1]))
    ranges = fa.causal_ranges(T)
    live = jnp.asarray(fa.dense_mask(ranges, T))
    before = _kernel_counts()
    split = lambda q, k, v, q2, k2: (fa.flash_attention(
        q, k, v, mask=ranges, pair=(q2, k2)) * w).sum()
    joined = lambda q, k, v, q2, k2: (
        _joined_dense(q, k, v, q2, k2, live) * w).sum()
    got = jax.grad(split, (0, 1, 2, 3, 4))(q, k, v, q2, k2)
    want = jax.grad(joined, (0, 1, 2, 3, 4))(q, k, v, q2, k2)
    for a, b, name in zip(got, want, ("q", "k", "v", "q2", "k2")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")
    assert _grew(before) == {(kernel, "paired", "rows")
                             for kernel in ("fwd", "dqkv")}
    # a key a kv head in place of the shared one: its gradient's sum over
    # the heads that shared it is the shared key's
    if tiles != WHOLE:      # once is enough: the sum is the caller's
        return
    g2 = Hkv // H2
    each = jax.grad(split, 4)(q, k, v, q2, jnp.repeat(k2, g2, axis=2))
    np.testing.assert_allclose(
        each.reshape(1, T, H2, g2, -1).sum(3), got[4], atol=2e-4, rtol=2e-4)


# ----------------- the one backward of a call with a second pair
# (``hvd_flash_dqkv``: ``hvd_flash_dkv``'s grid and orientation, ``dq`` and
# ``dq2`` of a kv head's group held in VMEM across its key tiles)

def _rows_masks(T):
    """``[2, T, 4]``, a mask a batch row: causal inside documents cut off
    every tile's edge in one, a window of 200 keys in the other, so the
    rows' tables of live pairs differ in length and one is padded."""
    return np.stack([_packed_documents(T)[0], fa.window_ranges(T, 200)])


ONE_BACKWARD_CASES = {
    # (H, Hkv, H2), (block, sub-tile), a mask a batch row
    "mla-whole-tiles-one-mask": ((2, 2, 1), WHOLE, False),
    "mla-sub-tiles-a-mask-a-row": ((2, 2, 1), (256, 128), True),
    "gqa-under-one-key-whole-tiles-a-mask-a-row": ((4, 2, 1), WHOLE, True),
    "gqa-under-one-key-sub-tiles-one-mask": ((4, 2, 1), (256, 128), False),
}


@pytest.mark.parametrize("case", sorted(ONE_BACKWARD_CASES))
def test_one_backward_equals_the_two_kernels(case, monkeypatch,
                                             pallas_interpret):
    """``hvd_flash_dqkv`` against ``hvd_flash_dq`` + ``hvd_flash_dkv`` built
    by the same builder for the same residuals (the step's budget set to
    nothing: the rule then keeps two kernels), on three key tiles or more,
    so that ``dq`` is carried in VMEM across key tiles: ``dk``, ``dv`` and
    ``dk2`` are the same bits (the same products in the same order), ``dq``
    and ``dq2`` equal up to float32's order of summation (a key tile at a
    time over the grid's steps there, over one step's loop here); a mixed
    tile whole and by its sub-tiles, one mask known where the call is built
    and one a batch row, traced, whose shorter table of pairs is padded."""
    from test_flash_masked import _grew, _kernel_counts, _pair_operands
    (H, Hkv, H2), tiles, per_row = ONE_BACKWARD_CASES[case]
    blk, _ = _tiles(monkeypatch, tiles)
    T, B = 3 * blk if blk > 128 else 512, 2 if per_row else 1
    assert T // blk >= 3
    q, k, v, q2, k2 = _pair_operands(B, T, H, Hkv, H2, seed=5)
    flat = lambda x, pad=0: jnp.pad(
        x, ((0, 0),) * 3 + ((0, pad),)).reshape(*x.shape[:2], -1)
    operands = flat(q), flat(k), flat(v)
    pair = flat(q2, 64), flat(k2, 64)
    mask = jnp.asarray(_rows_masks(T)) if per_row else fa.causal_ranges(T)
    scale = 192 ** -0.5
    out, lse = fa._masked_fwd(*operands, mask, scale, (128, 128), pair)
    do = jax.random.normal(jax.random.key(6), out.shape)
    dlse = jax.random.normal(jax.random.key(7), lse.shape)
    backward = lambda: fa._masked_bwd(*operands, out, lse, do, mask, scale,
                                      dlse, (128, 128), pair)
    before = _kernel_counts()
    one = backward()
    assert _grew(before) == {("dqkv", "paired", "rows")}
    monkeypatch.setattr(fa, "_DQKV_STEP_VMEM", 0)
    before = _kernel_counts()
    two = backward()
    assert _grew(before) == {("dq", "paired", "rows"),
                             ("dkv", "paired", "rows")}
    for a, b, name in zip(one, two, ("dq", "dk", "dv", "dq2", "dk2")):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a)).all(), name
        if name in ("dq", "dq2"):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def _paired_backward_kernels(T, H, Hkv):
    """The kernels' names of the backward of a causal call with a second
    pair, 128 + 64 wide on values of 128, one shared key (shapes only)."""
    sds = lambda h, d: jax.ShapeDtypeStruct((1, T, h, d), jnp.bfloat16)
    ranges = fa.causal_ranges(T)
    return [name for name, *_ in _pallas_calls(
        lambda *a: jax.grad(lambda *a: fa.flash_attention(
            *a[:3], mask=ranges, pair=a[3:]).astype(jnp.float32).sum(),
            (0, 1, 2, 3, 4))(*a),
        sds(H, 128), sds(Hkv, 128), sds(Hkv, 128), sds(H, 64), sds(1, 64))]


def test_which_calls_take_the_one_backward():
    """The rule, from the step's bytes: at the kanana cell's call (one row
    of 16,384, 32 heads each with its keys and values, 128 + the pair's 64
    padded to 128, bf16) the kv head's ``dq`` and ``dq2`` are 16.8 MB in
    float32, the step 41 MB, and the one kernel is built; twice the rows,
    or a group of eight under one rotary key, keep two kernels."""
    cell = (512, 512, 128, 32, 16384, 2, 128, 128)
    blocks, scratch, tiles = fa._dqkv_step_bytes(1, *cell)
    assert 16384 * 256 * 4 < scratch < 16384 * 256 * 4 + (1 << 20)
    assert (fa._MASKED_STEP_VMEM < 2 * blocks + scratch + tiles
            == fa._one_backward(1, *cell) == 40_960_000
            < fa._DQKV_STEP_VMEM)
    assert fa._one_backward(1, 512, 512, 128, 64, 32768, 2, 128, 128) is None
    assert fa._one_backward(8, *cell) is None
    assert _paired_backward_kernels(16384, 32, 32) == [
        "hvd_flash_fwd", "hvd_flash_dqkv"]
    assert _paired_backward_kernels(32768, 32, 32) == [
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]
    assert _paired_backward_kernels(16384, 32, 4) == [
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"]


@pytest.mark.parametrize("B,T,H,Hkv,D,Dv,mask,hb", [
    (2, 8192, 32, 4, 128, 128, lambda: _block_diffusion_ranges(4096, 4), 4),
    (1, 8192, 20, 10, 64, 128, lambda: fa.window_ranges(8192, 512), 2),
    (1, 16384, 32, 4, 128, 128, lambda: fa.window_ranges(16384, 1024), 2),
], ids=["sdar", "phi-window", "mellum-window"])
def test_calls_without_a_pair_keep_two_kernels(B, T, H, Hkv, D, Dv, mask, hb):
    """The accepted cells' calls, which give no second pair (a kv head's
    ``dq`` there is 16.8 MB at SDAR's eight heads of 4,096 x 2 rows, 67 MB
    at Mellum's): ``hvd_flash_dq`` and ``hvd_flash_dkv`` with the specs
    :func:`test_masked_backward_specs` holds, whatever the one backward's
    budget would say of their bytes."""
    _assert_two_backward_calls(B, T, H, Hkv, D, Dv, mask(), hb)
