"""The masked flash-attention kernels (``hvd_flash_fwd`` / ``dq`` /
``dkv`` under a mask of key ranges): against dense masked attention,
their tables, tiles and sub-tiles, the two layouts, their block specs and
what they count.  In interpret mode on the CPU; the packed kernels and the
entry points are tests/test_flash_attention.py.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import (block_diffusion_ranges as _block_diffusion_ranges,
                      dense_reference, described_chip as _described_chip,
                      eqns as _eqns, flash_grad_all as _grad_all,
                      flash_grew as _grew,
                      flash_kernel_counts as _kernel_counts, make_qkv,
                      pallas_calls as _pallas_calls)
from horovod_tpu.ops import flash_attention as fa


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
    # (groups of four at head_dim 64: the interpreter's time is the heads
    # a kernel unrolls, and a group's whole, a part of it and one head are
    # the branches; groups of eight run at 128, below)
    "g4-whole-group": ((4, 1, 64), None, 4),
    "g4-split-group": ((4, 1, 64), 2 << 20, 2),
    "g4-one-head": ((4, 1, 64), 1 << 20, 1),      # not even two fit
    "g2-d128": ((4, 2, 128), None, 2),
    "g1-d128": ((2, 2, 128), None, 1),
    "g8-d128": ((8, 1, 128), None, 8),
}
WHOLE = (128, None)          # tiles of 128: a mixed tile is taken whole
MASKED_CASES = (
    [(mask, per_batch, "g4-whole-group", WHOLE) for mask in sorted(MASKS)
     for per_batch in (False, True)]
    + [("block-diffusion", per_batch, heads, WHOLE) for heads in HEAD_CASES
       if heads != "g4-whole-group" for per_batch in (False, True)]
    # a mixed tile by its sub-tiles: 2 x 2 of them, and the chip's 4 x 4
    + [(mask, False, "g2-d128", (256, 128))
       for mask in sorted(set(SUB_MASKS) - {"window"})]
    + [(mask, False, "g4-whole-group", (256, 128))
       for mask in ("causal", "first-or-last")]
    + [("block-diffusion", False, "g2-d128", (512, 128))])


def _case_id(mask, per_batch, heads, tiles):
    return (f"{mask}-{'mask-per-row' if per_batch else 'one-mask'}-{heads}"
            + ("" if tiles == WHOLE else "-tiles-of-%d-by-%d" % tiles))


@pytest.mark.parametrize("mask,per_batch,heads,tiles", MASKED_CASES,
                         ids=[_case_id(*case) for case in MASKED_CASES])
def test_masked_kernels_match_dense_masked_attention(mask, per_batch, heads,
                                                     tiles, monkeypatch,
                                                     pallas_interpret):
    """Forward (out and lse, which ``dq`` and ``dkv`` read) and all three
    gradients, the mask known where the call is built (numpy) or traced
    per batch row, a forward step taking a whole GQA group, a part of
    one, or one head; at ``head_dim`` 64 transposed around the kernels
    (``heads``), at 128 on the caller's layout (``rows``: groups of 1, 2
    and 8); a mixed tile taken whole, or walked by its live sub-tiles
    (``tiles``) under every mask, two and eight heads a step."""
    from horovod_tpu import metrics
    blk, T = _tiles(monkeypatch, tiles)
    monkeypatch.setattr(metrics, "ACTIVE", True)
    (H, Hkv, D), budget, hb = HEAD_CASES[heads]
    before = _kernel_counts()
    if budget is not None:
        monkeypatch.setattr(fa, "_MASKED_STEP_VMEM", budget)
    assert fa._fwd_heads(H // Hkv, blk, blk, D, T // blk, T, 4) == hb
    ranges = SUB_MASKS[mask](T)
    # two batch rows where each has a mask of its own, else one: a grid
    # step of the interpreter costs what it costs, whatever it computes
    B = 2 if per_batch or ranges.ndim == 3 else 1
    q, k, v = make_qkv(B, T, H, Hkv, D)
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

    # one forward kernel for both: the gradients of ``(out ** 2).sum()``
    # are the forward's own transposed at ``2 out``
    (out, lse), transposed = jax.vjp(
        lambda q, k, v: fa.flash_attention_lse(q, k, v, mask=given), q, k, v)
    np.testing.assert_allclose(out, _dense_masked(q, k, v, live),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, _dense_masked_lse(q, k, live),
                               atol=2e-5, rtol=2e-5)
    got = transposed((2 * out, jnp.zeros_like(lse)))
    want = jax.grad(loss(lambda q, k, v: _dense_masked(q, k, v, live)),
                    (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")
    assert _grew(before) == {
        (kernel, "masked", "rows" if D % 128 == 0 else "heads")
        for kernel in ("fwd", "dq", "dkv")}


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


def test_masked_forward_specs(pallas_interpret):
    """A forward grid step's blocks: four of a group's eight query tiles
    on their kv head's whole keys and values.  At ``head_dim`` 128 every
    block is cut from the caller's layout: four heads are 512 lanes of
    ``[B, T, H*D]``, a kv head's keys 128 lanes of ``[B, T, Hkv*D]``."""
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


def test_causal_over_several_blocks_agrees_with_dense(monkeypatch,
                                                      pallas_interpret):
    """``causal=True`` is :func:`causal_ranges`: values and the three
    gradients against the dense reference, over a GQA group."""
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
def test_a_mask_that_cuts_no_tile_builds_no_sub_tile_table(mask, monkeypatch,
                                                           pallas_interpret):
    """Every tile full or dead (or a tile no larger than a sub-tile): no
    words, three tables in SMEM as before the sub-tiles, the pairs' table
    four wide, no scratch for the sub-tiles' statistics and no series of
    ``hvd_flash_subtiles_total``."""
    from horovod_tpu import metrics
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
def test_sdar_call_counts_its_tiles_and_sub_tiles(sub, monkeypatch,
                                                  pallas_interpret):
    """At the SDAR cell's ranges (``[xt ; x0]`` of 8,192 positions, blocks
    of 4, tiles of 512) ``hvd_flash_tiles_total`` reads what it read
    before the sub-tiles, 56 / 24 / 176 a kernel, and
    ``hvd_flash_subtiles_total`` the sub-tiles of the 24 mixed tiles in
    its three states, for each of the three kernels."""
    from horovod_tpu import metrics
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


def test_masked_call_counts_its_tiles_and_kernels(monkeypatch,
                                                  pallas_interpret):
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


@pytest.mark.parametrize("kind,dead,mixed,full", [
    ("window", 931, 62, 31), ("causal", 496, 32, 496)])
def test_plain_gqa_kernels_lower_at_16384_positions(kind, dead, mixed, full,
                                                    monkeypatch):
    """Mosaic takes the three masked kernels at the benchmark's
    mellum2-12b-a2.5b cell, the longest row they have had: 16,384
    positions (32 x 32 tiles of 512), 32 query heads over 4 at head_dim
    128 on the caller's ``[B, T, H*D]``, under the window of 1,024 (93
    tiles visited: the diagonal's 32 and the tile two back's 30 mixed, 31
    full) and under the causal ranges (528 visited, 32 mixed); the SMEM
    tables, the heads a step and the step's VMEM hold at that length."""
    T = 16384
    ranges = (fa.window_ranges(T, 1024) if kind == "window"
              else fa.causal_ranges(T))
    classes = fa.tile_classes(ranges[None], 512, 512, T)
    assert [int((classes == c).sum()) for c in (0, 1, 2)] == [dead, mixed, full]
    assert np.array_equal(
        fa.dense_mask(ranges[:2048], 2048),
        (lambda i, j: (j <= i) & ((i - j < 1024) | (kind == "causal")))(
            np.arange(2048)[:, None], np.arange(2048)[None, :]))
    one_chip = _described_chip(monkeypatch)
    q, k = (jax.ShapeDtypeStruct((1, T, h, 128), jnp.bfloat16,
                                 sharding=one_chip) for h in (32, 4))
    assert fa.supported(q, k, k, True, ranges)
    text = jax.jit(lambda q, k, v: jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, mask=ranges).astype(jnp.float32).sum(),
        (0, 1, 2))(q, k, v)).lower(q, k, k).compile().as_text()
    for name in ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"):
        assert name in text


# ----------------- differential attention's calls at published widths
# (models/hybrid.py: values twice as wide as queries and keys; the trunk's
# other tests are tests/test_hybrid.py)

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


# ----------------- latent attention's calls: a second query/key pair
# (models/hybrid.py's ``mla`` kind: a head's scores are a product with its
# own keys plus one with a rotary key that every head shares; the backward
# is tests/test_flash_masked_bwd.py)

def _pair_operands(B, T, H, Hkv, H2, D=128, D2=64, Dv=128, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    shapes = ((B, T, H, D), (B, T, Hkv, D), (B, T, Hkv, Dv), (B, T, H, D2),
              (B, T, H2, D2))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(ks, shapes)]


def _joined_dense(q, k, v, q2, k2, live):
    """Dense attention of the joined form: one query/key of both parts,
    the second pair's key heads copied to the first's."""
    from horovod_tpu.parallel.ring_attention import join_pair
    qq, kk = join_pair(q, k, (q2, k2))
    return _dense_masked(qq, kk, v, live)


@pytest.mark.parametrize("H,Hkv,H2,mask", [
    (2, 2, 1, "causal"), (4, 2, 1, "window"), (4, 4, 2, "block-diffusion")],
    ids=["mla-one-shared-key", "gqa-under-one-key", "two-key-heads"])
def test_second_pair_joins_the_scores_as_the_joined_form_does(
        H, Hkv, H2, mask, monkeypatch, pallas_interpret):
    """``flash_attention(pair=(q2, k2))``: the split form (the kernels add
    the second pair's product to each score tile, the pair's heads padded
    to a lane tile, its key head read under its own grouping) against dense
    attention over the joined ``[q ; q2]``, ``[k ; k2 a head]``: a key head
    for every query head (latent attention's), for a GQA group, and two key
    heads of the pair over four; ``out`` and ``lse``; the default scale is
    over both widths; counted as ``paired``."""
    from horovod_tpu import metrics
    monkeypatch.setattr(fa, "_BLOCK", 128)
    monkeypatch.setattr(metrics, "ACTIVE", True)
    T = 256
    q, k, v, q2, k2 = _pair_operands(1, T, H, Hkv, H2)
    ranges = MASKS[mask](T)
    live = jnp.asarray(fa.dense_mask(ranges, T))
    assert fa.supported(q, k, v, False, ranges, pair=(q2, k2))
    before = _kernel_counts()
    out, lse = fa._attention_lse(q, k, v, False, None, ranges, (q2, k2))
    np.testing.assert_allclose(out, _joined_dense(q, k, v, q2, k2, live),
                               atol=2e-5, rtol=2e-5)
    from horovod_tpu.parallel.ring_attention import join_pair
    np.testing.assert_allclose(lse, _dense_masked_lse(
        *join_pair(q, k, (q2, k2)), live), atol=2e-5, rtol=2e-5)
    assert _grew(before) == {("fwd", "paired", "rows")}


def test_second_pair_is_refused_where_the_kernels_cannot_take_it(
        pallas_interpret):
    q, k, v, q2, k2 = _pair_operands(1, 256, 4, 2, 1)
    why = lambda *a, **kw: fa._refusal(*a, **kw) or ""
    assert fa._refusal(q, k, v, (q2, k2)) is None
    assert "do not divide" in why(q, k, v, (q2, jnp.tile(k2, (1, 1, 4, 1))))
    assert "whole lane tiles" in why(q[..., :64], k[..., :64], v, (q2, k2))
    assert "multiple of 64" in why(q, k, v, (q2[..., :32], k2[..., :32]))
    assert "in q's dtype" in why(q, k, v, (q2.astype(jnp.bfloat16), k2))
    assert "in q's dtype" in why(q, k, v, (q2[:, :128], k2))
    # the pair's whole keys stay resident beside k and v: a step that does
    # not fit is refused, not built
    long = lambda h, d: jax.ShapeDtypeStruct((1, 65536, h, d), jnp.bfloat16)
    assert "bytes of VMEM" in why(long(2, 128), long(2, 128), long(2, 128),
                                  (long(2, 64), long(1, 64)))


@pytest.mark.parametrize("form", ["split", "joined"])
def test_latent_attentions_kernels_lower_at_16384_positions(form, monkeypatch):
    """Mosaic takes the masked kernels at the benchmark's
    kanana-2-30b-a3b cell, causal over 16,384 positions, 32 heads, bf16.
    ``split``: scores as a 128-wide product a head plus a 64-wide one with
    the one rotary key all 32 heads share (``pair=``), values 128, on the
    caller's ``[B, T, H*D]`` with no rank-4 ``transpose`` beside the
    kernels and the shared key never copied a head.  ``joined``: one
    192-wide query and key a head, the heads route, whose resident keys and
    values are the budget to the byte."""
    import re
    from horovod_tpu.parallel.ring_attention import join_pair
    one_chip = _described_chip(monkeypatch)
    T, H = 16384, 32
    sds = lambda h, d: jax.ShapeDtypeStruct((1, T, h, d), jnp.bfloat16,
                                            sharding=one_chip)
    q, k, v, q2, k2 = sds(H, 128), sds(H, 128), sds(H, 128), sds(H, 64), sds(1, 64)
    ranges = fa.causal_ranges(T)
    assert fa.supported(q, k, v, True, ranges, pair=(q2, k2))
    assert fa.supported(sds(H, 192), sds(H, 192), v, True, ranges)
    assert T * (192 + 128) * 2 == fa._VMEM_BUDGET

    def attend(q, k, v, q2, k2):
        if form == "split":
            return fa.flash_attention(q, k, v, mask=ranges, pair=(q2, k2))
        return fa.flash_attention(*join_pair(q, k, (q2, k2)), v, mask=ranges,
                                  sm_scale=192 ** -0.5)

    text = jax.jit(lambda *a: jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(),
        (0, 1, 2, 3, 4))(*a)).lower(q, k, v, q2, k2).compile().as_text()
    # the split form's backward is the one kernel (a kv head's dq and dq2
    # held in VMEM: tests/test_flash_masked_bwd.py has the rule)
    backward = (("hvd_flash_dqkv",) if form == "split" else
                ("hvd_flash_dq", "hvd_flash_dkv"))
    assert set(re.findall(r"hvd_flash_[a-z]+", text)) == {
        "hvd_flash_fwd", *backward}
    moved = re.findall(
        r"= \w+\[1,(?:32,16384|16384,32),\d+\]\S* (?:transpose|copy)\(", text)
    if form == "split":
        assert not moved, moved
    else:       # q, k, v, out and the gradients laid out heads first
        assert "bf16[1,32,16384,192]" in text
