"""The grouped matrix products' Pallas kernels (ops/grouped_matmul.py)
in interpret mode on the CPU, against ``lax.ragged_dot`` and a dense
product a group, for group layouts that break tilings; the combine
against ``.at[].add``; the expert layer's gradients through them against
the ``ragged_dot`` path's; which path a program's shapes take; and that
Mosaic takes the kernels at the benchmark's shapes, compiled for a v5e
that is described, not attached."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from _helpers import described_chip as _described_chip
from horovod_tpu import metrics
from horovod_tpu.models import moe
from horovod_tpu.ops import grouped_matmul as gm

R, K, N, G = 1536, 256, 128, 5            # three tiles of 512 rows
LAYOUTS = {
    "empty-group": [300, 0, 400, 100, 200],
    "one-row-group": [511, 1, 512, 0, 300],
    "straddling-tile-edges": [700, 323, 200, 100, 13],
    "one-group-holds-every-row": [0, 0, 1536, 0, 0],
    "rows-past-the-sum": [100, 50, 0, 0, 60],
    "sum-a-multiple-of-the-tile": [512, 256, 256, 0, 0],
    "sum-one-past-a-tile": [512, 256, 256, 1, 0],
    "no-row-at-all": [0, 0, 0, 0, 0],
}
CASES = [pytest.param(sizes, dtype, id=f"{name}-{jnp.dtype(dtype).name}")
         for name, sizes in LAYOUTS.items()
         for dtype in (jnp.float32, jnp.bfloat16)]


def _operands(sizes, dtype, transposed=False):
    """Rows (NaN past the sizes' sum: nothing may read them into a
    result), weights, sizes."""
    kx, kw = jax.random.split(jax.random.key(sum(sizes) + 7))
    live = (np.arange(R) < sum(sizes))[:, None]
    x = jnp.where(live, jax.random.normal(kx, (R, K)), jnp.nan).astype(dtype)
    w = jax.random.normal(kw, (G, N, K) if transposed else (G, K, N))
    return x, (w * K ** -0.5).astype(dtype), jnp.asarray(sizes, jnp.int32)


def _tol(dtype):
    return dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2e-2)


def _check_rows(y, x, w, sizes, dtype):
    total = int(sizes.sum())
    ref = lax.ragged_dot(jnp.nan_to_num(x), w, sizes)
    assert y.dtype == dtype and y.shape == ref.shape
    np.testing.assert_allclose(np.asarray(y[:total], np.float32),
                               np.asarray(ref[:total], np.float32),
                               **_tol(dtype))
    assert not np.asarray(y[total:], np.float32).any()    # zero, not garbage


@functools.lru_cache(maxsize=None)
def _gmm_of(dtype, transposed):
    """One product a group, traced and compiled once a dtype."""
    return jax.jit(lambda x, w, s: gm.gmm(
        lambda a, b: (gm.dot(a, b, transposed=transposed),), (x,), (w,), s,
        [(N, dtype)], "t"))


@pytest.mark.parametrize("sizes,dtype", CASES)
def test_gmm_is_ragged_dot(sizes, dtype, pallas_interpret):
    x, w, sizes = _operands(sizes, dtype)
    y, = _gmm_of(dtype, False)(x, w, sizes)
    _check_rows(y, x, w, sizes, dtype)


@pytest.mark.parametrize("sizes,dtype", CASES)
def test_gmm_reads_weights_transposed(sizes, dtype, pallas_interpret):
    x, w, sizes = _operands(sizes, dtype, transposed=True)
    y, = _gmm_of(dtype, True)(x, w, sizes)
    _check_rows(y, x, w.swapaxes(1, 2), sizes, dtype)


@functools.lru_cache(maxsize=None)
def _tgmm_walk(walk):
    """``tgmm`` jitted once a walk, so traced and compiled once a dtype
    and width: under what that walk's first caller had patched, and every
    caller of a walk patches alike."""
    return jax.jit(lambda *a: gm.tgmm(*a, walk))


def _tgmm_operands(sizes, dtype, wide=1):
    """``x`` (NaN past the sizes' sum, ``wide`` times the ``K`` columns),
    ``y``, sizes and what the accumulator held."""
    x, _, sizes = _operands(sizes, dtype)
    x = jnp.concatenate([x, -x[:, ::-1]][:wide], axis=1)
    live = (np.arange(R) < int(sizes.sum()))[:, None]
    y = jnp.where(live, jax.random.normal(jax.random.key(1), (R, N)),
                  jnp.nan).astype(dtype)
    return x, y, sizes, jax.random.normal(jax.random.key(3),
                                          (G, wide * K, N))


@pytest.mark.parametrize("sizes,dtype", CASES)
def test_tgmm_adds_each_groups_product_to_what_it_held(sizes, dtype, pallas_interpret):
    x, y, sizes, held = _tgmm_operands(sizes, dtype)
    out = _tgmm_walk("whole")(x, y, sizes, held)
    assert out.dtype == jnp.float32
    ends = np.cumsum(np.asarray(sizes))
    for g, (lo, hi) in enumerate(zip(ends - np.asarray(sizes), ends)):
        ref = np.asarray(held[g]) + (np.asarray(x[lo:hi], np.float32).T
                                     @ np.asarray(y[lo:hi], np.float32))
        np.testing.assert_allclose(out[g], ref, atol=1e-3, rtol=1e-4)
        if lo == hi:
            np.testing.assert_array_equal(out[g], held[g])   # kept to the bit


def _room(kb, item):
    """The VMEM a grid step needs for a block of ``kb`` rows of the toy
    accumulator (:func:`gm._tgmm_split`'s sum)."""
    return 2 * (2 * kb * N * 4 + 2 * gm._TGMM_TILE * max(kb, N) * item)


@pytest.mark.parametrize("sizes,dtype", CASES)
def test_tgmm_walks_a_large_accumulator_in_blocks_of_its_rows(
        sizes, dtype, pallas_interpret, monkeypatch):
    """An accumulator that, in and out and twice, does not fit a grid step
    (4,096 x 1,280 on the chip; here the step's room is cut to force it) is
    walked in two blocks of its rows over a grid (blocks, visits), to the
    same sums."""
    operands = _tgmm_operands(sizes, dtype)
    whole = _tgmm_walk("whole")(*operands)
    item = jnp.dtype(dtype).itemsize
    assert gm._tgmm_split(K, N, item) == 1
    monkeypatch.setattr(gm, "_STEP_VMEM", _room(K // 2, item))
    assert gm._tgmm_split(K, N, item) == 2
    halves = _tgmm_walk("halves")(*operands)
    np.testing.assert_allclose(halves, whole, atol=1e-3, rtol=1e-4)
    # the benchmark's two shapes: SDAR's whole, Solar's in two blocks
    monkeypatch.undo()
    assert gm._tgmm_split(2048, 768, 2) == gm._tgmm_split(768, 2048, 2) == 1
    assert gm._tgmm_split(4096, 1280, 2) == gm._tgmm_split(1280, 4096, 2) == 2
    assert gm._tgmm_split(8192, 2048, 4) == 8 and gm._tgmm_split(16384, 4096, 4) == 0


@pytest.mark.parametrize("blocks", [1, 2], ids=["a-block", "two-blocks"])
@pytest.mark.parametrize("sizes,dtype", CASES)
def test_tgmm_adds_a_long_product_in_passes_of_a_loop(
        sizes, dtype, blocks, pallas_interpret, monkeypatch):
    """A block whose product is over ``_TGMM_BODY`` (896 products at 2,048
    x 1,792 on the chip; here the line is cut to one pass of 128 rows) is
    added a slab of its rows a pass of a loop inside the visit: every
    element the same product over the tile's rows, added once, so the sums
    are the unlooped walk's to the bit; in a grid of two blocks (Solar's
    walk) as in one.  On whole numbers: the CPU's product at another width
    takes its sums in another order (the MXU's does not: bit-equal at the
    LFM2 cell's shapes, my chip runs, PR 54)."""
    x, y, sizes, held = _tgmm_operands(sizes, dtype, wide=blocks)
    operands = (jnp.round(2 * x), jnp.round(2 * y), sizes, jnp.round(8 * held))
    item, wide = jnp.dtype(dtype).itemsize, blocks * K
    if blocks == 2:
        monkeypatch.setattr(gm, "_STEP_VMEM", _room(K, item))
    assert gm._tgmm_split(wide, N, item) == blocks
    assert gm._tgmm_slab(K, N) == K
    at_once = _tgmm_walk(f"at-once-{blocks}")(*operands)
    monkeypatch.setattr(gm, "_TGMM_BODY", gm._TGMM_TILE // 128)
    assert gm._tgmm_slab(K, N) == 128
    looped = _tgmm_walk(f"looped-{blocks}")(*operands)
    np.testing.assert_array_equal(looped, at_once)


@pytest.mark.parametrize("K,N,split,slab", [
    (2048, 768, 1, 2048), (768, 2048, 1, 768),          # SDAR, kanana
    (2304, 896, 1, 2304), (896, 2304, 1, 896),          # Mellum
    (4096, 1280, 2, 1024), (1280, 4096, 2, 128),        # Solar
    (2048, 1792, 1, 1024), (1792, 2048, 1, 896),        # LFM2
    (2048, 1536, 1, 1024), (256, 128, 1, 256), (128, 8192, 1, 128),
])
def test_a_visits_body_holds_the_products_that_run_fast(K, N, split, slab):
    """The benchmark's five accumulators and their transposes: 384 and 504
    products a visit are made at once, as before PR 54; 640 (Solar's block)
    and 896 (LFM2) in passes of at most ``_TGMM_BODY``, the largest slab
    that divides the block in whole lanes."""
    assert gm._tgmm_split(K, N, 2) == split
    assert gm._tgmm_slab(K // split, N) == slab


def test_a_body_of_two_products_and_an_epilogue(pallas_interpret):
    """What the expert layer asks of ``gmm``: two weights a group, two
    outputs, one of them a column, from rows and a column of weights."""
    x, w, sizes = _operands(LAYOUTS["straddling-tile-edges"], jnp.float32)
    w2, wt = w[::-1], jnp.arange(R, dtype=jnp.float32)[:, None] / R

    def body(x, wt, a, b):
        acc = gm.dot(x, a) + gm.dot(x, b)
        return acc * wt, acc.sum(axis=1, keepdims=True)

    y, col = jax.jit(lambda *a: gm.gmm(
        body, a[:2], a[2:4], a[4], [(N, jnp.float32), (1, jnp.float32)],
        "t"))(x, wt, w, w2, sizes)
    ref = lax.ragged_dot(jnp.nan_to_num(x), w + w2, sizes)
    np.testing.assert_allclose(y, ref * wt, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(col, ref.sum(axis=1, keepdims=True),
                               atol=2e-4, rtol=2e-5)


def test_the_plan_visits_each_tile_group_pair_once_and_zeroes_the_rest():
    table, bounds, V = gm._plan(jnp.asarray([700, 0, 300, 24], jnp.int32),
                                2048, 512)
    g, written, read, flags = np.asarray(table).reshape(V, 4).T
    assert V == 4 + 4 - 1
    # tile 0 whole in group 0; tile 1 shared by groups 0, 2, 3; tile 2 and
    # 3 hold no row: zeroed; one visit left over
    assert list(zip(g, written, flags % 4)) == [
        (0, 0, 1), (0, 1, 1), (2, 1, 1), (3, 1, 1), (3, 2, 2), (3, 3, 2),
        (3, 3, 0)]
    assert list(read) == [0, 1, 1, 1, 1, 1, 1]          # nothing fetched past the pairs
    assert list(flags // 4 % 2) == [1, 1, 0, 0, 1, 1, 0]   # a tile's first visit
    assert list(flags // 8) == [1, 0, 1, 1, 0, 0, 0]       # a group's first
    assert list(bounds) == [0, 700, 700, 700, 700, 1000, 1000, 1024]


# ------------------------------------------------------------ the combine

TOKENS, WIDTH = 1024, 256                  # two tiles of 512 tokens
_ALL = list(range(TOKENS))
# for each group the tokens of its rows, ascending (a token meets a group
# once); group 0 first in the sorted rows
COMBINE_LAYOUTS = {
    "an-empty-expert": [_ALL[3::7], [], _ALL[::5], _ALL[500:530], [1023]],
    "every-pair-on-one-expert": [[], [], _ALL, [], []],
    # token 9 has no held expert, token 600 all five
    "a-token-without-and-one-with-all-k": [
        [t for t in _ALL[::3] if t != 9], [1, 600, 900], [600],
        [2, 3, 511, 512, 600, 1022], [600, 601]],
    "a-run-straddling-a-tile-edge": [
        _ALL[480:560], _ALL[505:519], [511], [512], _ALL[0:1024:2]],
    "no-row-at-all": [[], [], [], [], []],
    "rows-to-the-last": [_ALL[:700], _ALL[100:500], _ALL[600:], [5], [6, 7]],
}


def _combine_operands(layout, carry):
    """Rows (garbage past the groups' rows), their tokens (garbage there
    too), sizes, the carry, and how many rows the sizes cover."""
    rng = np.random.default_rng(len(str(layout)))
    tok = np.concatenate([np.sort(np.asarray(g, np.int64)) for g in layout]
                         + [np.zeros(0, np.int64)])
    p = len(tok)
    assert p <= R and all(len(set(g)) == len(g) for g in layout)
    tok = np.concatenate([tok, rng.integers(0, TOKENS, R - p)]).astype(np.int32)
    rows = rng.standard_normal((R, WIDTH)).astype(np.float32)
    rows[p:] = np.where(rng.random((R - p, WIDTH)) < 0.5, np.nan, 1e30)
    held = (rng.standard_normal((TOKENS, WIDTH)).astype(np.float32)
            if carry else np.zeros((TOKENS, WIDTH), np.float32))
    sizes = np.asarray([len(g) for g in layout], np.int32)
    return rows, tok, sizes, held, p


@jax.jit
def _combined(rows, tok, sizes, held, fresh):
    return gm.combine(rows, tok, sizes, held, "t", fresh=fresh)


@pytest.mark.parametrize("carry", [False, True, "fresh"],
                         ids=["zero", "a-carry", "fresh"])
@pytest.mark.parametrize("layout", COMBINE_LAYOUTS.values(),
                         ids=COMBINE_LAYOUTS.keys())
def test_combine_is_scatter_add(layout, carry, pallas_interpret):
    """To the last bit against the rows added in their order (the
    kernel's order), within 2 ulp of the sum's magnitude against
    ``.at[].add`` whatever order XLA takes; rows past the sizes' sum add
    nothing; a token with no row keeps what it held, or zero where the
    caller said the target was fresh."""
    rows, tok, sizes, held, p = _combine_operands(layout, carry is True)
    before = _kernel_counts()
    fresh = carry == "fresh"       # said fresh, what is held is never read
    got = np.asarray(_combined(
        rows, tok, sizes, held + np.float32("nan") if fresh else held, fresh))
    if metrics.ACTIVE:          # the kernel, traced once
        assert _grew(before) in ({}, {("combine", "pallas"): 1})
    ordered = held.copy()
    np.add.at(ordered, tok[:p], rows[:p])
    np.testing.assert_array_equal(got, ordered)
    xla = np.asarray(jnp.asarray(held).at[tok[:p]].add(rows[:p]))
    size = np.abs(held)
    np.add.at(size, tok[:p], np.abs(rows[:p]))
    assert (np.abs(got - xla) <= 2 * np.spacing(size)).all()
    untouched = np.setdiff1d(np.arange(TOKENS), tok[:p])
    np.testing.assert_array_equal(got[untouched], held[untouched])


def test_the_combines_plan_lists_each_run_in_chunks():
    """Two groups over two tiles of 512 tokens, chunks of 32 rows from
    the multiple of 8 under a run's first row."""
    tok = np.concatenate([np.arange(480, 560), [3, 600, 601], np.zeros(45)])
    chunks, first = gm._combine_plan(
        jnp.asarray(tok, jnp.int32), jnp.asarray([80, 3], jnp.int32), 1024)
    first = np.asarray(first)
    chunks = np.asarray(chunks).reshape(-1, 3)[:first[-1]]
    # tile 0: group 0's rows 0..31, then group 1's row 80 (copied from 80);
    # tile 1: group 0's rows 32..79 from row 32, 64; group 1's rows 81, 82
    assert first.tolist() == [0, 2, 5]
    assert chunks.tolist() == [[0, 0, 32], [80, 0, 1],
                               [32, 0, 32], [64, 0, 16], [80, 1, 3]]


@pytest.mark.parametrize("why,rows,out,reason", [
    ("cpu-backend", (1536, 256, jnp.float32), (1024, 256), "backend"),
    ("rows-of-bf16", (1536, 256, jnp.bfloat16), (1024, 256), "float32"),
    ("rows-no-chunk-multiple", (1000, 256, jnp.float32), (1024, 256), "multiple of 32"),
    ("tokens-no-tile-multiple", (1536, 256, jnp.float32), (1000, 256), "none of 512"),
    ("width-not-128", (1536, 96, jnp.float32), (1024, 96), "multiple of 128"),
    ("another-width", (1536, 256, jnp.float32), (1024, 128), "one width"),
    ("token-ids-past-the-smem", (131072, 128, jnp.float32), (1024, 128), "scalar memory"),
    ("a-step-past-the-vmem", (1536, 16384, jnp.float32), (1024, 16384), "VMEM"),
])
def test_combine_refusals_fall_back_to_scatter_add(why, rows, out, reason,
                                                   pallas_interpret):
    pallas_interpret(why != "cpu-backend")
    x = jax.ShapeDtypeStruct(rows[:2], rows[2])
    o = jax.ShapeDtypeStruct(out, jnp.float32)
    assert reason in gm._combine_refusal(x, o)
    if why == "another-width":          # nothing to add such rows to
        return
    before = _kernel_counts()
    tok, sizes = (jax.ShapeDtypeStruct((rows[0],), jnp.int32),
                  jax.ShapeDtypeStruct((4,), jnp.int32))
    text = str(jax.make_jaxpr(lambda *a: gm.combine(*a, "t"))(
        x, tok, sizes, o))
    assert "scatter-add" in text and "pallas_call" not in text
    if metrics.ACTIVE:
        assert _grew(before) == {("combine", "xla"): 1}


def test_combine_fallback_adds_nothing_past_the_sizes():
    rows, tok, sizes, held, p = _combine_operands(
        COMBINE_LAYOUTS["an-empty-expert"], True)
    got = np.asarray(gm.combine(jnp.nan_to_num(rows, nan=7.0), tok, sizes,
                                jnp.asarray(held), "t"))
    ordered = held.copy()
    np.add.at(ordered, tok[:p], rows[:p])
    np.testing.assert_array_equal(got, ordered)


# ----------------------------------------------------------- the dispatch

DISPATCH_LAYOUTS = {
    "an-empty-group-first": [[], _ALL[3::7], _ALL[::5], [1023]],
    "an-empty-group-in-the-middle": COMBINE_LAYOUTS["an-empty-expert"],
    "an-empty-group-last": [_ALL[3::7], _ALL[::5], _ALL[500:530], []],
    "every-pair-in-one-group": COMBINE_LAYOUTS["every-pair-on-one-expert"],
    "no-row-at-all": COMBINE_LAYOUTS["no-row-at-all"],
    # group 0's run in the second tile of tokens is one row, token 512
    "a-run-one-row-into-a-tile": [_ALL[400:513], _ALL[505:519], [511], [512]],
    # borders at rows 3, 8, 21, 121, 122, 635: no multiple of 16 but 8
    "borders-off-every-block": [[7, 8, 9], _ALL[500:505], _ALL[30:43],
                                _ALL[460:560], [0], _ALL[1:1024:2] + [1022]],
    "borders-on-blocks": [_ALL[:512], _ALL[16:48], _ALL[::4]],
    "six-groups-in-one-block": [[1], [2], [3, 4], [], [5], [600], _ALL[9:90]],
    # 1,536 pairs: the border after the last group is the end of the rows
    "rows-to-the-last": COMBINE_LAYOUTS["rows-to-the-last"] + [_ALL[10:19]],
}


def _dispatch_operands(layout, dtype, rows=R, groups=7):
    """The table, the rows' tokens (garbage past the groups' rows), the
    sizes (empty groups after the layout's, up to ``groups``: one traced
    kernel a dtype serves every layout) and how many rows they cover."""
    tok = np.concatenate([np.sort(np.asarray(g, np.int64)) for g in layout]
                         + [np.zeros(0, np.int64)])
    rng = np.random.default_rng(len(tok))
    p = len(tok)
    tok = np.concatenate([tok, rng.integers(0, TOKENS, rows - p)])
    table = jnp.asarray(rng.standard_normal((TOKENS, WIDTH)), dtype)
    sizes = [len(g) for g in layout] + [0] * (groups - len(layout))
    return table, jnp.asarray(tok, jnp.int32), jnp.asarray(sizes, jnp.int32), p


@jax.jit
def _dispatched(table, tok, sizes):
    return gm.dispatch(table, tok, sizes, jnp.bfloat16, "t")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16-to-bf16", "float32-to-bf16"])
@pytest.mark.parametrize("layout", DISPATCH_LAYOUTS.values(),
                         ids=DISPATCH_LAYOUTS.keys())
def test_dispatch_is_the_gather_on_the_rows_the_sizes_cover(
        layout, dtype, pallas_interpret):
    """``table[tok]`` in bfloat16, to the last bit, on every row the
    sizes cover, whatever blocks of 32 rows the groups' borders fall in;
    the cotangent's table is float32 and its rows leave rounded."""
    table, tok, sizes, p = _dispatch_operands(layout, dtype)
    before = _kernel_counts()
    got = _dispatched(table, tok, sizes)
    if metrics.ACTIVE:          # the kernel, traced once a dtype
        assert _grew(before) in ({}, {("gather", "pallas"): 1})
    assert got.shape == (R, table.shape[1]) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got[:p], np.float32),
        np.asarray(table[tok[:p]].astype(jnp.bfloat16), np.float32))


def test_the_dispatchs_plan_stages_a_block_with_a_border_once():
    """Borders at rows 0, 3, 3, 20, 36, 36 of blocks 0, 0, 0, 0, 32, 32:
    the first border of each block names it."""
    edge, blocks = gm._dispatch_plan(
        jnp.asarray(np.r_[1, 2, 600, np.arange(17), np.arange(500, 516),
                          np.zeros(28)], jnp.int32),
        jnp.asarray([3, 0, 17, 16, 0], jnp.int32), 1024)
    assert np.asarray(blocks).reshape(-1, 3).tolist() == [
        [0, 0, 0], [3, 0, 0], [3, 0, 0], [20, 0, 0], [36, 32, 4],
        [36, 32, 4]]
    # group 0: two rows under token 512 and one over; group 3: twelve, four
    assert np.asarray(edge).reshape(3, 5).tolist() == [
        [0, 3, 3, 20, 36], [2, 3, 20, 32, 36], [3, 3, 20, 36, 36]]


def test_rows_the_dispatch_leaves_unwritten_reach_no_result(pallas_interpret):
    """The dispatch writes no row past the sizes' sum; with NaN there the
    grouped products, their gradients and the combine stay finite, and
    zero where a row is of no pair."""
    E, D, F, rows = 4, WIDTH, 128, 512
    table, tok, sizes, p = _dispatch_operands(
        [_ALL[3::7], [], _ALL[::5], _ALL[500:530]], jnp.bfloat16, rows, E)
    xs = jnp.where((jnp.arange(rows) < p)[:, None],
                   _dispatched(table, tok, sizes), jnp.nan)
    ks = jax.random.split(jax.random.key(3), 5)
    wg, wu, wd = ((jax.random.normal(k, shape) * 0.1).astype(jnp.bfloat16)
                  for k, shape in zip(ks, ((E, D, F), (E, D, F), (E, F, D))))
    wt = jax.random.uniform(ks[3], (rows,), jnp.float32)
    assert gm.supported(xs, wg, wu, wd)

    @jax.jit
    def consumers(xs):
        ys = moe._expert_ffn(xs, wg, wu, wd, wt, sizes)
        held = [jnp.zeros(w.shape, jnp.float32) for w in (wg, wu, wd)]
        return (ys, gm.combine(ys, tok, sizes, jnp.zeros((TOKENS, D)), "t"),
                moe._expert_ffn_grads(xs, wg, wu, wd, wt, sizes, xs, *held))

    ys, out, grads = consumers(xs)
    for a in (ys, out, *grads):
        assert np.isfinite(np.asarray(a, np.float32)).all()
    assert not np.asarray(ys[p:]).any() and not np.asarray(grads[0][p:]).any()


@pytest.mark.parametrize("why,table,rows,reason", [
    ("cpu-backend", (1024, 256, jnp.bfloat16), 1536, "backend"),
    ("width-not-128", (1024, 96, jnp.bfloat16), 1536, "width 96 is no multiple of 256"),
    ("width-128-not-256", (1024, 384, jnp.bfloat16), 1536, "no multiple of 256"),
    ("half-precision", (1024, 256, jnp.float16), 1536, "neither"),
    ("rows-of-float32", (1024, 256, jnp.float32), 1536, "not bfloat16"),
    ("rows-no-block-multiple", (1024, 256, jnp.float32), 1000, "multiple of 32"),
    ("tokens-no-tile-multiple", (1000, 256, jnp.float32), 1536, "none of 512"),
    ("token-ids-past-the-smem", (1024, 256, jnp.bfloat16), 131072, "scalar memory"),
    ("a-step-past-the-vmem", (1024, 16384, jnp.float32), 1536, "VMEM"),
])
def test_dispatch_refusals_fall_back_to_the_gather(why, table, rows, reason,
                                                   pallas_interpret):
    pallas_interpret(why != "cpu-backend")
    t = jax.ShapeDtypeStruct(table[:2], table[2])
    tok, sizes = (jax.ShapeDtypeStruct((rows,), jnp.int32),
                  jax.ShapeDtypeStruct((4,), jnp.int32))
    dtype = jnp.float32 if why == "rows-of-float32" else jnp.bfloat16
    assert reason in gm._dispatch_refusal(t, tok, sizes, dtype)
    before = _kernel_counts()
    text = str(jax.make_jaxpr(lambda *a: gm.dispatch(*a, dtype, "t"))(
        t, tok, sizes))
    assert "gather" in text and "pallas_call" not in text
    if metrics.ACTIVE:
        assert _grew(before) == {("gather", "xla"): 1}


# ------------------------------------------------ the expert layer on them

def _layer_operands(dtype, D, F, E=4, k=2, n_tokens=1024, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    tokens = jax.random.normal(ks[0], (n_tokens, D)).astype(dtype)
    ws = [(jax.random.normal(key, shape) * shape[1] ** -0.5).astype(dtype)
          for key, shape in zip(ks[1:4], ((E, D, F), (E, D, F), (E, F, D)))]
    # a token's k experts are distinct, as top_k's are: a token meets a
    # group once, which the combine counts on
    e = (jax.random.randint(ks[4], (n_tokens, 1), 0, E + 2) + jnp.cumsum(
        jax.random.randint(ks[0], (n_tokens, k), 1, (E + 2) // k + 1),
        axis=1) - 1).reshape(-1) % (E + 2)
    e = jnp.where(e >= E, E, e)                 # a third are not held here
    pair_w = jax.random.uniform(ks[5], (n_tokens * k,), jnp.float32)
    rows = 512                                  # three chunks of the pairs
    order = jnp.pad(jnp.argsort(e, stable=True).astype(jnp.int32), (0, rows))
    sizes = (e[:, None] == jnp.arange(E)[None]).sum(0).astype(jnp.int32)
    co = jax.random.normal(ks[6], (n_tokens, D), jnp.float32)

    def loss(tokens, wg, wu, wd, pair_w):
        y, computed = moe._held_experts(tokens, wg, wu, wd, pair_w, order,
                                        sizes, k, rows)
        return (y * co).sum(), computed

    return loss, (tokens, *ws, pair_w), int(sizes.sum())


def _kernel_counts():
    family = metrics.registry().to_dict().get("hvd_moe_gmm_kernel_total", {})
    return {(s["labels"]["kernel"], s["labels"]["path"]): s["value"]
            for s in family.get("series", [])}


def _grew(before):
    after = _kernel_counts()
    return {key: after[key] - before.get(key, 0) for key in after
            if after[key] != before.get(key, 0)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_held_experts_gradients_through_the_kernels(dtype, pallas_interpret):
    pallas_interpret(False)
    loss, args, pairs = _layer_operands(dtype, D=256, F=128)
    grad = lambda: jax.jit(jax.value_and_grad(
        loss, (0, 1, 2, 3, 4), has_aux=True))(*args)
    before = _kernel_counts()
    (ref, computed), ref_grads = grad()
    assert computed == pairs
    if metrics.ACTIVE:         # forward 3, made again 3, autodiff's 3 + 3
        assert _grew(before) == {("gmm", "xla"): 9, ("tgmm", "xla"): 3,
                                 ("combine", "xla"): 2, ("gather", "xla"): 3}
    before = _kernel_counts()
    pallas_interpret()
    (got, computed), grads = grad()
    assert computed == pairs
    if metrics.ACTIVE:         # gate_up, down; gate_up, dh, dx; three tgmm
        assert _grew(before) == {               # float32 rows are XLA's
            ("gmm", "pallas"): 5, ("tgmm", "pallas"): 3,
            ("combine", "pallas"): 2,
            ("gather", "pallas" if dtype == jnp.bfloat16 else "xla"): 3}
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert abs(got - ref) <= tol * abs(ref) + tol
    for a, b in zip(grads, ref_grads):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()


def test_toy_widths_on_the_cpu_take_ragged_dot(pallas_interpret):
    """Widths that are no multiples of 128 run XLA's products even where
    the kernels could be interpreted: the path is chosen from the shapes."""
    loss, args, _ = _layer_operands(jnp.float32, D=32, F=16)
    before = _kernel_counts()
    jax.make_jaxpr(jax.grad(lambda *a: loss(*a)[0], (0, 1, 2, 3, 4)))(*args)
    if metrics.ACTIVE:
        assert _grew(before) == {("gmm", "xla"): 9, ("tgmm", "xla"): 3,
                                 ("combine", "xla"): 2, ("gather", "xla"): 3}


@pytest.mark.parametrize("why,rows,weights,reason", [
    ("cpu-backend", (1024, 256, jnp.float32), (4, 256, 128), "backend"),
    ("rows-no-tile-multiple", (768, 256, jnp.float32), (4, 256, 128), "multiple of 512"),
    ("width-not-128", (1024, 96, jnp.float32), (4, 96, 128), "multiples of 128"),
    ("half-precision", (1024, 256, jnp.float16), (4, 256, 128), "neither"),
    ("weights-of-another-dtype", (1024, 256, jnp.bfloat16), (4, 256, 128), "rows' dtype"),
    ("a-step-past-the-vmem", (1024, 8192, jnp.float32), (4, 8192, 2048), "VMEM"),
])
def test_refusals_name_their_reason(why, rows, weights, reason,
                                    pallas_interpret):
    pallas_interpret(why != "cpu-backend")
    x = jax.ShapeDtypeStruct(rows[:2], rows[2])
    w = jax.ShapeDtypeStruct(
        weights, jnp.float32 if why == "weights-of-another-dtype" else rows[2])
    assert reason in gm._refusal(x, w, w) and not gm.supported(x, w, w)
    ok = jax.ShapeDtypeStruct((1024, 256), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 256, 128), jnp.bfloat16)
    assert gm.supported(ok, w, w) == (why != "cpu-backend")


# ----------------------------------------------------- for the chip

@pytest.mark.parametrize("call", ["forward", "backward", "combine",
                                  "dispatch"])
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


@pytest.mark.parametrize("call", ["forward", "backward", "combine",
                                  "dispatch"])
def test_grouped_matmul_kernels_lower_at_a_hidden_size_of_4096(call, monkeypatch):
    """The same at the solar-open2-250b cell: a chunk of 2,560 rows of width
    4,096 over 8 held experts of width 1,280; ``tgmm`` walks its float32
    ``[4096, 1280]`` accumulator in two blocks of rows, since in and out and
    twice it is 84 MB and a grid step may hold 64."""
    assert gm._tgmm_split(4096, 1280, 2) == 2
    _grouped_kernels_lower(call, monkeypatch, 2560, 4096, 1280, 8, 8192)


@pytest.mark.parametrize("call", ["forward", "backward", "combine",
                                  "dispatch"])
def test_grouped_matmul_kernels_lower_at_the_most_rows_they_have_had(call, monkeypatch):
    """The same at the mellum2-12b-a2.5b cell: a chunk of 49,152 rows (half
    over the 32,768 pairs that even routing sends a layer) of width 2,304
    over 16 held experts of width 896, combined into 16,384 tokens;
    ``tgmm``'s float32 ``[2304, 896]`` accumulator, 8.3 MB, is walked whole
    like SDAR's ``[2048, 768]``, not in two blocks like Solar's."""
    assert moe._chunk_rows(16384, 8, 16, 64) == 49152
    assert gm._tgmm_split(2304, 896, 2) == gm._tgmm_split(896, 2304, 2) == 1
    _grouped_kernels_lower(call, monkeypatch, 49152, 2304, 896, 16, 16384)


@pytest.mark.parametrize("call", ["forward", "backward", "combine",
                                  "dispatch"])
def test_grouped_matmul_kernels_lower_at_the_widest_experts(call, monkeypatch):
    """The same at the lfm2-8b-a1b cell: a chunk of 24,576 rows of width
    2,048 over 8 held experts of width 1,792; ``tgmm``'s float32 ``[2048,
    1792]`` and ``[1792, 2048]`` accumulators, 14.7 MB, fit a grid step to
    the byte and are walked whole, a visit's 896 products in two passes of
    a loop (a slice of ``x``'s lanes at a traced multiple of the slab)."""
    assert moe._chunk_rows(16384, 4, 8, 32) == 24576
    _grouped_kernels_lower(call, monkeypatch, 24576, 2048, 1792, 8, 16384)


def _grouped_kernels_lower(call, monkeypatch, R, D, F, E, tokens):
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
    elif call == "dispatch":
        # the activations' table and the cotangent's, which leaves rounded
        text = jax.jit(lambda a, b, tok, sizes: (
            gm.dispatch(a, tok, sizes, jnp.bfloat16, "tokens"),
            gm.dispatch(b, tok, sizes, jnp.bfloat16, "dout"))).lower(
            sds((tokens, D), jnp.bfloat16), sds((tokens, D), jnp.float32),
            sds((R,), jnp.int32), sizes).compile().as_text()
        names = ("hvd_moe_dispatch_tokens", "hvd_moe_dispatch_dout")
        assert "gather" not in text
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
