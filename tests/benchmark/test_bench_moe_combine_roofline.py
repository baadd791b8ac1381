"""``moe_combine_roofline``: the two combines' least time by bytes over
the device time of the ``hvd_moe_combine*`` kinds; nothing where a trace
names none (the parent's program, whose combines are scatter-adds inside
``fusion``; the recorded BERT trace) or where no routing was counted."""

import gzip
import types

import pytest

import bench_tree
from harness import registry, xplane

CONFIG = bench_tree.BENCH / "configs" / "sdar-30b-a3b"
RECORDED = bench_tree.REPO / "tests" / "benchmark" / "data" / "bert_b32_3steps.xplane.pb.gz"
PAIRS, TOKENS, STEPS = 21502.0, 16384, 4
# two combines a layer, six layers: every pair's float32 row of 2,048 read,
# every token's written, at the v5e's 819 GB/s
LEAST_S = 6 * 2 * (PAIRS + TOKENS) * 2048 * 4 / 819e9


def _ctx(device_ops, said):
    return types.SimpleNamespace(
        config=bench_tree.load(CONFIG / "config.json"),
        traced=types.SimpleNamespace(stamps=[0.0] * STEPS, global_batch=2, chips=1),
        say=said.append, trace=types.SimpleNamespace(device_ops=device_ops),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.fixture
def read(monkeypatch):
    reader = registry.reader(str(bench_tree.BENCH), "layer_metrics",
                             "moe_combine_roofline")
    monkeypatch.setitem(reader.__globals__, "_routed",
                        lambda: {"pairs": 3 * PAIRS, "layers": 3.0})
    return reader


OTHERS = [["fusion", 1.0], ["hvd_moe_gmm_down (custom-call)", 0.02],
          ["hvd_flash_fwd (custom-call)", 0.2]]
COMBINES = [["hvd_moe_combine_out (custom-call)", 0.022],
            ["hvd_moe_combine_dtok (custom-call)", 0.024]]


@pytest.mark.parametrize("present", ["both", "out", "dtok"])
def test_reads_the_combine_kinds_and_no_other(read, present):
    said = []
    mine = [c for c in COMBINES if present == "both" or c[0].endswith(
        present + " (custom-call)")]
    value = read(_ctx(OTHERS + mine, said))
    seconds = sum(s for _, s in mine)
    assert value == pytest.approx(100 * LEAST_S * STEPS / seconds)
    assert LEAST_S == pytest.approx(4.548e-3, rel=1e-3)
    if present == "both":
        assert 35 < value < 100
    for kind, _ in mine:
        assert kind in said[-1]
    assert "gmm" not in said[-1] and "least by bytes" in said[-1]


def test_the_least_is_each_row_read_and_each_token_written_twice_a_layer(read):
    cost = read.__globals__["combine_bytes"]
    cfg = bench_tree.load(CONFIG / "config.json")
    assert cost(cfg, 0, TOKENS) == 2 * TOKENS * 2048 * 4
    assert cost(cfg, PAIRS, 0) == 2 * PAIRS * 2048 * 4
    # a call at the cell's sizes: 0.379 ms
    assert cost(cfg, PAIRS, TOKENS) / 2 / 819e9 == pytest.approx(0.379e-3, rel=2e-3)


@pytest.mark.parametrize("what", ["no-kind", "no-trace", "no-routing",
                                  "recorded-bert"])
def test_returns_nothing_where_there_is_nothing_to_read(read, what, monkeypatch):
    """The parent's program (scatter-adds inside ``fusion``), the other
    cells, a run without ``--trace``, a program that counted no routing:
    None, and no raise."""
    ctx = _ctx(list(OTHERS), [])
    if what == "no-routing":
        monkeypatch.setitem(read.__globals__, "_routed", lambda: {})
        ctx.trace.device_ops += COMBINES
    if what == "no-trace":
        ctx.trace = None
    if what == "recorded-bert":
        from jax.profiler import ProfileData
        ctx.trace = xplane.reduce_profile(ProfileData.from_serialized_xspace(
            gzip.decompress(RECORDED.read_bytes())), chips=1)
        assert ctx.trace.device_ops
    assert read(ctx) is None
