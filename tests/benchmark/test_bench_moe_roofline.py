"""``moe_experts_roofline``: the grouped products' least time over the
device time of every grouped-product kind, the program's Pallas kernels
and XLA's together; nothing where a trace names neither (the recorded
BERT trace, a program without an expert layer) or where no routing was
counted."""

import gzip
import types

import numpy as np
import pytest

import bench_tree
from harness import registry, xplane

CONFIG = bench_tree.BENCH / "configs" / "sdar-30b-a3b"
RECORDED = bench_tree.REPO / "tests" / "benchmark" / "data" / "bert_b32_3steps.xplane.pb.gz"
PAIRS, STEPS = 21504.0, 4
# 11 products of pairs x 2048 x 768 a layer, six layers, at the v5e's peak
LEAST_S = 6 * 2 * 11 * PAIRS * 2048 * 768 / 197e12


def _ctx(device_ops, said):
    cfg = bench_tree.load(CONFIG / "config.json")
    return types.SimpleNamespace(
        config=cfg, flops=registry.load_module(str(CONFIG / "flops.py")),
        traced=types.SimpleNamespace(stamps=[0.0] * STEPS, global_batch=2, chips=1),
        say=said.append, trace=types.SimpleNamespace(device_ops=device_ops),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.fixture
def read(monkeypatch):
    """The reader, with the routing the steps counted given here: one
    layer-step of PAIRS pairs."""
    reader = registry.reader(str(bench_tree.BENCH), "layer_metrics",
                             "moe_experts_roofline")
    monkeypatch.setitem(reader.__globals__, "_routed",
                        lambda: {"pairs": PAIRS, "layers": 1.0})
    return reader


KINDS = {
    "xla": [["ragged-dot-none (custom-call)", 0.24],
            ["ragged-dot-metadata (custom-call)", 0.0004]],
    "pallas": [["hvd_moe_gmm_gate_up (custom-call)", 0.05],
               ["hvd_moe_gmm_down (custom-call)", 0.02],
               ["hvd_moe_tgmm_gate (custom-call)", 0.04]],
}


@pytest.mark.parametrize("present", ["both", "xla", "pallas"])
def test_reads_every_grouped_product_kind_there_is(read, present):
    said = []
    ops = [["fusion", 1.0], ["hvd_flash_fwd (custom-call)", 0.2]] + sum(
        (KINDS[k] for k in KINDS if present in ("both", k)), [])
    seconds = sum(s for k, s in ops if k.startswith(("ragged", "hvd_moe")))
    value = read(_ctx(ops, said))
    assert value == pytest.approx(100 * LEAST_S * STEPS / seconds) and value < 100
    assert "compute-bound" in said[-1]
    for kind, _ in ops[2:]:
        assert kind in said[-1]


@pytest.mark.parametrize("what", ["no-kind", "no-trace", "no-cost",
                                  "no-routing", "recorded-bert"])
def test_returns_nothing_where_there_is_nothing_to_read(read, what, monkeypatch):
    """The other cells, a run without ``--trace``, a configuration
    without the cost function, a program that counted no routing: None,
    and no raise."""
    ctx = _ctx([["fusion", 1.0], ["hvd_flash_fwd (custom-call)", 0.2]], [])
    if what == "no-routing":
        monkeypatch.setitem(read.__globals__, "_routed", lambda: {})
        ctx.trace.device_ops += KINDS["pallas"]
    if what == "no-trace":
        ctx.trace = None
    if what == "no-cost":
        ctx.flops = types.SimpleNamespace()
        ctx.trace.device_ops += KINDS["xla"]
    if what == "recorded-bert":
        from jax.profiler import ProfileData
        ctx.trace = xplane.reduce_profile(ProfileData.from_serialized_xspace(
            gzip.decompress(RECORDED.read_bytes())), chips=1)
        assert ctx.trace.device_ops
    assert read(ctx) is None


def test_routing_comes_from_the_programs_counter():
    from horovod_tpu import metrics
    from horovod_tpu.models import moe
    if not metrics.ACTIVE:
        pytest.skip("the metrics registry is switched off")
    routed = registry.reader(str(bench_tree.BENCH), "layer_metrics",
                             "moe_experts_roofline").__globals__["_routed"]
    before = routed()
    moe.record_routing(np.array([PAIRS, PAIRS, 5800.0, 1.0]))
    after = routed()
    assert after["pairs"] - before.get("pairs", 0.0) == PAIRS
    assert after["layers"] - before.get("layers", 0.0) == 1.0


def test_the_parents_program_reads_what_its_line_said():
    """62.08 ms of ``ragged-dot*`` a step over 21,504.3 pairs a layer:
    36.5% (ledger, PR 29, the cell's ``moe_experts_ms`` and its line)."""
    flops = registry.load_module(str(CONFIG / "flops.py"))
    cfg = bench_tree.load(CONFIG / "config.json")
    f, _ = flops.moe_kernel_cost(cfg, 21504.3)
    assert 100 * 6 * f / 197e12 / 62.08e-3 == pytest.approx(36.5, abs=0.05)
