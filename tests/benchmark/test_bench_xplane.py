"""The reduction from a profiler trace to numbers: its arithmetic on
made-up events, and the whole of it on a small trace recorded on a v5e
(three steps of bert-base-ft.s128-b32.dp1, PR 23)."""

import gzip
import types

import pytest

import bench_tree
from harness import xplane

RECORDED = bench_tree.REPO / "tests" / "benchmark" / "data" / "bert_b32_3steps.xplane.pb.gz"


def test_union_overlap_and_self_time():
    merged = xplane._union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]] and xplane._length(merged) == 6
    assert xplane._overlap(merged, [[2, 6], [7, 20]]) == 1 + 1 + 1
    ops = [(0, 100, "%while.1 = (s32[]) while(%t)"),
           (10, 30, "%fusion.2 = f32[8] fusion(%a)"),
           (50, 40, "%fusion.3 = f32[8] fusion(%b)"),
           (200, 5, "%copy.9 = f32[8] copy(%c)")]
    assert xplane._self_times(ops) == [["while.1", "while", 30], ["fusion.2", "fusion", 30],
                                       ["fusion.3", "fusion", 40], ["copy.9", "copy", 5]]
    assert xplane._instruction(
        '%all-reduce.4 = (f32[2,64]{1,0}, f32[8]{0}) all-reduce(%x, %y), channel_id=1'
    ) == ("all-reduce.4", "all-reduce")


def test_reduce_a_made_up_two_chip_trace():
    def chip(i):
        return bench_tree.plane(f"/device:TPU:{i}", **{
            "XLA Modules": [(0, 1000, "jit_step(1)")],
            "XLA Ops": [(0, 400, "%fusion.1 = f32[8] fusion(%a)"),
                        (400, 200, "%all-reduce.1 = f32[8] all-reduce(%g)"),
                        (700, 100, '%k.1 = f32[8] custom-call(%q), custom_call_target="tpu_custom_call"')],
            "Async XLA Ops": [(300, 200, "%all-reduce-start.2 = f32[8] all-reduce-start(%h)")]})
    host = bench_tree.plane("/host:CPU", python=[(590, 50, "bench_dispatch"), (640, 400, "bench_wait"),
                                       (0, 5, "$builtins len")])
    r = xplane.reduce_profile(types.SimpleNamespace(planes=[chip(0), chip(1), host]), chips=2)
    assert (r.busy_s, r.window_s) == (700e-9, 1000e-9)
    assert r.kernel_s == 100e-9
    # all-reduce runs over [300, 600); a fusion hides [300, 400)
    assert (r.collective_s, r.collective_exposed_s) == (300e-9, 200e-9)
    assert r.breakdown()["device_ops"][0] == ["fusion", 400e-9]
    assert dict(map(tuple, r.breakdown()["idle_gaps"])) == pytest.approx(
        {"bench_dispatch": 40e-9, "bench_wait": 60e-9 + 200e-9})
    assert xplane.reduce_profile(types.SimpleNamespace(planes=[host]), chips=1) is None


def test_reduce_the_recorded_v5e_trace(tmp_path):
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes()))
    r = xplane.reduce_profile(profile, chips=1)
    assert 0 < r.busy_s <= r.window_s
    assert r.busy_s / r.window_s > 0.95            # two steps in flight: the chip stays fed
    assert r.collective_s is None                  # one chip: nothing to exchange
    steps = 3
    # 4 Pallas calls a layer (forward, its remat, two backward), 12 layers
    assert 5e-3 < r.kernel_s / steps < 40e-3
    ops = r.breakdown()["device_ops"]
    assert len(ops) == 10 and all(s > 0 for _, s in ops)
    assert sum(s for _, s in r.device_ops) == pytest.approx(r.busy_s, rel=0.02)
    assert any("custom-call" in name for name, _ in ops)
    assert {name for name, _ in r.idle_gaps} <= set(xplane.HOST_SPANS) | {"between spans"}
