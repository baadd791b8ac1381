"""Every cell rehearsed on the CPU at a toy size through run.py's own code
path: a well-formed last line that names the CPU, and each configuration's
plain reference against the program on one and on four virtual devices."""

import json

import pytest

import bench_tree

MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]
# float32 at a toy size agrees far inside the limits that bf16 is held to
TIGHT = 2e-3


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tree.make_tree(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,chips", CELLS)
def test_cell_rehearses_to_a_well_formed_line_that_names_the_cpu(tree, cell, chips):
    trace = 1 if chips == 4 else 0
    result, out = bench_tree.run_cell(tree, cell, chips, trace=trace)
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["device"]["platform"] == "cpu"          # refused as a measurement
    assert result["device"]["count"] == chips
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and all(result["checks"].values())
    section = "per_layer" if trace else "end_to_end"
    sources = {m["name"]: m["source"] for m in MANIFEST[section]}
    assert result["metrics"], "a cell reports something"
    for name, m in result["metrics"].items():
        assert m["unit"] == next(x["unit"] for x in MANIFEST[section] if x["name"] == name)
        # no time or rate from a CPU goes under a device metric's name
        assert (m["value"] is None) == (sources[name] != "program_counter")
    # the plain reference and the program agree, on four devices with the
    # single-device reference of the whole batch
    assert {k.split(".")[0] for k in result["compared"]} == (
        {"base", "main"} if chips == 4 else {"main"})
    for name, c in result["compared"].items():
        assert c["value"] < TIGHT, (name, c)
    if chips == 4:
        assert "replicas bit-identical" in result["checks"]
        assert result["metrics"]["allreduce_mb"]["value"] > 0
        assert result["metrics"]["compiles_in_window"]["value"] == 0
