"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files, and an entry each in BENCHMARK.json; the harness runs
them with no edit to a file that was there."""

import hashlib
import json
import shutil

import pytest

import bench_tree
import test_bench_manifest as held_to


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def _add(root, config, cfg, traffic, metric, read, new_traffic=None):
    """Under ``root``: a copy of ``resnet50-synth`` named ``config`` with
    ``cfg`` laid over its file (None takes a key away), one cell of it
    under ``traffic`` (a new mix if ``new_traffic`` is given), one per-layer
    metric that ``read`` computes, and their entries.  Returns (the
    manifest, the cell's name)."""
    bench = root / "benchmark"
    shutil.copytree(bench / "configs" / "resnet50-synth", bench / "configs" / config)
    cfg = {**bench_tree.load(bench / "configs" / config / "config.json"),
           "name": config, **cfg}
    cfg = {k: v for k, v in cfg.items() if v is not None}
    (bench / "configs" / config / "config.json").write_text(json.dumps(cfg))
    if new_traffic:
        (bench / "traffic" / f"{traffic}.json").write_text(json.dumps(new_traffic))
    cell = {"config": config, "traffic": traffic, "chips": 1, "why": "a throw-away cell"}
    name = f"{config}.{traffic}.dp1"
    (bench / "workloads" / f"{name}.json").write_text(json.dumps(cell))
    (bench / "layer_metrics" / f"{metric}.py").write_text(
        'UNIT, LAYER, MOVES, SOURCE = "count", "Runtime", "throughput", "program_counter"\n'
        f"def read(ctx):\n    return {read}\n")
    manifest = bench_tree.load(root / "BENCHMARK.json")
    manifest["configs"].append(
        {"name": config, "source": cfg["source"], "reduced": cfg["reduced"], "why": "test",
         "file": f"benchmark/configs/{config}/config.json"})
    manifest["workloads"].append({"name": name, **cell})
    manifest["per_layer"].append(
        {"name": metric, "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "Runtime", "moves": "throughput",
         "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return manifest, name


def test_new_cell_config_traffic_and_metric_run_as_added_files(tmp_path):
    tree = bench_tree.make_tree(tmp_path)
    before = _digest(tree / "benchmark")
    _, cell = _add(tree, "throwaway-net", {"num_classes": 7}, "throwaway-mix",
                   "steps_counted", "len(ctx.main.stamps)",
                   new_traffic={"per_chip_batch": 4, "resident": "host", "pool_batches": 3,
                                "warmup_steps": 2, "loss_must_fall": False})
    result, _ = bench_tree.run_cell(tree, cell, 1, trace=1)
    assert result["correct"] is True
    assert result["metrics"]["steps_counted"]["value"] == result["attempted"] > 0
    assert "feed_ms" not in result["metrics"]      # another cell's metric stays out
    after = _digest(tree / "benchmark")
    assert {k: after[k] for k in before} == before, "no file that was there changed"
    assert len(after) == len(before) + 7


# The next model_config PR in small: a configuration cut to a chip's share,
# with the cut stated in its own file, lands beside the real files at their
# published sizes, before any toy size is laid over them.
CUT = {"num_classes": 125, "stage_blocks": [3, 4, 6, 1],
       "reduced": ["num_classes", "stage_blocks"],
       "published": {"num_classes": 1000, "stage_blocks": [3, 4, 6, 3]},
       "deployment": "8 chips share each layer: a chip holds an eighth of the "
                     "classifier's rows; the stages left out lie on further chips",
       "toy": {"block": "basic", "stage_blocks": [2, 2, 2, 2], "width": 8,
               "num_classes": 7, "image_size": 32}}


def _add_cut_configuration(root, **changes):
    return _add(root, "throwaway-cut", {**CUT, **changes}, "device-fixed.b128",
                "rows_counted", "len(ctx.main.stamps) * ctx.main.global_batch")


def test_cut_configuration_with_its_toy_sizes_runs_as_added_files(tmp_path):
    real = bench_tree.copy_files(tmp_path / "real")
    before = _digest(real / "benchmark")
    manifest, cell = _add_cut_configuration(real)
    held_to.workload_file_agrees_with_manifest(manifest, real, cell)
    held_to.layer_metric_file_agrees_with_manifest(manifest, real, "rows_counted")
    for c in manifest["configs"]:       # those that were there pass beside it
        held_to.config_file_states_what_is_run(manifest, real, c["name"])

    tree = bench_tree.make_tree(tmp_path / "tree", source=real)
    toy = bench_tree.load(tree / "benchmark" / "configs" / "throwaway-cut" / "config.json")
    assert (toy["num_classes"], toy["width"]) == (7, 8)
    result, _ = bench_tree.run_cell(tree, cell, 1, trace=1)
    assert result["correct"] is True
    assert result["metrics"]["rows_counted"]["value"] == 8 * result["attempted"] > 0
    after = _digest(real / "benchmark")
    assert {k: after[k] for k in before} == before, "no file that was there changed"
    assert len(after) == len(before) + 6


@pytest.mark.parametrize("changes", [
    {"published": None}, {"deployment": None},
    {"deployment": "each layer is divided over some chips"},
    {"published": {"num_classes": 125, "stage_blocks": [3, 4, 6, 3]}},
    {"published": {"num_classes": 1000}},
    {"reduced": ["num_classes", "stage_blocks", "depth"],
     "published": {"num_classes": 1000, "stage_blocks": [3, 4, 6, 3], "depth": 50}},
    {"reduced": ["width"], "published": {"width": 64}, "width": 8},
    {"toy": None},
], ids=["no-published", "no-deployment", "deployment-without-a-count",
        "published-equals-run", "published-lacks-a-key", "reduced-key-not-in-file",
        "a-width-cut", "no-toy"])
def test_cut_stated_in_part_is_refused(tmp_path, changes):
    real = bench_tree.copy_files(tmp_path / "real")
    manifest, _ = _add_cut_configuration(real, **changes)
    with pytest.raises((AssertionError, KeyError)):
        held_to.config_file_states_what_is_run(manifest, real, "throwaway-cut")


def test_configuration_without_toy_sizes_is_not_rehearsed(tmp_path):
    real = bench_tree.copy_files(tmp_path / "real")
    _add_cut_configuration(real, toy=None)
    with pytest.raises(ValueError, match='throwaway-cut/config.json has no "toy"'):
        bench_tree.make_tree(tmp_path / "tree", source=real)
