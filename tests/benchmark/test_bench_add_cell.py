"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files, and an entry each in BENCHMARK.json; the harness runs
them with no edit to a file that was there."""

import hashlib
import json
import shutil

import bench_tree


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_config_traffic_and_metric_run_as_added_files(tmp_path):
    tree = bench_tree.make_tree(tmp_path)
    bench = tree / "benchmark"
    before = _digest(bench)

    shutil.copytree(bench / "configs" / "resnet50-synth", bench / "configs" / "throwaway-net")
    cfg = bench_tree.load(bench / "configs" / "throwaway-net" / "config.json")
    cfg.update(name="throwaway-net", num_classes=7)
    (bench / "configs" / "throwaway-net" / "config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps(
        {"per_chip_batch": 4, "resident": "host", "pool_batches": 3,
         "warmup_steps": 2, "loss_must_fall": False}))
    (bench / "workloads" / "throwaway-net.mix.dp1.json").write_text(json.dumps(
        {"config": "throwaway-net", "traffic": "throwaway-mix", "chips": 1,
         "why": "a throw-away cell"}))
    (bench / "layer_metrics" / "steps_counted.py").write_text(
        'UNIT, LAYER, MOVES, SOURCE = "count", "Runtime", "throughput", "program_counter"\n'
        "def read(ctx):\n    return len(ctx.main.stamps)\n")

    manifest = bench_tree.load(tree / "BENCHMARK.json")
    manifest["configs"].append(
        {"name": "throwaway-net", "source": "none", "reduced": [], "why": "test",
         "file": "benchmark/configs/throwaway-net/config.json"})
    manifest["workloads"].append(
        {"name": "throwaway-net.mix.dp1", "config": "throwaway-net",
         "traffic": "throwaway-mix", "chips": 1, "why": "a throw-away cell"})
    manifest["per_layer"].append(
        {"name": "steps_counted", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "Runtime", "moves": "throughput",
         "workloads": ["throwaway-net.mix.dp1"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest))

    result, _ = bench_tree.run_cell(tree, "throwaway-net.mix.dp1", 1, trace=1)
    assert result["correct"] is True
    assert result["metrics"]["steps_counted"]["value"] == result["attempted"] > 0
    assert "feed_ms" not in result["metrics"]      # another cell's metric stays out
    after = _digest(bench)
    assert {k: after[k] for k in before} == before, "no file that was there changed"
    assert len(after) == len(before) + 7
