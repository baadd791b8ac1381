"""Helpers for the benchmark's tests: a copy of the benchmark's data files
cut to toy sizes, and one run of a cell through ``run.run_cell`` in a
process of its own (so that it has exactly the cell's number of virtual
CPU devices and an ``hvd.init()`` of its own)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
DATA_DIRS = ("configs", "traffic", "workloads", "layer_metrics", "end_to_end")
TINY = {
    "resnet50-synth": {"block": "basic", "stage_blocks": [2, 2, 2, 2],
                       "width": 8, "num_classes": 10, "image_size": 32},
    "bert-base-ft": {"vocab_size": 256, "hidden_size": 64,
                     "num_hidden_layers": 2, "num_attention_heads": 4,
                     "head_dim": 16, "intermediate_size": 128,
                     "max_position_embeddings": 64, "seq_len": 32},
}

if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def load(path):
    return json.loads(Path(path).read_text())


def make_tree(tmp):
    """``tmp/BENCHMARK.json`` and ``tmp/benchmark/<data dirs>``: the real
    files, every configuration at a toy size in float32, every batch 8."""
    tmp = Path(tmp)
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for d in DATA_DIRS:
        shutil.copytree(BENCH / d, tmp / "benchmark" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for name, sizes in TINY.items():
        path = tmp / "benchmark" / "configs" / name / "config.json"
        cfg = load(path)
        cfg.update(sizes)
        cfg["dtype"]["compute"] = "float32"
        path.write_text(json.dumps(cfg))
    for path in (tmp / "benchmark" / "traffic").glob("*.json"):
        traffic = load(path)
        traffic.update(per_chip_batch=8, warmup_steps=2,
                       pool_batches=min(traffic["pool_batches"], 4))
        path.write_text(json.dumps(traffic))
    return tmp


def run_cell(tree, workload, chips, trace=0, seed=2 ** 31 + 7, seconds=1.0):
    """One rehearsal in a child; returns (result object, all of stdout)."""
    code = (
        "import json, sys\n"
        f"sys.path[:0] = [{str(BENCH)!r}, {str(REPO)!r}]\n"
        "import run\n"
        f"r = run.run_cell({str(Path(tree) / 'benchmark')!r}, "
        f"{str(Path(tree) / 'BENCHMARK.json')!r}, {workload!r}, {seed}, "
        f"{seconds}, {bool(trace)}, require_chip=False)\n"
        "print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=tree,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout
