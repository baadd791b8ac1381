"""Helpers for the benchmark's tests: a copy of the benchmark's data files
cut to toy sizes, and one run of a cell through ``run.run_cell`` in a
process of its own (so that it has exactly the cell's number of virtual
CPU devices and an ``hvd.init()`` of its own)."""

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
DATA_DIRS = ("configs", "traffic", "workloads", "layer_metrics", "end_to_end")

if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def load(path):
    return json.loads(Path(path).read_text())


def plane(name, **lines):
    """A made-up plane of a profile as harness/xplane reads one: each line
    a list of (start ns, duration ns, event name)."""
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=[
            types.SimpleNamespace(start_ns=s, duration_ns=d, name=e)
            for s, d, e in events])
        for n, events in lines.items()])


def copy_files(dest, source=REPO):
    """``dest/BENCHMARK.json`` and ``dest/benchmark/<data dirs>`` as they
    stand under ``source``."""
    dest, source = Path(dest), Path(source)
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(source / "BENCHMARK.json", dest / "BENCHMARK.json")
    for d in DATA_DIRS:
        shutil.copytree(source / "benchmark" / d, dest / "benchmark" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return dest


def make_tree(tmp, source=REPO):
    """A copy of ``source``'s files for a rehearsal: every configuration
    found there cut to the ``toy`` sizes of its own ``config.json``, in
    float32, every batch 8.  A configuration that states no ``toy`` is
    refused: at its published sizes a rehearsal on the CPU would not end."""
    tmp = copy_files(tmp, source)
    for path in sorted((tmp / "benchmark" / "configs").glob("*/config.json")):
        cfg = load(path)
        if not cfg.get("toy"):
            raise ValueError(
                f'configs/{path.parent.name}/config.json has no "toy": the '
                "sizes a CPU rehearsal lays over the configuration; without "
                "them it would be rehearsed at full size")
        cfg.update(cfg["toy"])
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
