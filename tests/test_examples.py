"""Examples smoke suite (reference: the examples/ checklist is the
capability surface users copy from — SURVEY.md §2.3).

Each script runs as a real subprocess the way a user would launch it
(CPU-forced, single process; the multi-process variants are covered by
the hvdrun tests).  Only the fast examples run here — the model
benchmarks (llama_benchmark, resnet50_synthetic_benchmark, ...) have
their own bench/test coverage and take minutes on CPU.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAST_EXAMPLES = [
    "collectives_tour.py",
    "process_sets.py",
    "adasum_mnist.py",
    "tf_jit_training.py",
]


@pytest.mark.parametrize("script", FAST_EXAMPLES)
def test_example_runs_clean(script):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "HOROVOD_CYCLE_TIME": "0.2",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd="/tmp")
    assert proc.returncode == 0, (
        f"{script} failed rc={proc.returncode}\n"
        f"stdout tail: {proc.stdout[-800:]}\n"
        f"stderr tail: {proc.stderr[-800:]}")
