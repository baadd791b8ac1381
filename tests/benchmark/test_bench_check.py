"""The command's refusals, the timed path broken underneath, BERT on four
devices (the known fault), and the control in lower precision."""


import json
import os
import subprocess
import sys

import pytest

import bench_tree
from harness import check, registry

MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
CELLS = [(w["name"], w["chips"]) for w in MANIFEST["workloads"]]
# float32 at a toy size agrees far inside the limits that bf16 is held to
TIGHT = 2e-3


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_tree.make_tree(tmp_path_factory.mktemp("bench"))


def test_command_refuses_to_run_without_a_tpu():
    done = subprocess.run(
        [sys.executable, str(bench_tree.BENCH / "run.py"), "--workload",
         CELLS[0][0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "{" not in done.stdout, "no result line, no metric"
    assert "no TPU" in done.stderr


def test_broken_step_comes_out_not_correct(tree, tmp_path):
    """The rest of a run as it is, the step returning its state unchanged."""
    broken = bench_tree.make_tree(tmp_path)
    cell = next(n for n, _ in CELLS if n.startswith("bert"))
    adapter = broken / "benchmark" / "configs" / "bert-base-ft" / "adapter.py"
    adapter.write_text(adapter.read_text() + (
        "\n_step = Program.step\n"
        "Program.step = lambda self, state, batch: "
        "(state, _step(self, state, batch)[1])\n"))
    result, _ = bench_tree.run_cell(broken, cell, 1)
    assert result["correct"] is False
    assert result["compared"]["main.update_norm_gap"]["value"] > \
        result["compared"]["main.update_norm_gap"]["limit"]


@pytest.mark.xfail(strict=False, reason=(
    "ISSUE 23, Motivation 1: bert.make_dp_finetune_step reduces the gradient "
    "twice on more than one chip (autodiff's psum under check_vma, then "
    "hvd.DistributedOptimizer) and never divides by the workers, so the "
    "applied update is N x the mean gradient"))
def test_bert_on_four_devices_matches_the_whole_batch_reference(tree, tmp_path):
    four = bench_tree.make_tree(tmp_path)
    cell = next(n for n, _ in CELLS if n.startswith("bert"))
    path = four / "benchmark" / "workloads" / f"{cell}.json"
    path.write_text(json.dumps(dict(bench_tree.load(path), chips=4)))
    manifest = bench_tree.load(four / "BENCHMARK.json")
    next(w for w in manifest["workloads"] if w["name"] == cell)["chips"] = 4
    (four / "BENCHMARK.json").write_text(json.dumps(manifest))
    result, _ = bench_tree.run_cell(four, cell, 4)
    assert result["compared"]["main.grad_norm_gap"]["value"] < TIGHT
    assert result["correct"] is True


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_control_in_fp8_fails_the_limits_and_float32_passes(tree, config):
    """The reference in the program's place, computed one precision below
    bf16, must come out not correct; computed as itself it is exact."""
    import jax
    cfg = bench_tree.load(tree / "benchmark" / "configs" / config / "config.json")
    cfg["num_classes"] = cfg.get("num_classes", 0) and 100
    reference = registry.load_module(
        str(bench_tree.BENCH / "configs" / config / "reference.py"))
    key = jax.random.key(3)
    batches = [reference.make_samples(cfg, jax.random.fold_in(key, k), 16)
               for k in range(check.STEPS)]
    plain = check.Reference(reference, cfg)
    ref = plain.run(key, batches)
    again = check.compare(plain.run(key, batches), ref)
    assert check.within(again, reference.LIMITS)
    assert max(v for v, _ in again.values()) < 1e-5
    control = check.compare(check.Reference(
        reference, cfg, quant=check.quant_fp8).run(key, batches), ref)
    assert not check.within(control, reference.LIMITS), control


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_reference_keeps_weights_and_optimizer_state_and_no_copy(tree, config):
    """16 bytes a parameter with the gradient, under AdamW: the first
    weights are made again from the key for the difference at the end."""
    import jax
    cfg = bench_tree.load(tree / "benchmark" / "configs" / config / "config.json")
    reference = registry.load_module(
        str(bench_tree.BENCH / "configs" / config / "reference.py"))
    kept = jax.eval_shape(check.Reference(reference, cfg)._start, jax.random.key(3))

    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(t))

    assert nbytes(kept) <= 3 * nbytes(kept[0])      # the gradient is the fourth
