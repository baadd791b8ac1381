"""BENCHMARK.json against the files it names, and the counts the
utilisation metrics rest on."""

import re
from pathlib import Path

import pytest

import bench_tree
from harness import hlo, peaks, registry

MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_name_is_a_contract_name():
    names = [m["name"] for sec in ("configs", "workloads", "end_to_end", "per_layer")
             for m in MANIFEST[sec]]
    names += [w[k] for w in MANIFEST["workloads"] for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}) \
        == len(MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"])


# What a cell, a configuration and a per-layer metric have to satisfy, as
# functions of a manifest and the root that holds its files: the tests below
# hold the repo's own to them, and test_bench_add_cell.py a copy to which a
# later PR's kind of files and entries have been added.

def workload_file_agrees_with_manifest(manifest, root, cell):
    bench = Path(root) / "benchmark"
    loaded = registry.load_cell(str(bench), manifest, cell)
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    assert loaded.chips == entry["chips"] in (1, 4)
    assert len(entry["why"]) <= 200
    assert bench_tree.load(bench / "workloads" / f"{cell}.json")["why"] \
        == entry["why"]
    assert sum(p["chips"] == entry["chips"] for p in loaded.phases) == 1
    assert abs(sum(p["share"] for p in loaded.phases) - 1) < 1e-9
    # a cell reports setup_s, another end-to-end metric and a per-layer one
    e2e = [m["name"] for m in registry.metrics_for(manifest, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert registry.metrics_for(manifest, "per_layer", cell)


# what the contract never lets ``reduced`` name: a width
_WIDTH = re.compile(r"_dim$|_rank$|^width$|expansion|per_tok$|"
                    r"(hidden|intermediate|latent|state|proj|head)\w*_size$")


def config_file_states_what_is_run(manifest, root, config):
    """``config.json`` is the configuration as run.  A cut is stated whole:
    each key of ``reduced`` is a key of the file, ``published`` holds the
    source's value for it, and ``deployment`` says in one line over how
    many chips a layer is divided and how.  ``toy`` is what a rehearsal on
    the CPU lays over the file (bench_tree.make_tree)."""
    entry = next(c for c in manifest["configs"] if c["name"] == config)
    cfg = bench_tree.load(Path(root) / entry["file"])
    assert cfg["name"] == config and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["dtype"]["compute"] == "bfloat16" and cfg["dtype"]["params"] == "float32"
    assert any(w["config"] == config for w in manifest["workloads"])
    assert cfg["toy"] and set(cfg["toy"]) <= set(cfg), "toy sizes lie over keys of the file"
    if cfg["reduced"]:
        assert set(cfg["published"]) == set(cfg["reduced"]) <= set(cfg)
        for key in cfg["reduced"]:
            assert not _WIDTH.search(key), f"{key}: a width is never cut"
            assert cfg["published"][key] != cfg[key], f"{key} is run as published"
        assert _one_line(cfg["deployment"]) and re.search(r"\d", cfg["deployment"]), \
            "over how many chips a layer is divided, and how"


def layer_metric_file_agrees_with_manifest(manifest, root, metric):
    entry = next(m for m in manifest["per_layer"] if m["name"] == metric)
    mod = registry.load_module(
        str(Path(root) / "benchmark" / "layer_metrics" / f"{metric}.py"))
    assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry["moves"] in {m["name"] for m in manifest["end_to_end"]}
    # every cell that reads it reports the end-to-end metric it moves
    moved = next(m for m in manifest["end_to_end"] if m["name"] == entry["moves"])
    cells = entry.get("workloads") or [w["name"] for w in manifest["workloads"]]
    assert set(cells) <= set(moved.get("workloads") or
                             [w["name"] for w in manifest["workloads"]])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_workload_file_agrees_with_manifest(cell):
    workload_file_agrees_with_manifest(MANIFEST, bench_tree.REPO, cell)


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_file_states_what_is_run(config):
    config_file_states_what_is_run(MANIFEST, bench_tree.REPO, config)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_layer_metric_file_agrees_with_manifest(metric):
    layer_metric_file_agrees_with_manifest(MANIFEST, bench_tree.REPO, metric)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["end_to_end"]])
def test_end_to_end_metric_has_a_reader_and_a_bound(metric):
    entry = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric)
    assert callable(registry.reader(str(bench_tree.BENCH), "end_to_end", metric))
    assert 0.01 <= entry["bound"] <= 0.1
    assert entry["source"] in ("host_clock", "device_trace")


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


def _flops(config):
    cfg = bench_tree.load(bench_tree.BENCH / "configs" / config / "config.json")
    return cfg, registry.load_module(
        str(bench_tree.BENCH / "configs" / config / "flops.py"))


def test_resnet50_flops_from_shapes():
    cfg, flops = _flops("resnet50-synth")
    # the published 4.1 G is multiply-adds of the forward pass; two FLOPs each
    assert 4.08e9 <= flops.forward_macs(cfg) <= 4.14e9
    convs, c_last = flops.conv_shapes(cfg)
    assert len(convs) == 53 and c_last == 2048
    assert 5.9 * flops.forward_macs(cfg) < flops.train_flops_per_sample(cfg) \
        < 6 * flops.forward_macs(cfg)


def test_bert_base_flops_from_shapes():
    cfg, flops = _flops("bert-base-ft")
    assert 84e6 <= flops.matmul_params(cfg) <= 86e6      # not the 110 M with embeddings
    per_token = flops.train_flops_per_sample(cfg) / cfg["seq_len"]
    assert 6 * 85e6 < per_token < 6 * 85e6 * 1.06         # attention adds under 6% at T=128
    f, b = flops.flash_kernel_cost(cfg, 64)
    assert f == 2 * 7 * 128 * 128 * 768 * 12 * 64 and b > 0


def test_peaks_table_knows_the_v5e_and_nothing_else():
    row = peaks.for_kind("TPU v5 lite")
    assert (row["bf16_flops_per_s"], row["hbm_bytes_per_s"], row["hbm_bytes"]) \
        == (197e12, 819e9, 16e9)
    with pytest.raises(LookupError):
        peaks.for_kind("TPU v9 imaginary")
    with pytest.raises(LookupError):
        peaks.for_kind("cpu")


def test_all_reduce_bytes_from_hlo_text():
    text = """
  %all-reduce.1 = f32[92100]{0} all-reduce(f32[92100]{0} %x), replica_groups={{0,1,2,3}}
  %ar = (f32[2,64]{1,0}, bf16[8]{0}) all-reduce-start((f32[2,64]{1,0}, bf16[8]{0}) %t)
  %done = (f32[2,64]{1,0}, bf16[8]{0}) all-reduce-done(%ar)
  %fusion.3 = f32[10]{0} fusion(f32[10]{0} %all-reduce.1)
"""
    assert [b for b, _ in hlo.all_reduces(text)] == [92100 * 4, 2 * 64 * 4 + 8 * 2]


_METRIC_KEYS = {"name", "unit", "better", "source"}
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_manifest_keeps_the_contracts_form():
    """The limits the driver holds BENCHMARK.json to before any run."""
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert (bench_tree.REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(m["paths"]) <= 16 and all(
        _PATH.match(p) and not p.startswith("/") and ".." not in p for p in m["paths"])
    assert len(m["command"]) <= 32 and all(_one_line(w) for w in m["command"])
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check with the full 24 cells fits into its 43200 seconds
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(m["configs"]) <= 24
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _one_line(c["why"]) and _one_line(c["source"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
    assert 1 <= len(m["workloads"]) <= 24
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in {c["name"] for c in m["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs), "a pair of config and traffic appears once"
    for section in ("configs", "workloads"):
        names = [x["name"] for x in m[section]]
        assert len(set(names)) == len(names)
    cells = {w["name"] for w in m["workloads"]}
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == _METRIC_KEYS | {"bound"}
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == _METRIC_KEYS | {"layer", "moves"}
        assert _one_line(p["layer"])
    for x in m["end_to_end"] + m["per_layer"]:
        assert _UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert set(x.get("workloads", [])) <= cells


def test_files_under_paths_are_named_from_a_names_characters():
    import subprocess
    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard", "--",
         *MANIFEST["paths"]], cwd=bench_tree.REPO, capture_output=True, text=True)
    if listed.returncode:
        pytest.skip("not a git checkout")
    assert all(_PATH.match(f) for f in listed.stdout.split())
