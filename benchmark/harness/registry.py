"""Finds a cell's files by the names in BENCHMARK.json: a workload is
``workloads/<name>.json``, its configuration the directory
``configs/<name>/`` (config.json, adapter.py, reference.py, flops.py), its
traffic ``traffic/<name>.json`` (which may split the window into ``phases``
on fewer chips than the cell's; a workload file may give, per phase,
``limits`` that tighten the configuration's own for this batch), and a
metric the reader
``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``.  Nothing here
names a model, a cell or a metric, so a later PR adds files, not edits.
"""

import importlib.util
import json
import os
import types


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """A python file by path (its name may hold dots and hyphens)."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(bench_dir, manifest, workload):
    """Everything one cell names, read and checked against the manifest."""
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise LookupError(f"BENCHMARK.json has no workload {workload!r}")
    cell = load_json(os.path.join(bench_dir, "workloads", workload + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise ValueError(f"workloads/{workload}.json and BENCHMARK.json "
                             f"disagree on {key}")
    cfg_dir = os.path.join(bench_dir, "configs", cell["config"])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    phases = traffic.get("phases") or [{"name": "main", "chips": cell["chips"],
                                        "share": 1.0}]
    return types.SimpleNamespace(
        name=workload, chips=cell["chips"], phases=phases,
        limits=cell.get("limits", {}),
        config=load_json(os.path.join(cfg_dir, "config.json")),
        traffic=traffic,
        traffic_name=cell["traffic"],
        adapter_path=os.path.join(cfg_dir, "adapter.py"),
        reference=load_module(os.path.join(cfg_dir, "reference.py")),
        flops=load_module(os.path.join(cfg_dir, "flops.py")))


def metrics_for(manifest, section, workload):
    """The manifest's metrics of one section that this cell reports."""
    return [m for m in manifest[section]
            if workload in m.get("workloads", [workload])]


def reader(bench_dir, section, name):
    return load_module(os.path.join(bench_dir, section, name + ".py")).read
