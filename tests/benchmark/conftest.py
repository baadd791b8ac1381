"""For the benchmark's tests.

``test_bench_granite.py`` (PR 40) asserts that its configuration's entries
are the last of the manifest's lists.  They were when it was written; a
configuration appended since comes after them, and the file, being the
accepted benchmark's, is not edited by the PR that appends one.  Its
manifest test therefore reads ``BENCHMARK.json`` as far as its own
entries: everything up to them must still stand as that PR left it.  (A
new configuration's test asserts where its entries lie relative to the
accepted ones, not that they are last: test_bench_solar.py.)
"""

import copy

import pytest

_ASSERTS_LAST = {"test_bench_granite": ("granite-4.0-h-micro",
                                        "ssd_xla_call_sites")}


def _as_far_as(manifest, config, last_metric):
    """The manifest cut after ``config``'s entries: its configuration, its
    last cell, ``last_metric``, and no later cell in any metric's list."""
    m = copy.deepcopy(manifest)
    names = [c["name"] for c in m["configs"]]
    m["configs"] = m["configs"][:names.index(config) + 1]
    last_cell = max(i for i, w in enumerate(m["workloads"])
                    if w["config"] == config)
    later = {w["name"] for w in m["workloads"][last_cell + 1:]}
    m["workloads"] = m["workloads"][:last_cell + 1]
    metrics = [x["name"] for x in m["per_layer"]]
    m["per_layer"] = m["per_layer"][:metrics.index(last_metric) + 1]
    for section in ("per_layer", "end_to_end"):
        for x in m[section]:
            if "workloads" in x:
                x["workloads"] = [w for w in x["workloads"] if w not in later]
    return m


@pytest.fixture(autouse=True)
def _manifest_as_far_as_the_modules_own_entries(request, monkeypatch):
    cut = _ASSERTS_LAST.get(request.module.__name__)
    if cut and request.function.__name__.startswith("test_manifest_names"):
        monkeypatch.setattr(request.module, "MANIFEST",
                            _as_far_as(request.module.MANIFEST, *cut))
