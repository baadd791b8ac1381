"""The readers of start-up (``import_s``, ``init_s``, ``lower_s``,
``compile_s``, ``cache_misses``, ``setup_other_s``): self time over a
hand-made list of spans, and nothing, without raising, from a program
that leaves no such span or counter (the parent of the PR that added
them)."""

import types

import pytest

import bench_tree
from harness import registry, startup
from horovod_tpu import metrics, tracing
from horovod_tpu.tracing.span import SpanBuffer

TIMES = ("import_s", "init_s", "lower_s", "compile_s", "setup_other_s")


def _span(cat, name, t0, t1, **args):
    return {"cat": cat, "name": name, "t0": t0, "t1": t1, "args": args}


# process start at 100.0, the window at 130.0
SPANS = [
    _span("setup", "import", 102.0, 104.0),
    # a compile inside hvd.init() is a compile, not init
    _span("setup", "init", 104.0, 114.0, parent=None),
    _span("setup", "init.backend", 105.0, 113.0, parent=2),
    _span("compile", "convert_element_type", 106.0, 106.5, stage="trace"),
    _span("compile", "jit(convert_element_type)", 106.5, 107.0, stage="lower"),
    _span("compile", "jit(convert_element_type)", 107.0, 108.0, stage="backend"),
    # the step: traced, with an eager op compiled inside the trace, then
    # lowered and loaded; a second trace of the same stretch counts once
    _span("compile", "step", 116.0, 120.0, stage="trace"),
    _span("compile", "step", 117.0, 119.0, stage="trace"),
    _span("compile", "jit(add)", 118.0, 118.5, stage="backend"),
    _span("compile", "jit(step)", 120.0, 121.0, stage="lower"),
    _span("compile", "jit(step)", 121.0, 121.5, stage="backend"),
    # a compile that runs into the window is cut there; one after it is out
    _span("compile", "jit(late)", 129.0, 131.0, stage="backend"),
    _span("compile", "jit(reference)", 140.0, 150.0, stage="backend"),
    _span("cycle", "cycle3", 110.0, 111.0),
]


def test_self_time_counts_every_second_once_and_sums_to_the_whole():
    got = startup.self_seconds(SPANS, 100.0, 130.0)
    assert got == pytest.approx({
        "import": 2.0,
        "init": 10.0 - 0.5 - 0.5 - 1.0,
        "lower": (0.5 + 0.5) + (4.0 - 0.5) + 1.0,
        "compile": 1.0 + 0.5 + 0.5 + 1.0})
    covered = 2.0 + 10.0 + (4.0 + 1.0 + 0.5) + 1.0       # union of all of them
    assert sum(got.values()) == pytest.approx(covered)
    assert sum(got.values()) <= 30.0


def test_self_time_cuts_spans_at_process_start_and_at_the_window():
    spans = [_span("setup", "import", 90.0, 101.0),
             _span("setup", "init", 129.5, 135.0)]
    assert startup.self_seconds(spans, 100.0, 130.0) == pytest.approx(
        {"import": 1.0, "init": 0.5, "lower": 0.0, "compile": 0.0})


@pytest.fixture
def ctx():
    """What run.py hands a reader, as far as these readers look."""
    said = []
    return types.SimpleNamespace(
        phases={"base": types.SimpleNamespace(start=130.0),
                "main": types.SimpleNamespace(start=137.0)},
        setup_s=30.0, say=said.append, said=said)


@pytest.fixture
def buffer():
    old = tracing.swap_buffer(SpanBuffer(capacity=32))
    try:
        yield tracing.buffer()
    finally:
        tracing.swap_buffer(old)


def _read(name, ctx):
    return registry.reader(str(bench_tree.BENCH), "layer_metrics", name)(ctx)


def test_readers_split_setup_s_with_nothing_left_over_or_counted_twice(ctx, buffer):
    for s in SPANS:
        buffer.add(s["cat"], s["name"], s["t0"], s["t1"], **s["args"])
    got = {name: _read(name, ctx) for name in TIMES}
    assert got == pytest.approx({"import_s": 2.0, "init_s": 8.0, "lower_s": 5.5,
                                 "compile_s": 3.0, "setup_other_s": 11.5})
    assert sum(got.values()) == pytest.approx(ctx.setup_s)
    said = "\n".join(ctx.said)
    assert "init.backend 8.000" in said           # init's parts, by name
    assert "step 7.000" in said    # both traces and the lowering, by span
    assert "dropped 0" in said


@pytest.mark.parametrize("name", TIMES)
def test_time_reader_gives_nothing_without_the_programs_spans(name, ctx, buffer):
    buffer.add("cycle", "cycle3", 110.0, 111.0)   # the eager engine's only
    assert _read(name, ctx) is None


@pytest.mark.parametrize("name", TIMES)
def test_time_reader_gives_nothing_once_the_ring_dropped_spans(name, ctx, buffer):
    for _ in range(40):
        buffer.add("setup", "import", 102.0, 104.0)
    assert buffer.snapshot()["dropped"] > 0
    assert _read(name, ctx) is None


def test_cache_misses_reads_the_programs_counter(ctx, monkeypatch):
    fresh = metrics.MetricRegistry()
    monkeypatch.setattr(metrics, "_REGISTRY", fresh)
    assert _read("cache_misses", ctx) is None     # no such family: the parent
    family = fresh.counter("hvd_compile_cache_total", "", labels=("result",))
    assert _read("cache_misses", ctx) == 0        # a family, nothing looked up
    family.inc(result="hit")
    family.inc(3, result="miss")
    assert _read("cache_misses", ctx) == 3
    assert "miss 3" in ctx.said[-1] and "hit 1" in ctx.said[-1]
