"""The readers of the step loop (``step_dispatch_ms``,
``steps_in_flight_min``, ``step_gap_max_ms``, ``gc_pause_ms``,
``setup_steps_s``) over two rings built by hand: known spans in, known
values out, a window with a planted gap and a planted pause of the
collector; and nothing, without raising, from a program that keeps no
step ring (the parent of the PR that added it)."""

import types

import pytest

import bench_tree
from harness import registry, steploop
from horovod_tpu import tracing
from horovod_tpu.tracing.span import SpanBuffer

NAMES = ("step_dispatch_ms", "steps_in_flight_min", "step_gap_max_ms",
         "gc_pause_ms", "setup_steps_s")

# process start at 100.0, the first step at 110.0, the window of the only
# phase from 130.0 to 131.0, a traced stretch after it
STARTUP = [
    ("setup", "import", 102.0, 104.0, {}),
    ("setup", "init", 104.0, 108.0, {"parent": None}),
    # the step's trace, with an eager op compiled inside it, lowering, compile
    ("compile", "shard_step", 110.0, 112.0, {"stage": "trace"}),
    ("compile", "jit(add)", 111.0, 111.5, {"stage": "backend"}),
    ("compile", "jit(shard_step)", 112.0, 113.0, {"stage": "lower"}),
    ("compile", "jit(shard_step)", 113.0, 116.0, {"stage": "backend"}),
    # the check's own jit between the drives, and one inside the window
    ("compile", "jit(norms)", 118.0, 119.0, {"stage": "backend"}),
    ("compile", "jit(late)", 130.52, 130.58, {"stage": "backend"}),
]


def _step(n, t0, ms, in_flight, done=()):
    return ("step", "shard_step", t0, t0 + ms * 1e-3,
            {"n": n, "in_flight": in_flight, "done": list(done)})


LOOP = [
    # set-up: the first call holds the compile, then 22 more
    _step(0, 110.0, 6000.0, 0),
    *[_step(n, 116.0 + n * 0.5, 2.0, min(n, 5)) for n in range(1, 23)],
    ("gc", "gen2", 129.0, 129.4, {"collected": 90}),     # run.py's own
    # the window: nine calls, 100 ms apart but for a gap of 400 ms before
    # n=28, with a 30 ms collection and jit(late) inside it, and the last
    # two, which come early; the queue stands at 0 after the gap
    _step(23, 130.00, 2.0, 0), _step(24, 130.10, 2.0, 1),
    _step(25, 130.20, 2.0, 2), _step(26, 130.30, 3.0, 3),
    _step(27, 130.40, 3.0, 4),
    ("gc", "gen1", 130.45, 130.48, {"collected": 3}),
    _step(28, 130.80, 9.0, 0, done=(23, 24, 25, 26, 27)),
    _step(29, 130.90, 2.0, 1), _step(30, 131.00 - 0.08, 2.0, 2),
    _step(31, 131.00 - 0.05, 1.0, 3),
    # after the window: the traced stretch
    ("gc", "gen2", 131.5, 131.9, {"collected": 1}),
    _step(32, 132.0, 2.0, 0), _step(33, 132.1, 2.0, 1),
]


@pytest.fixture
def rings():
    tracing.steps()     # what the collector's watch still holds goes to the old ring
    old = (tracing.swap_buffer(SpanBuffer(capacity=64)),
           tracing.swap_steps(SpanBuffer(capacity=64)))
    try:
        for ring, spans in ((tracing.buffer(), STARTUP), (tracing.steps(), LOOP)):
            for cat, name, t0, t1, args in spans:
                ring.add(cat, name, t0, t1, round=-1, **args)
        yield tracing.buffer(), tracing.steps()
    finally:
        tracing.swap_buffer(old[0])
        tracing.swap_steps(old[1])


@pytest.fixture
def ctx():
    """What run.py hands a reader, as far as these readers look."""
    said = []
    main = types.SimpleNamespace(start=130.0, stamps=[130.6, 130.9, 131.0])
    return types.SimpleNamespace(phases={"main": main}, main=main,
                                 setup_s=30.0, say=said.append, said=said)


def _read(name, ctx):
    return registry.reader(str(bench_tree.BENCH), "layer_metrics", name)(ctx)


def test_window_is_the_calls_that_began_inside_the_main_stretch(rings, ctx):
    w = steploop.window(ctx)
    assert [s["args"]["n"] for s in w.steps] == list(range(23, 32))
    assert [s["name"] for s in w.gc] == ["gen1"]


def test_readers_give_the_planted_values(rings, ctx):
    got = {name: _read(name, ctx) for name in NAMES}
    assert got == pytest.approx({
        "step_dispatch_ms": 2.0,            # of 2 2 2 3 3 9 2 2 1
        "steps_in_flight_min": 1,           # n=29; the 0 of n=28 is the sixth's
        "step_gap_max_ms": 400.0,
        "gc_pause_ms": 30.0,
        # 110.0 to 130.0 less the trace (2.0, jit(add) inside it counted
        # once), the lowering, and the two compiles (3.0 and 1.0)
        "setup_steps_s": 20.0 - (2.0 + 1.0 + 3.0 + 1.0)})
    assert isinstance(got["steps_in_flight_min"], int)


def test_in_flight_min_reads_zero_where_the_queue_ran_dry(rings, ctx):
    ctx.main.stamps[-1] = 130.85        # the window ends after n=28
    assert [s["args"]["n"] for s in steploop.window(ctx).steps[6:]] == []
    assert _read("steps_in_flight_min", ctx) is None    # only the filling
    ctx.main.start = 126.4              # two calls of the warm-up join
    assert _read("steps_in_flight_min", ctx) == 0       # n=27 and n=28 count


def test_gap_reader_names_the_step_the_queue_the_collector_and_the_compile(
        rings, ctx):
    _read("step_gap_max_ms", ctx)
    (line,) = ctx.said
    # the call before the gap held the host 3 ms of it; the queue stood at
    # 0 after it and no lower over the six calls that followed
    assert line.startswith("step gaps, longest of 8: n=28 400.000 ms held "
                           "3.000 in_flight 0 then 0 gc 30.000 ms compiled "
                           "jit(late); ")
    rest = line.split("; ")[1:]
    assert len(rest) == 4
    assert all(" 100.000 ms held " in part
               and part.endswith("gc 0.000 ms compiled nothing")
               for part in rest)
    assert "n=27 100.000 ms held 3.000 in_flight 4 then 0 gc" in line


def test_gc_pause_counts_only_what_lies_inside_the_window(rings, ctx):
    rings[1].add("gc", "gen2", 129.9, 130.04, round=-1, collected=0)
    rings[1].add("gc", "gen2", 130.99, 131.3, round=-1, collected=0)
    assert _read("gc_pause_ms", ctx) == pytest.approx(30.0 + 40.0 + 10.0)


def test_setup_steps_counts_the_calls_and_the_compiling_on_an_earlier_line(
        rings, ctx):
    _read("setup_steps_s", ctx)
    assert ctx.said[-1] == ("steps before the window: 23 of shard_step in "
                            "20.000 s, of which 7.000 s tracing, lowering "
                            "and compiling")


def test_setup_steps_cuts_at_the_first_phases_window(rings, ctx):
    """Two phases (the four-chip cell: base, then main): the stretch ends
    where ``setup_s`` does, at the first window's start, and the main
    window's metrics read the last phase alone."""
    ctx.phases = {"base": types.SimpleNamespace(start=125.0), "main": ctx.main}
    assert _read("setup_steps_s", ctx) == pytest.approx(15.0 - 7.0)
    assert "18 of shard_step in 15.000 s" in ctx.said[-1]
    assert _read("step_gap_max_ms", ctx) == pytest.approx(400.0)


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_nothing_from_a_program_without_the_step_ring(
        name, rings, ctx, monkeypatch):
    monkeypatch.delattr(tracing, "steps")       # the parent of PR 51
    assert _read(name, ctx) is None
    assert ctx.said == []


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_nothing_where_no_step_was_recorded(name, ctx):
    old = (tracing.swap_buffer(SpanBuffer(capacity=8)),
           tracing.swap_steps(SpanBuffer(capacity=8)))     # HOROVOD_TRACE=0
    try:
        tracing.buffer().add("setup", "import", 102.0, 104.0, round=-1)
        assert _read(name, ctx) is None
    finally:
        tracing.swap_buffer(old[0])
        tracing.swap_steps(old[1])


def test_setup_steps_gives_nothing_once_the_first_step_is_gone(rings, ctx):
    for n in range(40):
        rings[1].add("step", "shard_step", 132.2, 132.3, round=-1, n=34 + n,
                     in_flight=5, done=[])
    assert rings[1].snapshot()["dropped"] > 0
    assert _read("setup_steps_s", ctx) is None
    assert _read("step_gap_max_ms", ctx) == pytest.approx(400.0)


@pytest.mark.parametrize("name", NAMES)
def test_reader_file_agrees_with_the_manifests_entry(name):
    entry = next(m for m in bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
                 ["per_layer"] if m["name"] == name)
    module = registry.load_module(
        str(bench_tree.BENCH / "layer_metrics" / f"{name}.py"))
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (
        module.UNIT, module.LAYER, module.MOVES, module.SOURCE)
    assert "workloads" not in entry and entry["source"] == "program_span"
