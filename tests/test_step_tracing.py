"""The step loop in the program's own record: ``tracing.TracedStep`` around
each builder's jitted step, the collector's pauses beside it, both in a
ring apart from the one start-up's spans live in
(docs/observability.md "Start-up and the jitted step")."""

import functools
import gc
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu import metrics, tracing, training
from horovod_tpu.models import bert, llama
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
from horovod_tpu.tracing.span import SpanBuffer


@pytest.fixture
def rings():
    """Both rings the test's own, tracing on; yields (start-up's, the
    step loop's)."""
    tracing.steps()     # what the collector's watch still holds goes to the old ring
    old = (tracing.swap_buffer(SpanBuffer(capacity=64)),
           tracing.swap_steps(SpanBuffer(capacity=64)))
    was = tracing.ACTIVE
    tracing.enable()
    try:
        yield tracing.buffer(), tracing.steps()
    finally:
        tracing.ACTIVE = was
        tracing.swap_buffer(old[0])
        tracing.swap_steps(old[1])


def _spans(ring, cat="step"):
    return [s for s in ring.snapshot()["spans"] if s["cat"] == cat]


@jax.jit
def trivial(x):
    return x, x + 1.0, x.sum()


# ---- the five builders' steps ------------------------------------------------

_LLAMA = llama.LlamaConfig(vocab_size=32, d_model=16, n_layers=1, n_heads=2,
                           n_kv_heads=1, d_ff=32, max_seq_len=8,
                           dtype=jnp.float32)
_BERT = bert.BertConfig(vocab_size=32, d_model=16, n_layers=1, n_heads=2,
                        d_ff=32, max_seq_len=8, num_labels=3,
                        dtype=jnp.float32)


def _pmesh(dp):
    return ParallelMesh(MeshConfig(dp=dp), devices=jax.devices()[:dp])


def _filled(shapes):
    """Arrays of the given shapes, the same ones every time."""
    rng = np.random.RandomState(0)
    return jax.tree_util.tree_map(
        lambda s: (rng.standard_normal(s.shape) * 0.1).astype(s.dtype)
        if jnp.issubdtype(s.dtype, jnp.floating)
        else np.zeros(s.shape, s.dtype), shapes)


def _llama_step(build, dp, **kw):
    ts = build(_LLAMA, _pmesh(dp), optax.sgd(0.1), **kw)
    state = jax.eval_shape(ts.init_fn, jax.random.PRNGKey(0))
    tokens = np.arange(2 * dp * 8, dtype=np.int32).reshape(2 * dp, 8) % 32
    return ts.step_fn, 2, lambda: (*_filled(state), tokens, tokens)


def _classifier_step():
    def forward(params, state, images, train, axis_name):
        return images.reshape(images.shape[0], -1) @ params["w"], state

    def init(rng):
        return {"w": jax.random.normal(rng, (12, 3)) * 0.1}, {}

    ts = training.make_classifier_train_step(forward, init, _pmesh(1))
    state = jax.eval_shape(ts.init_fn, jax.random.PRNGKey(0))
    images = np.linspace(-1, 1, 48, dtype=np.float32).reshape(4, 2, 2, 3)
    labels = np.array([0, 1, 2, 0], np.int32)
    return ts.step_fn, 3, lambda: (*_filled(state), images, labels)


def _bert_step():
    opt = optax.sgd(0.1)
    step = bert.make_dp_finetune_step(
        _BERT, Mesh(np.array(jax.devices()[:1]), ("dp",)), "dp", opt,
        reduce_grads=True)
    params = jax.eval_shape(lambda k: bert.init_params(_BERT, k),
                            jax.random.PRNGKey(0))
    state = (params, jax.eval_shape(opt.init, params))
    tokens = np.arange(16, dtype=np.int32).reshape(2, 8)
    return step, 2, lambda: (*_filled(state), tokens, np.array([0, 2], np.int32))


_BUILDERS = {
    "llama": lambda: _llama_step(training.make_llama_train_step, 1),
    "llama-overlap": lambda: _llama_step(training.make_llama_train_step, 2,
                                         overlap=True),
    "fsdp": lambda: _llama_step(training.make_llama_fsdp_step, 2),
    "fsdp-overlap": lambda: _llama_step(training.make_llama_fsdp_step, 2,
                                        overlap=True),
    "classifier": _classifier_step,
    "bert": _bert_step,
}


@functools.lru_cache(maxsize=None)
def _built(name):
    return _BUILDERS[name]()


@pytest.mark.parametrize("name", sorted(_BUILDERS))
def test_builders_step_is_the_jitted_function_behind_one_record(name, rings):
    step, loss_index, args = _built(name)
    assert isinstance(step, tracing.TracedStep)
    jitted = step._jitted
    assert step.lower == jitted.lower and step.__name__ == jitted.__name__
    want = jitted(*args())          # the state is donated: fresh arrays a call
    assert _spans(rings[1]) == []
    got = step(*args())
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and np.array_equal(np.asarray(g), np.asarray(w))
    (span,) = _spans(rings[1])
    assert span["name"] == jitted.__name__ and span["round"] == -1
    assert span["args"] == {"n": 0, "in_flight": 0, "done": []}
    # the output the record watches is the loss: a scalar no call donates
    assert got[loss_index].shape == () and not got[loss_index].is_deleted()
    assert _spans(rings[0]) == []       # nothing of the loop in start-up's ring


# ---- what a call records -----------------------------------------------------

def test_one_span_a_call_with_n_rising_and_every_call_done_once(rings):
    step = tracing.TracedStep(trivial, 2)
    x = jnp.ones((4,))
    for _ in range(12):
        out = step(x)
    jax.block_until_ready(out)
    step(x)
    spans = _spans(rings[1])
    assert [s["args"]["n"] for s in spans] == list(range(13))
    assert all(s["name"] == "trivial" and s["t0"] <= s["t1"] for s in spans)
    assert all(a["t1"] <= b["t0"] for a, b in zip(spans, spans[1:]))
    assert all(0 <= s["args"]["in_flight"] <= 8 for s in spans)
    done = [n for s in spans for n in s["args"]["done"]]
    assert done == list(range(12))      # each once, in order, none its own call's
    assert all(n < s["args"]["n"] for s in spans for n in s["args"]["done"])


class _Loss:
    """An output whose readiness the test decides."""

    def __init__(self, log):
        self.ready, self.log = False, log

    def is_deleted(self):
        return False

    def is_ready(self):
        self.log.append(self)
        return self.ready


def test_in_flight_counts_the_calls_not_yet_ready_and_looks_at_eight(rings):
    asked, made = [], []

    def fake(x):
        made.append(_Loss(asked))
        return None, None, made[-1]

    step = tracing.TracedStep(fake, 2)
    for _ in range(3):
        step(0)
    made[0].ready = made[1].ready = True
    del asked[:]
    step(0)
    # results become ready in order: the first that is not ends the look
    assert asked == [made[0], made[1], made[2]]
    for _ in range(10):
        step(0)
    spans = _spans(rings[1])
    assert spans[3]["args"] == {"n": 3, "in_flight": 1, "done": [0, 1]}
    assert [s["args"]["in_flight"] for s in spans] == [
        0, 1, 2, 1, 2, 3, 4, 5, 6, 7, 8, 8, 8, 8]
    assert spans[0]["name"] == "fake"


def test_step_holds_a_trace_annotation_named_hvd_step(rings, monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            opened.append("in")

        def __exit__(self, *exc):
            opened.append("out")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tracing.TracedStep(lambda: (opened.append("call"), 0, 0), 2)()
    assert opened == ["hvd.step", "in", "call", "out"]


def test_nothing_recorded_and_nothing_asked_with_tracing_off(rings):
    asked = []
    step = tracing.TracedStep(lambda: (0, 0, _Loss(asked)), 2)
    tracing.disable()
    for _ in range(3):
        step()
    assert _spans(rings[1]) == [] and asked == []


def test_steps_total_counts_calls_by_the_functions_name(rings, monkeypatch):
    monkeypatch.setattr(metrics, "ACTIVE", True)
    family = metrics.registry().counter("hvd_steps_total", labels=("step",))
    before = family.value(step="trivial")
    step = tracing.TracedStep(trivial, 2)
    step(jnp.ones((2,)))
    tracing.disable()               # the counter is the metrics plane's
    step(jnp.ones((2,)))
    assert family.value(step="trivial") == before + 2
    monkeypatch.setattr(metrics, "ACTIVE", False)
    step(jnp.ones((2,)))
    assert family.value(step="trivial") == before + 2


def test_a_call_under_an_outer_trace_watches_no_tracer(rings):
    step = tracing.TracedStep(trivial, 2)
    jax.make_jaxpr(step)(jnp.ones((2,)))
    step(jnp.ones((2,)))
    step(jnp.ones((2,)))
    assert [s["args"]["n"] for s in _spans(rings[1])] == [0, 1, 2]


# ---- the two rings -----------------------------------------------------------

def test_ten_thousand_steps_push_no_startup_span_out(rings):
    startup, loop = rings
    startup.add("setup", "import", 1.0, 2.0, round=-1)
    step = tracing.TracedStep(trivial, 2)
    x = jnp.ones((2,))
    for _ in range(10_000):
        step(x)
    snap = startup.snapshot()
    assert snap["dropped"] == 0
    assert [s["name"] for s in snap["spans"]] == ["import"]
    assert len(loop) == loop.capacity == 64
    assert loop.snapshot()["dropped"] == 10_000 - 64
    assert _spans(loop)[-1]["args"]["n"] == 9_999


def test_local_trace_and_the_scrape_carry_both_rings(rings):
    startup, loop = rings
    startup.set_identity(process=3, host="solo")
    startup.add("setup", "import", 1.0, 2.0, round=-1)
    tracing.TracedStep(trivial, 2)(jnp.ones((2,)))
    loop.add("gc", "gen2", 3.0, 3.5, round=-1, collected=7)
    events = [e for e in tracing.local_trace()["traceEvents"]
              if e["ph"] == "X"]
    assert [(e["cat"], e["name"]) for e in events] == [
        ("setup", "import"), ("step", "trivial"), ("gc", "gen2")]
    assert len({e["tid"] for e in events}) == 3     # a lane a category
    assert all(e["args"]["host"] == "solo" and e["args"]["process"] == 3
               and e["args"]["round"] == -1 for e in events)
    assert events[2]["args"]["collected"] == 7
    pulled = tracing.pull_handler({})
    assert [s["cat"] for s in pulled["spans"]] == ["setup", "step", "gc"]
    assert pulled["host"] == "solo" and pulled["dropped"] == 0
    json.dumps(pulled)                              # rides the RPC reply as it is
    assert set(tracing.pull_handler({"probe": True})) == {"now", "host", "process"}
    # the ring start-up's readers cut stays its own alone
    assert [s["cat"] for s in startup.snapshot()["spans"]] == ["setup"]


def test_critical_path_ignores_the_loops_categories(rings):
    tracing.TracedStep(trivial, 2)(jnp.ones((2,)))
    rings[1].add("gc", "gen2", 3.0, 3.5, round=-1, collected=0)
    report = tracing.critical.analyze(tracing.local_trace())
    assert report["rounds"] == 0


# ---- the collector's pauses --------------------------------------------------

def _gc_spans():
    """The loop's ``gc`` spans, through the accessor that lets them in."""
    return _spans(tracing.steps(), "gc")


def test_init_watches_the_collector_and_shutdown_stops(rings):
    import horovod_tpu as hvd
    was_up = hvd.is_initialized()
    hvd.shutdown()
    assert not any(isinstance(f, tracing.GcWatch) for f in gc.callbacks)
    try:
        hvd.init()
        hvd.init()                                  # idempotent
        assert sum(isinstance(f, tracing.GcWatch) for f in gc.callbacks) == 1
        assert tracing.steps() is rings[1]          # init kept the test's ring
        family = metrics.registry().counter(
            "hvd_gc_pause_seconds_total", labels=("generation",))
        before = family.value(generation="2")
        mine = len(_gc_spans())
        gc.collect()
        spans = _gc_spans()[mine:]
        assert [s["name"] for s in spans] == ["gen2"]
        assert spans[0]["round"] == -1 and spans[0]["t0"] < spans[0]["t1"]
        assert isinstance(spans[0]["args"]["collected"], int)
        if metrics.ACTIVE:
            assert family.value(generation="2") - before == pytest.approx(
                spans[0]["t1"] - spans[0]["t0"])
        gc.collect(0)       # a young collection of a microsecond: no span
        assert len(_gc_spans()) == mine + 1
        tracing.disable()
        gc.collect()
        assert len(_gc_spans()) == mine + 1
    finally:
        hvd.shutdown()
        assert not any(isinstance(f, tracing.GcWatch) for f in gc.callbacks)
        if was_up:
            hvd.init()


def test_collector_never_waits_for_a_lock_its_own_thread_may_hold(rings):
    """A collection can start inside ``add`` or a scrape, in the thread
    that holds the ring's lock or the counter's: the watch takes neither
    there.  Its spans enter the ring when ``hand_on`` is called from
    outside a collection, the seconds the counter could not take are
    offered again at the next collection."""
    loop = rings[1]
    watch = tracing.GcWatch()
    family = metrics.registry().counter(
        "hvd_gc_pause_seconds_total", labels=("generation",))
    before = family.value(generation="2")
    info = {"generation": 2, "collected": 5, "uncollectable": 0}
    metrics_was = metrics.ACTIVE
    metrics.enable()
    try:
        with loop._lock, family._lock:
            watch("start", info)
            watch("stop", info)                     # returns: no deadlock
        assert family.value(generation="2") == before
        watch("start", dict(info, generation=0))
        watch("stop", dict(info, generation=0))
    finally:
        metrics.ACTIVE = metrics_was
    assert _spans(loop, "gc") == []
    watch.hand_on(loop)
    watch.hand_on(loop)                             # each span once
    (span,) = _spans(loop, "gc")
    assert span["name"] == "gen2" and span["args"] == {"collected": 5}
    assert family.value(generation="2") - before == pytest.approx(
        span["t1"] - span["t0"])
    watch("stop", info)     # a stop with no start (appended mid-collection)
    watch.hand_on(loop)
    assert len(_spans(loop, "gc")) == 1
