"""The program's own spans and names on the jitted data-parallel path:
``tracing.scope``, start-up split by ``hvd.init()`` part and by compiled
function, the scope names inside the two step builders and the kernel
names (docs/observability.md "Start-up and the jitted step")."""

import contextlib
import dataclasses
import functools
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu import tracing, training
from horovod_tpu.models import bert, llama, resnet
from horovod_tpu.parallel.mesh import MeshConfig, ParallelMesh
from horovod_tpu.tracing.span import SpanBuffer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def spans():
    """A buffer of the test's own; yields a function that lists it."""
    old = tracing.swap_buffer(SpanBuffer(capacity=256))
    was = tracing.ACTIVE
    tracing.enable()
    try:
        yield lambda: tracing.buffer().snapshot()["spans"]
    finally:
        tracing.ACTIVE = was
        tracing.swap_buffer(old)


# ---- tracing.scope ----------------------------------------------------------

def test_scope_records_one_span_with_parent_and_nests(spans):
    with tracing.scope("setup", "outer", why="test"):
        with tracing.scope("setup", "inner"):
            pass
        with tracing.scope("setup", "second"):
            pass
    with tracing.scope("setup", "alone"):
        pass
    got = {s["name"]: s for s in spans()}
    assert sorted(got) == ["alone", "inner", "outer", "second"]
    outer = got["outer"]
    assert outer["cat"] == "setup" and outer["args"] == {"parent": None,
                                                        "why": "test"}
    assert got["inner"]["args"]["parent"] == outer["seq"]
    assert got["second"]["args"]["parent"] == outer["seq"]
    assert got["alone"]["args"]["parent"] is None
    assert len({s["seq"] for s in got.values()}) == 4
    for child in ("inner", "second"):
        assert outer["t0"] <= got[child]["t0"] <= got[child]["t1"] <= outer["t1"]
    assert got["inner"]["t1"] <= got["second"]["t0"]


def test_scope_closes_its_span_when_the_block_raises(spans):
    with pytest.raises(KeyError):
        with tracing.scope("setup", "outer"):
            with tracing.scope("setup", "inner"):
                raise KeyError("x")
    with tracing.scope("setup", "after"):
        pass
    got = {s["name"]: s for s in spans()}
    assert got["inner"]["args"]["parent"] == got["outer"]["seq"]
    assert got["after"]["args"]["parent"] is None    # the stack unwound


def test_scope_records_nothing_when_tracing_is_off(spans):
    tracing.disable()
    with tracing.scope("setup", "quiet"):
        pass
    assert spans() == []


def test_scope_parent_is_per_thread(spans):
    import threading

    def work():
        with tracing.scope("setup", "other"):
            pass

    with tracing.scope("setup", "main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(10)
        assert not t.is_alive()
    got = {s["name"]: s for s in spans()}
    assert got["other"]["args"]["parent"] is None


def test_scope_holds_a_trace_annotation_named_hvd_dot_name(spans, monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            opened.append("in")

        def __exit__(self, *exc):
            opened.append("out")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with tracing.scope("setup", "init.backend"):
        opened.append("body")
    assert opened == ["hvd.init.backend", "in", "body", "out"]


def test_reserved_seq_is_the_spans_own():
    buf = SpanBuffer(capacity=8)
    first = buf.reserve()
    buf.add("setup", "child", 0.0, 1.0)
    buf.add("setup", "parent", 0.0, 2.0, seq=first)
    assert [(s["name"], s["seq"]) for s in buf.snapshot()["spans"]] == [
        ("child", first + 1), ("parent", first)]


def test_monotonic_and_perf_counter_are_one_clock():
    """The span buffer stamps ``time.monotonic``, the benchmark's harness
    ``time.perf_counter``: its readers cut one by the other."""
    gaps = []
    for _ in range(5):
        a = time.monotonic()
        b = time.perf_counter()
        c = time.monotonic()
        gaps.append(max(abs(b - a), abs(c - b)))
    assert min(gaps) < 1e-3


# ---- hvd.init() and the compile listener, in a process of their own ---------

_CHILD = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import horovod_tpu as hvd
from horovod_tpu import metrics, tracing
hvd.init()

@jax.jit
def inner(x):
    return jnp.sum(x * 2.0)

@jax.jit
def outer_fn(x):
    return inner(x) + 1.0

outer_fn(jnp.ones((3, 5))).block_until_ready()
cache = metrics.registry().to_dict()["hvd_compile_cache_total"]["series"]
print(json.dumps({"spans": tracing.buffer().snapshot()["spans"],
                  "cache": {s["labels"]["result"]: s["value"] for s in cache}}))
"""


def _child(tmp_path, **env):
    done = subprocess.run(
        [sys.executable, "-c", _CHILD], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env))
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    """The child twice against one cache directory of its own."""
    tmp = tmp_path_factory.mktemp("startup")
    env = {"JAX_ENABLE_COMPILATION_CACHE": "true",
           "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache")}
    return _child(tmp, **env), _child(tmp, **env)


def test_import_leaves_its_span_before_init(cold_and_warm):
    spans = cold_and_warm[0]["spans"]
    imp = next(s for s in spans if s["name"] == "import")
    init = next(s for s in spans if s["name"] == "init")
    assert imp["cat"] == "setup" and imp["t0"] < imp["t1"] <= init["t0"]


def test_init_leaves_its_span_and_five_children_in_order(cold_and_warm):
    spans = [s for s in cold_and_warm[0]["spans"] if s["cat"] == "setup"]
    init = next(s for s in spans if s["name"] == "init")
    assert init["args"]["parent"] is None
    children = [s for s in spans if s["args"].get("parent") == init["seq"]]
    assert [s["name"] for s in children] == [
        "init.rendezvous", "init.backend", "init.native",
        "init.observability", "init.engine"]
    edges = [init["t0"]]
    for s in children:
        edges += [s["t0"], s["t1"]]
    edges.append(init["t1"])
    assert edges == sorted(edges)      # inside the parent, one after another


def test_compile_listener_gives_a_span_per_stage_named_by_function(cold_and_warm):
    spans = [s for s in cold_and_warm[0]["spans"] if s["cat"] == "compile"]
    assert all(s["args"]["stage"] in ("trace", "lower", "backend") and s["name"]
               and s["t0"] <= s["t1"] for s in spans)
    stages = [(s["name"], s["args"]["stage"]) for s in spans
              if "outer_fn" in s["name"]]
    assert stages == [("outer_fn", "trace"), ("jit(outer_fn)", "lower"),
                      ("jit(outer_fn)", "backend")]
    # a function traced inside another's trace has no span of its own
    assert not any(s["name"] == "inner" for s in spans)
    trace, lower, backend = (s for s in spans if "outer_fn" in s["name"])
    assert trace["t1"] <= lower["t1"] <= backend["t0"] + 1e-3


def test_compile_cache_counter_counts_misses_cold_and_hits_warm(cold_and_warm):
    cold, warm = cold_and_warm
    assert cold["cache"].get("miss", 0) >= 1 and cold["cache"].get("hit", 0) == 0
    assert warm["cache"].get("hit", 0) == cold["cache"]["miss"]
    assert warm["cache"].get("miss", 0) == 0


def test_start_profiler_leaves_the_python_tracer_off(hvd, monkeypatch, tmp_path):
    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda logdir, **kw: seen.update(logdir=logdir, **kw))
    hvd.start_profiler(str(tmp_path))
    assert seen["logdir"] == str(tmp_path)
    assert seen["profiler_options"].python_tracer_level == 0


# ---- names inside the step ---------------------------------------------------

def _resnet_lowered(dp, sync_bn=True):
    cfg = resnet.ResNetConfig(variant=18, num_classes=10, width=8,
                              dtype=jnp.float32)
    pmesh = ParallelMesh(MeshConfig(dp=dp), devices=jax.devices()[:dp])
    ts = training.make_classifier_train_step(
        lambda p, s, x, train, axis_name: resnet.forward(
            p, s, x, cfg, train=train, axis_name=axis_name),
        lambda rng: resnet.init(cfg, rng), pmesh, sync_bn=sync_bn)
    params, state = jax.eval_shape(lambda k: resnet.init(cfg, k),
                                   jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optax.sgd(0.1, momentum=0.9).init, params)
    x = jax.ShapeDtypeStruct((4 * dp, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((4 * dp,), jnp.int32)
    return ts.step_fn.lower(params, state, opt_state, x, y)


def _resnet_step(dp, sync_bn=True):
    return _resnet_lowered(dp, sync_bn).compile().as_text()


def _bert_lowered(dp, reduce_grads=True):
    cfg = bert.tiny(vocab=64, seq=32, num_labels=3)
    mesh = Mesh(np.array(jax.devices()[:dp]), ("dp",))
    opt = optax.adamw(1e-3)
    step = bert.make_dp_finetune_step(cfg, mesh, "dp", opt,
                                      reduce_grads=reduce_grads)
    params = jax.eval_shape(lambda k: bert.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    tokens = jax.ShapeDtypeStruct((2 * dp, 32), jnp.int32)
    labels = jax.ShapeDtypeStruct((2 * dp,), jnp.int32)
    return step.lower(params, opt_state, tokens, labels)


def _bert_step(dp, reduce_grads=True):
    return _bert_lowered(dp, reduce_grads).compile().as_text()


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


@pytest.mark.parametrize("build", [_resnet_step, _bert_step],
                         ids=["resnet", "bert"])
def test_compiled_step_carries_forward_backward_and_optimizer(build):
    names = _op_names(build(2))
    fwd = training.SCOPE_FORWARD
    # everything under the differentiated function is jvp'd, so the forward
    # pass reads jvp(..) and the backward pass transpose(jvp(..))
    for scope in (f"/jvp({fwd})/", f"/transpose(jvp({fwd}))/",
                  f"/{training.SCOPE_OPTIMIZER}/", f"/{training.SCOPE_REDUCE}/"):
        assert any(scope in n for n in names), scope
    # nothing of the optimizer's sits under the forward scope
    assert not any(training.SCOPE_OPTIMIZER in n and training.SCOPE_FORWARD in n
                   for n in names)


def test_sync_bn_psum_is_named_on_two_devices_and_absent_without_sync_bn():
    scope = training.SCOPE_SYNC_BN
    text = _resnet_step(2, sync_bn=True)
    named = [l for l in text.splitlines() if f"/{scope}/" in l]
    assert named and any("all-reduce" in l for l in named)
    # it sits inside the forward scope, and its transpose in the backward one
    assert any(f"jvp({training.SCOPE_FORWARD})" in l for l in named)
    assert scope not in _resnet_step(2, sync_bn=False)
    assert scope not in _resnet_step(1, sync_bn=True)   # one device: no psum


def _without_metadata(hlo_text):
    """The program alone: no ``metadata={...}`` on an instruction and none
    of the module's tables of files, functions and stack frames."""
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    blocks = [b for b in hlo_text.split("\n\n") if not b.startswith(tables)]
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", "\n\n".join(blocks))
    # an instruction is named after its op_name (%jvp_jit_take_along_axis__.21
    # against %jit_take_along_axis_.25), so names go too: what is compared is
    # every instruction's shape, opcode, operands' positions and attributes,
    # in the order the compiler scheduled them
    return re.sub(r"%[\w.\-]+", "%", text)


@pytest.mark.parametrize("build,dp", [(_resnet_step, 1), (_resnet_step, 2),
                                      (_bert_step, 1), (_bert_step, 2)],
                         ids=["resnet-dp1", "resnet-dp2", "bert-dp1", "bert-dp2"])
def test_scopes_change_nothing_but_metadata(build, dp, monkeypatch):
    """The compiled program with ``metadata={...}`` and the instructions'
    names stripped is the same text with the scopes and with
    ``jax.named_scope`` switched off."""
    with_scopes = build(dp)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = build(dp)
    assert training.SCOPE_FORWARD in with_scopes
    assert training.SCOPE_FORWARD not in without
    assert _without_metadata(with_scopes) == _without_metadata(without)


# ---- the model's own names ----------------------------------------------------

_HYBRID_KINDS = ("mamba", "window", "mamba", "full", "gmu", "cross")
_LLAMA_TINIES = {
    "llama": llama.tiny(),
    "dropless": dataclasses.replace(
        llama.tiny(), n_experts=4, expert_top_k=2, moe_dispatch="dropless"),
    "hybrid": llama.LlamaConfig(
        vocab_size=256, d_model=64, n_layers=len(_HYBRID_KINDS), n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, dtype=jnp.float32,
        remat_policy="full", layer_kinds=_HYBRID_KINDS, sliding_window=16,
        ssm_inner=128, ssm_dt_rank=8),
}


def _llama_lowered(which):
    """The llama tiny through the plain step, which opens no scope of its
    own; the other two through ``objective=``, as their cells' adapters
    build them, under ``hvd_forward``."""
    pmesh = ParallelMesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    objective = None if which == "llama" else (
        lambda params, batch, cfg, par: llama.loss_fn(
            params, *batch, cfg, par, with_stats=True))
    ts = training.make_llama_train_step(_LLAMA_TINIES[which], pmesh,
                                        optax.adamw(1e-3), objective=objective)
    state = jax.eval_shape(ts.init_fn, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    batch = (tokens, tokens) if objective is None else ((tokens, tokens),)
    return ts.step_fn.lower(*state, *batch)


def _model_lowered(model):
    if model == "bert":
        return _bert_lowered(1)
    if model == "resnet":
        return _resnet_lowered(2)       # two devices: SyncBN's psum is there
    return _llama_lowered(model)


# scopes every pass has, and those of them inside a remat'd layer (the
# embedding and the head lie outside the stack)
_EMBED, _ATTN, _MLP, _HEAD = (training.SCOPE_EMBED, training.SCOPE_ATTENTION,
                              training.SCOPE_MLP, training.SCOPE_HEAD)
_MIXERS = (training.SCOPE_SSM_MIXER, training.SCOPE_GMU,
           training.SCOPE_DIFF_ATTENTION)
_MODEL_SCOPES = {
    "llama": ((_EMBED, _ATTN, _MLP, _HEAD), (_ATTN, _MLP)),
    "dropless": ((_EMBED, _ATTN, _MLP, _HEAD, "hvd_moe_route",
                  "hvd_moe_experts"), (_ATTN, _MLP, "hvd_moe_route")),
    "hybrid": ((_EMBED, _MLP, _HEAD) + _MIXERS, (_MLP,) + _MIXERS),
    "bert": ((_EMBED, _ATTN, _MLP, _HEAD), (_ATTN, _MLP)),
    "resnet": ((training.SCOPE_STEM, _HEAD)
               + tuple(training.SCOPE_STAGE.format(i) for i in range(4)), ()),
}


@functools.lru_cache(maxsize=None)
def _model_op_names(model):
    return frozenset(_op_names(_model_lowered(model).compile().as_text()))


@pytest.mark.parametrize("model", sorted(_MODEL_SCOPES))
def test_compiled_step_carries_the_models_scopes_in_every_pass(model):
    """The sublayers' names are opened in ``models/``, so the step of any
    builder carries them (under ``hvd_forward`` where the builder opens
    it): in the forward pass, under autodiff's transpose in the backward
    pass, and under remat's second forward where the layer stack is
    remat'd."""
    names = _model_op_names(model)
    every_pass, rematted = _MODEL_SCOPES[model]
    fwd = training.SCOPE_FORWARD
    opens_forward = model != "llama"
    assert any(fwd in n for n in names) == opens_forward
    for scope in every_pass:
        # ``jvp(hvd_embed)/gather`` where the scope is the outermost name
        under = [n for n in names if re.search(rf"[/(]{scope}[/)]", n)
                 and (fwd in n) == opens_forward]
        assert any("transpose(" not in n and "rematted_computation" not in n
                   for n in under), f"{scope}: forward"
        assert any("transpose(" in n for n in under), f"{scope}: backward"
        if scope in rematted:
            assert any("rematted_computation" in n for n in under), \
                f"{scope}: recompute"
    # a part of the model is named once: the trunk's attention scope is not
    # opened around hybrid's (a reader that adds the two would count twice)
    assert not any(_ATTN in n and training.SCOPE_DIFF_ATTENTION in n
                   for n in names)
    assert not any(fwd in n and training.SCOPE_OPTIMIZER in n for n in names)


def test_routed_experts_lie_inside_the_mlp_scope():
    names = [n for n in _model_op_names("dropless")
             if "hvd_moe_experts" in n or "hvd_moe_route" in n]
    assert names
    for n in names:
        assert re.search(rf"\b{_MLP}/(.*/)?hvd_moe_(experts|route)/", n), n
    # and the attention's kernels' call under the attention's
    assert not any(_ATTN in n for n in names)


def test_resnet_blocks_sync_bn_lies_inside_its_stage():
    names = [n for n in _model_op_names("resnet")
             if f"/{training.SCOPE_SYNC_BN}/" in n]
    assert names
    assert all(re.search(r"/hvd_(stem|stage\d)/(.*/)?hvd_sync_bn/", n)
               for n in names)


def test_models_take_their_scope_names_from_a_leaf_not_from_training(tmp_path):
    """``horovod_tpu/scopes.py`` holds the names and imports nothing of
    the package: the package and the four model files that open scopes
    import without ``training.py``, which imports the models and hands
    the names on under its own."""
    child = (
        "import sys\n"
        "import horovod_tpu\n"
        "from horovod_tpu.models import llama, hybrid, bert, resnet\n"
        "assert 'horovod_tpu.training' not in sys.modules\n"
        "from horovod_tpu import scopes, training\n"
        "from horovod_tpu.models import moe\n"
        "names = [n for n in dir(scopes) if n.startswith('SCOPE_')]\n"
        "assert len(names) == 26, names\n"
        "for n in names:\n"
        "    home = moe if 'moe' in getattr(scopes, n) else training\n"
        "    assert getattr(home, n) is getattr(scopes, n), n\n"
        "print('ok')\n")
    done = subprocess.run(
        [sys.executable, "-c", child], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert done.returncode == 0, done.stderr[-3000:]
    assert done.stdout.strip().splitlines()[-1] == "ok"
    with open(os.path.join(REPO, "horovod_tpu", "scopes.py")) as f:
        assert not re.search(r"^\s*(import|from)\s", f.read(), re.M)


@pytest.mark.parametrize("model", sorted(_MODEL_SCOPES))
def test_model_scopes_leave_the_lowered_text_as_it_was(model, monkeypatch):
    """``lower().as_text()`` carries no debug info: with the scopes and
    with ``jax.named_scope`` switched off it is the same text, byte for
    byte (a scope is a string in the lowered module's locations)."""
    with_scopes = _model_lowered(model).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _model_lowered(model).as_text() == with_scopes
    assert "hvd_" not in re.sub(r"hvd_flash_(out|lse)", "", with_scopes)


@pytest.mark.parametrize("seq_len,names", [
    # one block a sequence: the packed path and its single backward kernel
    (128, ("hvd_flash_fwd", "hvd_flash_bwd")),
    # several: the masked path
    (1024, ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv")),
])
def test_flash_kernels_carry_their_names(seq_len, names, pallas_interpret):
    from horovod_tpu.ops import flash_attention as fa
    q = jax.ShapeDtypeStruct((1, seq_len, 2, 64), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=False).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    found = {name for name in ("hvd_flash_fwd", "hvd_flash_bwd",
                               "hvd_flash_dq", "hvd_flash_dkv")
             if name in text}
    assert found == set(names)
