"""Device time by the program's own scopes (PR 35): op_name -> (scope,
pass) on made-up names and text, the join with a made-up trace, and the
whole of it on two traces recorded on a v5e, the second with its compiled
step's text beside it."""

import gzip
import json
import types

import pytest

import bench_tree
from harness import hlo, registry, scopes, xplane

DATA = bench_tree.REPO / "tests" / "benchmark" / "data"
MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
SCOPE_METRICS = ("forward_ms", "backward_ms", "recompute_ms", "optimizer_ms",
                 "attention_ms", "moe_route_ms", "moe_experts_ms",
                 "ssm_mixer_ms", "scope_unattributed_pct")


@pytest.mark.parametrize("op_name, where", [
    # the scope opened inside the differentiated function (bert.py) ...
    ("jit(step)/jvp(hvd_forward)/while/body/dot_general",
     ("hvd_forward", "forward")),
    ("jit(step)/transpose(jvp(hvd_forward))/while/body/closed_call/checkpoint/mul",
     ("hvd_forward", "backward")),
    # ... and outside it (training.py), an inner scope and a kernel's name
    ("jit(s)/hvd_forward/transpose(jvp())/while/body/hvd_moe_experts/while/body/"
     "hvd_moe_gmm_dh/pallas_call",
     ("hvd_forward/hvd_moe_experts/hvd_moe_gmm_dh", "backward")),
    ("jit(s)/hvd_forward/jvp()/while/body/closed_call/hvd_moe_route/top_k",
     ("hvd_forward/hvd_moe_route", "forward")),
    # remat's second forward, and the backward of a checkpoint nested in it
    ("jit(s)/hvd_forward/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/hvd_moe_route/dot_general",
     ("hvd_forward/hvd_moe_route", "recompute")),
    ("jit(s)/hvd_forward/transpose(jvp())/checkpoint/rematted_computation/"
     "checkpoint/transpose(jvp())/mul", ("hvd_forward", "backward")),
    ("checkpoint/rematted_computation/hvd_moe_route/exp",
     ("hvd_moe_route", "recompute")),
    # the scopes that are a pass of their own
    ("jit(step)/hvd_optimizer/hvd_bucket0/add",
     ("hvd_optimizer/hvd_bucket0", "optimizer")),
    ("jit(step)/hvd_reduce/div", ("hvd_reduce", "reduce")),
    # no scope of the program's; a primitive called transpose is no pass
    ("jit(step)/jit(main)/transpose", ("", "")),
    ("", ("", "")),
    # XLA's gluing: a merged pair reads as its first, an inlined call as its site
    ("jit(s)/hvd_optimizer/mul;jit(s)/hvd_forward/jvp()/add",
     ("hvd_optimizer", "optimizer")),
    ("jit(s)/hvd_forward/transpose(jvp())/while/body/closed_call/jit(s)/"
     "hvd_forward/jvp()/while/body/closed_call/hvd_moe_experts/jit(searchsorted)/lt",
     ("hvd_forward", "backward")),
])
def test_op_name_to_scope_and_pass(op_name, where):
    assert hlo.classify(op_name) == where


TEXT = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8], param_1.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0:T(256)} parameter(0)
  %param_1.1 = f32[8]{0:T(256)} parameter(1)
  %convolution.5 = f32[8]{0:T(256)} convolution(%param_0.1, %param_1.1), metadata={op_name="jit(step)/transpose(jvp(hvd_forward))/conv_general_dilated" stack_frame_id=4}
  %constant.3 = f32[]{:T(128)} constant(0.9), metadata={op_name="jit(step)/hvd_optimizer/mul"}
  %broadcast.3 = f32[8]{0:T(256)} broadcast(%constant.3), dimensions={}, metadata={op_name="jit(step)/hvd_optimizer/mul"}
  %multiply.9 = f32[8]{0:T(256)} multiply(%param_1.1, %broadcast.3), metadata={op_name="jit(step)/hvd_optimizer/mul"}
  ROOT %add.9 = f32[8]{0:T(256)} add(%multiply.9, %convolution.5), metadata={op_name="jit(step)/hvd_optimizer/add"}
}

%fused_computation.2 (param_0.2: f32[8]) -> bf16[8] {
  %param_0.2 = f32[8]{0:T(256)} parameter(0)
  %constant.4 = f32[]{:T(128)} constant(1), metadata={op_name="jit(step)/transpose(jvp(hvd_forward))/mul"}
  %broadcast.4 = f32[8]{0:T(256)} broadcast(%constant.4), dimensions={}, metadata={op_name="jit(step)/transpose(jvp(hvd_forward))/mul"}
  %tanh.1 = f32[8]{0:T(256)} tanh(%param_0.2), metadata={op_name="jit(step)/jvp(hvd_forward)/hvd_mlp/tanh"}
  %add.2 = f32[8]{0:T(256)} add(%tanh.1, %broadcast.4), metadata={op_name="jit(step)/jvp(hvd_forward)/hvd_mlp/add"}
  ROOT %convert.2 = bf16[8]{0:T(1024)(128)(2,1)} convert(%add.2), metadata={op_name="jit(step)/transpose(jvp(hvd_forward))/convert_element_type"}
}

%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0:T(256)} parameter(0)
  ROOT %negate.3 = f32[8]{0:T(256)} negate(%param_0.3), metadata={op_name="jit(step)/jvp(hvd_forward)/checkpoint/neg"}
}

%body.1 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.1 = (s32[]{:T(128)}, f32[8]{0:T(256)}) parameter(0)
  %get-tuple-element.2 = f32[8]{0:T(256)} get-tuple-element(%arg.1), index=1
  %fusion.7 = f32[8]{0:T(256)} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.3
  ROOT %tuple.1 = (s32[]{:T(128)}, f32[8]{0:T(256)}) tuple(%get-tuple-element.2, %fusion.7)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0:T(256)} parameter(0), metadata={op_name="params"}
  %copy.1 = f32[8]{0:T(256)} copy(%Arg_0.1)
  %while.1 = (s32[]{:T(128)}, f32[8]{0:T(256)}) while(%copy.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(hvd_forward)/while"}
  %fusion.2 = bf16[8]{0:T(1024)(128)(2,1)} fusion(%copy.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(hvd_forward)/hvd_mlp/add"}
  %hvd_flash_fwd.3 = (bf16[8]{0}, f32[8]{0}) custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(hvd_forward)/hvd_flash_fwd/pallas_call"}
  ROOT %multiply_add_fusion.1 = f32[8]{0:T(256)} fusion(%copy.1, %Arg_0.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/hvd_optimizer/add" stack_frame_id=9}
}
'''


def test_scopes_of_a_made_up_module():
    s = hlo.scopes(TEXT)
    # a fusion whose computing instructions span two (scope, pass) is mixed
    # and keeps its own op_name
    assert s["multiply_add_fusion.1"] == (
        "hvd_optimizer", "optimizer",
        "hvd_forward backward + hvd_optimizer optimizer", "f32[8]{0:T(256)}")
    # a constant and a cast pulled in from another pass do not mix it
    assert s["fusion.2"][:3] == ("hvd_forward/hvd_mlp", "forward", "")
    # without an op_name of its own: what the instructions it calls carry
    assert s["fusion.7"][:3] == ("hvd_forward", "forward", "")
    assert s["while.1"][:3] == ("hvd_forward", "forward", "")
    assert s["hvd_flash_fwd.3"][:2] == ("hvd_forward/hvd_flash_fwd", "forward")
    assert s["copy.1"][:3] == ("", "", "")
    assert s["hvd_flash_fwd.3"][3] == "(bf16[8]{0}, f32[8]{0})"
    # the instructions inside the fused computations are there too
    assert s["convolution.5"][:2] == ("hvd_forward", "backward")


def _made_up_profile():
    """Three steps of the module above and, between them, a small program
    of the feed's that also has a ``fusion.2``."""
    ops, modules = [], []
    for k in range(3):
        t = 1000 * k
        modules += [(t, 800, "jit_step(77)"), (t + 850, 100, "jit_convert(5)")]
        ops += [(t, 100, "%copy.1 = f32[8] copy(%Arg_0.1)"),
                (t + 100, 300, "%while.1 = (s32[], f32[8]) while(%copy.1)"),
                (t + 150, 200, "%fusion.7 = f32[8] fusion(%g)"),
                (t + 400, 50, "%fusion.2 = bf16[8] fusion(%copy.1)"),
                (t + 450, 150, '%hvd_flash_fwd.3 = (bf16[8]) custom-call(%fusion.2), '
                               'custom_call_target="tpu_custom_call"'),
                (t + 600, 200, "%multiply_add_fusion.1 = f32[8] fusion(%copy.1)"),
                (t + 850, 100, "%fusion.2 = s32[4] fusion(%tokens)")]
    return types.SimpleNamespace(planes=[bench_tree.plane(
        "/device:TPU:0", **{"XLA Modules": modules, "XLA Ops": ops})])


def _ctx(reduced, text, steps):
    said = []
    return types.SimpleNamespace(
        trace=reduced, hlo_text=lambda: text, say=said.append,
        traced=types.SimpleNamespace(stamps=[0.0] * steps)), said


def test_two_modules_with_one_instruction_name_do_not_mix():
    r = xplane.reduce_profile(_made_up_profile(), chips=1)
    assert set(r.instructions) == {"jit_step", "jit_convert"}
    assert r.instructions["jit_step"]["fusion.2"] == ["fusion", pytest.approx(150e-9), 3]
    assert r.instructions["jit_convert"] == {"fusion.2": ["fusion", pytest.approx(300e-9), 3]}
    # a container counts its self time only
    assert r.instructions["jit_step"]["while.1"] == ["while", pytest.approx(300e-9), 3]
    # the kinds are what they were: both fusion.2 in one line
    assert dict(map(tuple, r.device_ops))["fusion"] == pytest.approx(1050e-9)
    assert dict(map(tuple, r.device_ops))["hvd_flash_fwd (custom-call)"] \
        == pytest.approx(450e-9)


def test_the_table_of_a_made_up_trace_adds_up_and_says_itself():
    r = xplane.reduce_profile(_made_up_profile(), chips=1)
    ctx, said = _ctx(r, TEXT, steps=3)
    t = scopes.table(ctx)
    assert scopes.table(ctx) is t and len(said) == 3        # made and said once
    ms = lambda ns: pytest.approx(ns * 1e-6)
    assert scopes.ms(ctx, passes=("forward",)) == ms(100 + 200 + 50 + 150)
    assert scopes.ms(ctx, passes=("optimizer",)) == ms(200)
    assert scopes.ms(ctx, scope="hvd_mlp") == ms(50)
    assert scopes.ms(ctx, scope="hvd_flash_fwd") == ms(150)
    assert scopes.ms(ctx, passes=("backward",)) is None
    assert scopes.ms(ctx, scope="hvd_moe_experts") is None
    assert t.unattributed_s == pytest.approx(300e-9)
    assert t.mixed == {"multiply_add_fusion.1": pytest.approx(600e-9)}
    assert t.spans == {"hvd_forward backward + hvd_optimizer optimizer":
                       pytest.approx(600e-9)}
    # the passes and the unattributed add up to the module's busy time,
    # which leaves the other module's 300 ns out
    assert sum(t.rows.values()) == pytest.approx(r.busy_s - 300e-9)
    assert said[0].startswith("scopes, ms a step: ")
    assert "unattributed 0.000" in said[0] and "multiply_add_fusion.1" in said[0]
    first = said[1].split(": ", 1)[1].split("; ")[0]
    assert first.startswith("multiply_add_fusion.1 fusion hvd_optimizer/optimizer mixed 0.000 in 1 ")
    assert "f32[8]{0:T(256)}" in said[1]


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_scope_readers_read_what_is_there_and_nothing_otherwise(metric):
    """Without a trace (an untraced run, a CPU rehearsal), on a trace
    without the text's module, and on a program without the scope (a
    parent commit under these files) a reader returns None and does not
    raise; with the scope it reads the number."""
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert entry["source"] == "device_trace" and entry["moves"] == "throughput"
    assert read(_ctx(None, TEXT, 3)[0]) is None
    assert read(_ctx(types.SimpleNamespace(device_ops=[["fusion", 1.0]]), TEXT, 3)[0]) is None
    r = xplane.reduce_profile(_made_up_profile(), chips=1)
    other = TEXT.replace("HloModule jit_step", "HloModule jit_other")
    assert read(_ctx(r, other, 3)[0]) is None
    expected = {"forward_ms": 500e-6, "optimizer_ms": 200e-6,
                "scope_unattributed_pct": 100 * (100 + 200) / 800}
    assert read(_ctx(r, TEXT, 3)[0]) == (
        pytest.approx(expected[metric]) if metric in expected else None)
    # every scope in a name stack of its own: each reader finds its own
    scope = {"attention_ms": "hvd_diff_attention", "moe_route_ms": "hvd_moe_route",
             "moe_experts_ms": "hvd_moe_experts", "ssm_mixer_ms": "hvd_ssm_mixer",
             "backward_ms": "transpose(jvp())",
             "recompute_ms": "checkpoint/rematted_computation"}.get(metric)
    if scope:
        text = TEXT.replace("jvp(hvd_forward)/hvd_mlp/", f"hvd_forward/{scope}/")
        assert read(_ctx(r, text, 3)[0]) == pytest.approx(50e-6)


def test_attention_ms_takes_the_trunks_scope_when_the_program_opens_it():
    read = registry.reader(str(bench_tree.BENCH), "layer_metrics", "attention_ms")
    r = xplane.reduce_profile(_made_up_profile(), chips=1)
    text = TEXT.replace("hvd_flash_fwd/pallas_call", "hvd_attention/hvd_flash_fwd/pallas_call") \
               .replace("hvd_mlp", "hvd_diff_attention")
    assert read(_ctx(r, text, 3)[0]) == pytest.approx(200e-6)


# ---- the recorded traces -------------------------------------------------

def _profile(name):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / name).read_bytes()))


@pytest.mark.parametrize("stem", ["bert_b32_3steps", "bert_b32_3steps_pr35"])
def test_the_kinds_of_a_recorded_trace_are_the_parents_to_the_bit(stem):
    """``<stem>.parent.json`` is what the parent commit's reduction
    (a819322, before ``Reduced`` kept the instructions) read off the same
    trace: every kind, gap and second is equal to the last digit."""
    r = xplane.reduce_profile(_profile(stem + ".xplane.pb.gz"), chips=1)
    parent = json.loads((DATA / (stem + ".parent.json")).read_text())
    for key, value in parent.items():
        assert getattr(r, key) == value, key
    assert set(r.breakdown()) == {"device_ops", "idle_gaps"}
    assert r.breakdown()["device_ops"] == parent["device_ops"][:10]
    # and the kinds are the instructions', summed again by kind
    by_kind = {}
    for kept in r.instructions.values():
        for name, (opcode, seconds, _) in kept.items():
            kind = name.rstrip(".0123456789") + (
                " (custom-call)" if opcode == "custom-call" else "")
            by_kind[kind] = by_kind.get(kind, 0.0) + seconds
    assert by_kind == pytest.approx(dict(map(tuple, r.device_ops)))


def test_the_recorded_step_by_the_programs_scopes():
    """Three steps of bert-base-ft.s128-b32.dp1 on a v5e with the compiled
    step's text (my chip run, PR 35)."""
    r = xplane.reduce_profile(_profile("bert_b32_3steps_pr35.xplane.pb.gz"), chips=1)
    text = gzip.decompress((DATA / "bert_b32_3steps_pr35.hlo.txt.gz").read_bytes()).decode()
    ctx, said = _ctx(r, text, steps=3)
    t = scopes.table(ctx)
    assert set(r.instructions) == {"jit_step"}
    # every instruction of the trace is an instruction of the text
    assert set(r.instructions["jit_step"]) <= set(hlo.scopes(text))
    # the passes and the unattributed add up to the busy time
    by_pass = sum(t.seconds(passes=(p,)) for p in hlo.PASSES)
    assert by_pass + t.unattributed_s == pytest.approx(t.total_s, rel=1e-9)
    assert t.total_s == pytest.approx(r.busy_s, rel=0.005)
    read = lambda m: registry.reader(str(bench_tree.BENCH), "layer_metrics", m)(ctx)
    assert 0 < read("forward_ms") < read("backward_ms")
    assert 0 < read("recompute_ms") < read("forward_ms")    # remat under "dots"
    assert 2.0 < read("optimizer_ms") < 8.0                 # AdamW over 110 M fp32
    assert read("scope_unattributed_pct") < 10
    assert read("moe_experts_ms") is None and read("ssm_mixer_ms") is None
    assert len(said) == 3 and said[1].count(";") == scopes.LARGEST - 1


# ---- the program's side: the scopes the readers ask for ------------------

@pytest.mark.parametrize("config, scopes_read, passes", [
    ("resnet50-synth", {"hvd_forward", "hvd_optimizer"},
     {"forward", "backward", "optimizer"}),
    ("bert-base-ft", {"hvd_forward", "hvd_optimizer"},
     {"forward", "recompute", "backward", "optimizer"}),
    ("sdar-30b-a3b", {"hvd_forward", "hvd_optimizer", "hvd_moe_route",
                      "hvd_moe_experts"},
     {"forward", "recompute", "backward", "optimizer"}),
    ("phi4-mini-flash", {"hvd_forward", "hvd_optimizer", "hvd_ssm_mixer",
                         "hvd_diff_attention", "hvd_gmu"},
     {"forward", "recompute", "backward", "optimizer"}),
])
def test_the_programs_step_opens_the_scopes_the_readers_ask_for(
        hvd, config, scopes_read, passes):
    """The step each configuration's adapter builds, compiled at the toy
    sizes on the CPU: its text names every scope and pass that a reader of
    the manifest sums (a scope renamed in the program would otherwise read
    None on the chip and say nothing here)."""
    import jax
    cfg_dir = bench_tree.BENCH / "configs" / config
    cfg = bench_tree.load(cfg_dir / "config.json")
    cfg.update(cfg["toy"])
    cfg["dtype"]["compute"] = "float32"
    ref = registry.load_module(str(cfg_dir / "reference.py"))
    adapter = registry.load_module(str(cfg_dir / "adapter.py"))
    # bert's step runs on hvd.mesh(), every device of the process
    devices = jax.devices() if config == "bert-base-ft" else jax.devices()[:1]
    program = adapter.build(cfg, ref, devices, 2)
    key = jax.random.key(3)
    batch = program.place(ref.make_samples(cfg, key, program.global_batch))
    text = program.compiled(program.init(key), batch).as_text()
    found = {w[:2] for w in hlo.scopes(text).values() if w[0]}
    assert scopes_read <= {part for scope, _ in found for part in scope.split("/")}
    assert passes <= {p for _, p in found}
    assert {p for _, p in found} <= set(hlo.PASSES)
