"""The readers of the model's own scopes (PR 36: ``hvd_embed``,
``hvd_attention``, ``hvd_mlp``, ``hvd_head`` in the trunks and the encoder,
``hvd_stem`` / ``hvd_stage<i>`` in ResNet): on a made-up module, on the
trace recorded from the parent of that PR, and against each
configuration's toy step compiled on the CPU."""

import gzip
import types

import pytest

import bench_tree
from harness import hlo, registry, scopes, xplane

DATA = bench_tree.REPO / "tests" / "benchmark" / "data"
MANIFEST = bench_tree.load(bench_tree.REPO / "BENCHMARK.json")
# what each new reader sums (model_unscoped_pct: ``hvd_forward`` alone)
READS = {"mlp_ms": ("hvd_mlp",), "head_ms": ("hvd_head",),
         "stem_stage0_ms": ("hvd_stem", "hvd_stage0"),
         "model_unscoped_pct": ("hvd_forward",)}


def _read(metric, ctx):
    return registry.reader(str(bench_tree.BENCH), "layer_metrics", metric)(ctx)


def _ctx(reduced, text, steps):
    said = []
    return types.SimpleNamespace(
        trace=reduced, hlo_text=lambda: text, say=said.append,
        traced=types.SimpleNamespace(stamps=[0.0] * steps)), said


# One step of a made-up program: (instruction, opcode, op_name under
# jit(step)/, device ns).  ``fusion.9`` is computed inside ``fusion.5``'s
# fused computation below and makes it mixed.
STEP = [
    ("fusion.1", "fusion", "hvd_forward/jvp()/hvd_embed/gather", 10),
    ("fusion.2", "fusion", "hvd_forward/jvp()/while/body/hvd_attention/dot_general", 100),
    ("hvd_flash_fwd.3", "custom-call",
     "hvd_forward/jvp()/while/body/hvd_attention/hvd_flash_fwd/pallas_call", 200),
    ("fusion.4", "fusion", "hvd_forward/jvp()/while/body/hvd_mlp/mul", 30),
    ("fusion.5", "fusion", "hvd_forward/jvp()/while/body/hvd_attention/dot_general", 90),
    ("fusion.6", "fusion", "hvd_forward/jvp()/while/body/hvd_mlp/hvd_moe_route/top_k", 40),
    ("fusion.7", "fusion", "hvd_forward/jvp()/while/body/hvd_mlp/hvd_moe_experts/sort", 50),
    ("hvd_moe_gmm_down.8", "custom-call",
     "hvd_forward/jvp()/while/body/hvd_mlp/hvd_moe_experts/hvd_moe_gmm_down/pallas_call", 300),
    ("fusion.10", "fusion", "hvd_forward/jvp()/while/body/add", 20),
    ("fusion.11", "fusion", "hvd_forward/transpose(jvp())/hvd_head/dot_general", 400),
    ("fusion.12", "fusion", "hvd_forward/jvp()/hvd_stem/conv_general_dilated", 60),
    ("fusion.13", "fusion", "hvd_forward/transpose(jvp())/hvd_stage0/hvd_sync_bn/psum", 70),
    ("fusion.14", "fusion", "hvd_forward/jvp()/hvd_stage1/conv_general_dilated", 80),
    ("fusion.15", "fusion", "hvd_optimizer/add", 500),
    ("copy.16", "copy", None, 50),
]
STEP_NS = sum(ns for *_, ns in STEP)
MIXED = "hvd_forward/hvd_attention forward + hvd_forward/hvd_mlp forward"


def _text():
    meta = lambda op_name: (f', metadata={{op_name="jit(step)/{op_name}"}}'
                            if op_name else "")
    lines = ["HloModule jit_step, is_scheduled=true", "",
             "%fused_computation.0 (param_0.0: f32[8]) -> f32[8] {",
             "  %param_0.0 = f32[8]{0} parameter(0)",
             "  ROOT %negate.0 = f32[8]{0} negate(%param_0.0)",
             "}", "",
             "%fused_computation.5 (param_0.5: f32[8]) -> (f32[8], f32[]) {",
             "  %param_0.5 = f32[8]{0} parameter(0)",
             "  %dot.5 = f32[8]{0} dot(%param_0.5, %param_0.5)"
             + meta("hvd_forward/jvp()/while/body/hvd_attention/dot_general"),
             "  %reduce.9 = f32[] reduce(%dot.5, %param_0.5), dimensions={0}"
             + meta("hvd_forward/jvp()/while/body/hvd_mlp/reduce_sum"),
             "  ROOT %tuple.5 = (f32[8]{0}, f32[]) tuple(%dot.5, %reduce.9)",
             "}", "", "ENTRY %main.1 (Arg_0.1: f32[8]) -> f32[8] {",
             "  %Arg_0.1 = f32[8]{0} parameter(0)"]
    for name, opcode, op_name, _ in STEP:
        calls = (f", kind=kLoop, calls=%fused_computation.{5 * (name == 'fusion.5')}"
                 if opcode == "fusion" else "")
        lines.append(f"  %{name} = f32[8]{{0}} {opcode}(%Arg_0.1){calls}{meta(op_name)}")
    return "\n".join(lines + ["}", ""])


def _profile(steps=2):
    ops, modules, t = [], [], 0
    for _ in range(steps):
        modules.append((t, STEP_NS, "jit_step(7)"))
        for name, opcode, _, ns in STEP:
            ops.append((t, ns, f"%{name} = f32[8] {opcode}(%Arg_0.1)"))
            t += ns
    return types.SimpleNamespace(planes=[bench_tree.plane(
        "/device:TPU:0", **{"XLA Modules": modules, "XLA Ops": ops})])


@pytest.mark.parametrize("metric, expected", [
    # the norm's row, the router's and the experts' with their kernel: each once
    ("mlp_ms", (30 + 40 + 50 + 300) * 1e-6),
    ("head_ms", 400e-6),
    # a stage's SyncBN lies inside it; the next stage is another reader's
    ("stem_stage0_ms", (60 + 70) * 1e-6),
    # the residual add alone, of everything the step ran
    ("model_unscoped_pct", 100 * 20 / STEP_NS),
])
def test_new_readers_on_a_made_up_module(metric, expected):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert (entry["source"], entry["moves"], entry["layer"]) == (
        "device_trace", "throughput", "Model")
    r = xplane.reduce_profile(_profile(), chips=1)
    text = _text()
    assert _read(metric, _ctx(r, text, 2)[0]) == pytest.approx(expected)
    # no trace, a trace without instructions, a trace of another module:
    # nothing to read and nothing raised
    assert _read(metric, _ctx(None, text, 2)[0]) is None
    assert _read(metric, _ctx(types.SimpleNamespace(device_ops=[]), text, 2)[0]) is None
    other = text.replace("HloModule jit_step", "HloModule jit_other")
    assert _read(metric, _ctx(r, other, 2)[0]) is None
    # a program that opens none of these scopes (the parent): the three
    # times read nothing, and the model outside its kernels, its experts
    # and SyncBN lies in no part of it
    bare = text
    for scope in ("hvd_embed", "hvd_attention", "hvd_mlp", "hvd_head",
                  "hvd_stem", "hvd_stage0", "hvd_stage1"):
        bare = bare.replace(scope + "/", "")
    got = _read(metric, _ctx(r, bare, 2)[0])
    if metric == "model_unscoped_pct":
        named = 200 + 40 + 50 + 300 + 70
        assert got == pytest.approx(100 * (STEP_NS - 500 - 50 - named) / STEP_NS)
    else:
        assert got is None


def test_a_mixed_fusion_stays_where_its_op_name_says():
    r = xplane.reduce_profile(_profile(), chips=1)
    ctx, said = _ctx(r, _text(), 2)
    t = scopes.table(ctx)
    assert hlo.scopes(_text())["fusion.5"][:3] == (
        "hvd_forward/hvd_attention", "forward", MIXED)
    assert t.mixed == {"fusion.5": pytest.approx(2 * 90e-9)}
    # whole under the attention's scope, none of it under the MLP's
    assert scopes.ms(ctx, scope="hvd_attention") == pytest.approx((100 + 200 + 90) * 1e-6)
    assert _read("mlp_ms", ctx) == pytest.approx(420e-6)
    assert _read("attention_ms", ctx) == pytest.approx(390e-6)
    assert _read("moe_experts_ms", ctx) == pytest.approx(350e-6)
    assert _read("scope_unattributed_pct", ctx) == pytest.approx(100 * (50 + 90) / STEP_NS)
    # the rows add up to the module's busy time, nesting or not
    assert t.total_s == pytest.approx(2 * STEP_NS * 1e-9) == pytest.approx(r.busy_s)
    assert "hvd_forward/hvd_mlp/hvd_moe_experts/hvd_moe_gmm_down forward" in said[0]


def test_the_parents_recorded_step_reads_no_sublayer_and_most_of_it_unscoped():
    """Three steps of bert-base-ft.s128-b32.dp1 on a v5e from the commit
    before the model named its parts (my chip run, PR 35)."""
    from jax.profiler import ProfileData
    r = xplane.reduce_profile(ProfileData.from_serialized_xspace(gzip.decompress(
        (DATA / "bert_b32_3steps_pr35.xplane.pb.gz").read_bytes())), chips=1)
    text = gzip.decompress((DATA / "bert_b32_3steps_pr35.hlo.txt.gz").read_bytes()).decode()
    ctx, _ = _ctx(r, text, steps=3)
    for metric in ("mlp_ms", "head_ms", "stem_stage0_ms"):
        assert _read(metric, ctx) is None
    assert 65 < _read("model_unscoped_pct", ctx) < 80


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_each_toy_step_names_what_its_cells_new_readers_sum(hvd, config):
    """The step each configuration's adapter builds, compiled at the toy
    sizes on the CPU: its text names every scope that a reader of this
    file sums in one of the configuration's cells, in the forward and the
    backward pass; the experts' scopes lie inside ``hvd_mlp`` and the
    trunk's attention scope is not opened around hybrid's."""
    import jax
    cells = {w["name"] for w in MANIFEST["workloads"] if w["config"] == config}
    wanted = {scope for m in MANIFEST["per_layer"] if m["name"] in READS
              and cells & set(m["workloads"]) for scope in READS[m["name"]]}
    assert wanted > {"hvd_forward"}
    cfg_dir = bench_tree.BENCH / "configs" / config
    cfg = bench_tree.load(cfg_dir / "config.json")
    cfg.update(cfg["toy"])
    cfg["dtype"]["compute"] = "float32"
    ref = registry.load_module(str(cfg_dir / "reference.py"))
    adapter = registry.load_module(str(cfg_dir / "adapter.py"))
    devices = jax.devices() if config == "bert-base-ft" else jax.devices()[:1]
    program = adapter.build(cfg, ref, devices, 2)
    key = jax.random.key(3)
    batch = program.place(ref.make_samples(cfg, key, program.global_batch))
    text = program.compiled(program.init(key), batch).as_text()
    where = {w[:2] for w in hlo.scopes(text).values()}
    found = {(part, p) for scope, p in where
             if scope.startswith("hvd_forward") for part in scope.split("/")}
    for scope in wanted:
        assert {(scope, "forward"), (scope, "backward")} <= found, scope
    chains = {scope for scope, _ in where}
    assert all("hvd_mlp" in c.split("/") for c in chains if "hvd_moe_" in c)
    assert not any("hvd_attention" in c and "hvd_diff_attention" in c for c in chains)
