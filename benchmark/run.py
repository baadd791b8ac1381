"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process drives all of the cell's chips.  Order of a run: set-up (init,
weights on the device from the seed, the program's own step builder, the
first three steps that the check reads, warm-up), the measured window,
with ``--trace 1`` a short traced stretch, then the plain reference and
the comparison that decides ``correct``.  The last line of standard
output is the result object; everything else goes on earlier lines.
"""

import time

_T0 = time.perf_counter()       # process start, as near as python lets us

import argparse
import gc
import json
import math
import os
import shutil
import sys
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
for _p in (REPO, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import check, peaks, registry, window, xplane

TRACED_SECONDS = 2.0            # the traced stretch: about this long,
TRACED_STEPS = (10, 100)        # and within these counts of steps


def say(*parts):
    print(*parts, flush=True)


def seed_keys(jax, seed):
    """(weights key, data key) from any whole number, also one past 32
    signed bits."""
    seed = int(seed)
    root = jax.random.fold_in(jax.random.key(seed % 2 ** 31), seed // 2 ** 31)
    return jax.random.fold_in(root, 1), jax.random.fold_in(root, 2)


def start_jax():
    """jax with every program of a run sent to the persistent cache,
    however quick its compile (so that only a checkout's first run of a
    cell compiles), and ``hvd.init()``, which places that cache inside the
    checkout.  Returns (jax, the counter of programs lowered)."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import horovod_tpu as hvd
    compiles = CompileCounter(jax)
    hvd.init()
    return jax, compiles


class CompileCounter:
    """Counts every program jax lowers, found in the cache or not."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_, **__):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def device_report(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def replicas_identical(jax, state):
    """Every device's copy of every replicated leaf, bit for bit."""
    import numpy as np
    for leaf in jax.tree_util.tree_leaves(state):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if any(not np.array_equal(shards[0], s, equal_nan=True) for s in shards[1:]):
            return False
    return True


def free(jax, *trees):
    for leaf in jax.tree_util.tree_leaves(trees):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


def build_phases(jax, cell, devices):
    """The program's step for each phase of the cell, built by the
    program's own builder through the configuration's adapter."""
    adapter = registry.load_module(cell.adapter_path)
    return [types.SimpleNamespace(
        name=spec["name"], share=spec["share"],
        program=adapter.build(cell.config, cell.reference,
                              devices[:spec["chips"]],
                              cell.traffic["per_chip_batch"]))
        for spec in cell.phases]


def first_steps(jax, cell, phase, weights_key, data_key):
    """State from the seed, then the first three steps through the
    window's own loop and feed.  Leaves the state and the feed on the
    phase, to be handed to the window, and returns what the check
    compares (still on the device): the losses, the first gradient's norms
    as the optimizer got it, the norms of the parameters' change."""
    cfg, reference, program = cell.config, cell.reference, phase.program
    if not hasattr(phase, "grad_norms"):
        phase.grad_norms = jax.jit(lambda st: check.leaf_norms(
            program.first_gradient(st)))

        def delta(st, key):
            w0 = reference.make_weights(cfg, key)
            return check.leaf_norms(
                {k: v - w0[k] for k, v in program.params(st).items()})

        phase.update_norms = jax.jit(delta)
    phase.feed = window.Feed(cell.traffic, cfg, reference, program, data_key)
    norms = {}

    def after_step(k, state):
        if k == 0:
            norms["grad"] = phase.grad_norms(state)

    phase.state, first = window.drive(
        program, phase.feed, program.init(weights_key), steps=check.STEPS,
        after_step=after_step)
    return {"losses": first.losses, "grad_norms": norms["grad"],
            "update_norms": phase.update_norms(phase.state, weights_key)}


def run_cell(bench_dir, manifest_path, workload, seed, seconds, trace,
             t0=None, require_chip=True, keep_trace=False):
    """One run of one cell; returns the result object.  ``require_chip``
    False is for rehearsals and tests only: the line then names the device
    it ran on and carries no device number."""
    t0 = time.perf_counter() if t0 is None else t0
    manifest = registry.load_json(manifest_path)
    cell = registry.load_cell(bench_dir, manifest, workload)

    jax, compiles = start_jax()
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    if require_chip:
        if not on_chip:
            raise SystemExit(f"no TPU: jax found {devices[0].platform!r}")
        if len(devices) != cell.chips:
            raise SystemExit(f"{workload} needs {cell.chips} chip(s), "
                             f"jax found {len(devices)}")
    chip_peaks = peaks.for_kind(devices[0].device_kind if on_chip
                                else "TPU v5 lite")
    weights_key, data_key = seed_keys(jax, seed)
    cfg, reference, traffic = cell.config, cell.reference, cell.traffic

    phases = build_phases(jax, cell, devices)
    got = [first_steps(jax, cell, p, weights_key, data_key) for p in phases]
    for p in phases:
        p.state, _ = window.drive(p.program, p.feed, p.state,
                                  steps=traffic["warmup_steps"])
    got = jax.device_get(got)

    # ---- the window ------------------------------------------------------
    # What set-up built stays for good, so a full collection inside the
    # window has only the window's own garbage to walk: unfrozen, each one
    # walked jax's whole object graph for 114 ms, two to four times a
    # window (my chip run, PR 23), and moved throughput by a percent.
    gc.collect()
    gc.freeze()
    compiled_before = compiles.n
    setup_s = time.perf_counter() - t0
    for p in phases:
        p.state, p.window = window.drive(p.program, p.feed, p.state,
                                         seconds=seconds * p.share)
    compiles_in_window = compiles.n - compiled_before
    device = device_report(devices)
    main = phases[-1]
    for p in phases:
        say(f"window {p.name}: {len(p.window.stamps)} steps in "
            f"{p.window.seconds:.3f} s on {p.window.chips} chip(s), "
            f"batch {p.window.global_batch}, loss {p.window.losses[0]:.5f} -> "
            f"{p.window.losses[-1]:.5f}")
        say(f"window {p.name}: {p.window.host_summary()}")

    # ---- the traced stretch ---------------------------------------------
    reduced = traced = None
    if trace:
        step_s = float(main.window.seconds) / len(main.window.stamps)
        n = min(max(round(TRACED_SECONDS / step_s), TRACED_STEPS[0]), TRACED_STEPS[1])
        out = os.path.join(
            keep_trace if isinstance(keep_trace, str)
            else os.path.join(os.path.dirname(bench_dir), ".bench_out"),
            workload, "trace")
        shutil.rmtree(out, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the loop's own spans are enough
        jax.profiler.start_trace(out, profiler_options=options)
        try:
            main.state, traced = window.drive(main.program, main.feed,
                                              main.state, steps=n)
        finally:
            jax.profiler.stop_trace()
        reduced = xplane.reduce_dir(out, chips=main.program.chips)
        if not keep_trace:
            shutil.rmtree(out, ignore_errors=True)

    # ---- checks over the window -----------------------------------------
    failed = sum(not math.isfinite(x) for p in phases for x in p.window.losses)
    attempted = sum(len(p.window.losses) for p in phases)
    checks = {"losses finite": failed == 0,
              "no compile in the window": compiles_in_window == 0}
    if traffic.get("loss_must_fall"):
        checks["loss fell over the window"] = all(
            p.window.losses[-1] < p.window.losses[0] for p in phases)
    if main.program.chips > 1:
        checks["replicas bit-identical"] = replicas_identical(jax, main.state[0])
    # The allocator's counter leaves out what a program reserves for its
    # temporaries when it is loaded (a step fails to load exactly when
    # counter + temporaries pass the chip's limit: my chip run, PR 23), so
    # the peak is the counter's plus the step program's temporaries.
    compiled = main.program.compiled(main.state, main.feed.next())
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    say(f"memory: counter's peak {device['memory_peak_bytes']} + step "
        f"temporaries {temporaries}")
    device["memory_peak_bytes"] += temporaries
    if trace and keep_trace:        # the text that the trace's names are of
        with open(os.path.join(os.path.dirname(out), "step.hlo.txt"), "w") as f:
            f.write(compiled.as_text())

    ctx = types.SimpleNamespace(
        cell=cell, phases={p.name: p.window for p in phases}, main=main.window,
        setup_s=setup_s, compiles_in_window=compiles_in_window,
        flops_per_sample=cell.flops.train_flops_per_sample(cfg),
        flops=cell.flops, config=cfg, peaks=chip_peaks, trace=reduced,
        traced=traced, hlo_text=compiled.as_text, say=say)
    section = "per_layer" if trace else "end_to_end"
    sources = {}
    values = {}
    for m in registry.metrics_for(manifest, section, workload):
        value = registry.reader(bench_dir, "layer_metrics" if trace else "end_to_end",
                                m["name"])(ctx)
        if value is None:
            continue
        sources[m["name"]] = m["source"]
        values[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---- the reference, once the program's state is freed ---------------
    free(jax, [p.state for p in phases], [p.feed.batches for p in phases])
    correct = all(checks.values())
    for name, ok in checks.items():
        say(f"check {name}: {'ok' if ok else 'FAILED'}")
    t_ref = time.perf_counter()
    compared = {}
    for p, g in zip(phases, got):
        ref = check.Reference(
            reference, cfg, devices[:p.program.chips]).run(
                weights_key, [p.feed.samples(k) for k in range(check.STEPS)])
        numbers = check.compare(g, ref)
        limits = {**reference.LIMITS, **cell.limits.get(p.name, {})}
        for name, limit in limits.items():
            value, where = numbers[name]
            say(f"check {p.name} {name}: {value:.6g} (limit {limit:g}, "
                f"worst at {where})")
            compared[f"{p.name}.{name}"] = {
                "value": value if math.isfinite(value) else 1e30, "limit": limit}
        say(f"check {p.name} losses: program {g['losses']} reference {ref['losses']}")
        correct = correct and check.within(numbers, limits)
    say(f"reference took {time.perf_counter() - t_ref:.2f} s (not in setup_s)")

    if not on_chip:
        # a rehearsal: counts stay, no time or rate goes under a device
        # metric's name
        for name, m in values.items():
            if sources[name] != "program_counter":
                m["value"] = None
    if reduced is not None and on_chip:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": values, "device": device,
              "checks": checks}
    if reduced is not None and on_chip:
        result["breakdown"] = reduced.breakdown()
    result["compared"] = compared       # each number beside its limit, last
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", nargs="?", const=True, default=False,
                    metavar="DIR",
                    help="leave the .xplane.pb under DIR/<workload>/trace and "
                         "the compiled step's text beside it as step.hlo.txt, "
                         "DIR .bench_out unless given (how the tests' recorded "
                         "traces were made)")
    args = ap.parse_args(argv)
    result = run_cell(BENCH_DIR, os.path.join(REPO, "BENCHMARK.json"),
                      args.workload, args.seed, args.seconds, bool(args.trace),
                      t0=_T0, keep_trace=args.keep_trace)
    # each number compared beside its limit, also at the end of standard error
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']:.6g} limit {c['limit']:g}",
              file=sys.stderr, flush=True)
    say(json.dumps(result))


if __name__ == "__main__":
    main()
