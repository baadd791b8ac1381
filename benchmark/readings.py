"""Readings for the check's limits, many seeds in one process.

    python benchmark/readings.py --workload <name> --seeds 1,2,3 [--control N]

For each seed: the program's first three steps (the same code path a run
takes) against the plain float32 reference, every number compared printed
with the leaf it was worst at.  With ``--control N`` the first N seeds
also read the control: the reference itself computed with fp8 (e4m3)
operands, put in the program's place.  A limit belongs above the sound
runs' largest reading and below the control's smallest (PERF.md has the
table these printed).  Not part of a benchmark run.
"""

import argparse
import json
import os

import run
from harness import check, registry


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--dump", help="append every norm and loss read, as JSON "
                                   "lines, for limits to be worked out from")
    args = ap.parse_args(argv)
    manifest = registry.load_json(os.path.join(run.REPO, "BENCHMARK.json"))
    cell = registry.load_cell(run.BENCH_DIR, manifest, args.workload)

    jax, _ = run.start_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        raise SystemExit(f"{args.workload} needs {cell.chips} TPU chip(s)")
    phases = run.build_phases(jax, cell, devices)
    for p in phases:
        devs = devices[:p.program.chips]
        p.reference = check.Reference(cell.reference, cell.config, devs)
        p.control = check.Reference(cell.reference, cell.config, devs,
                                    quant=check.quant_fp8)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        wkey, dkey = run.seed_keys(jax, seed)
        for p in phases:
            got = jax.device_get(run.first_steps(jax, cell, p, wkey, dkey))
            run.free(jax, p.state, p.feed.batches)
            batches = [p.feed.samples(k) for k in range(check.STEPS)]
            ref = p.reference.run(wkey, batches)
            row = {"seed": seed, "phase": p.name,
                   "program": check.compare(got, ref)}
            raw = {"got": got, "ref": ref}
            if i < args.control:
                raw["control"] = p.control.run(wkey, batches)
                row["control"] = check.compare(raw["control"], ref)
            run.say("reading", json.dumps(row))
            if args.dump:
                with open(args.dump, "a") as f:
                    f.write(json.dumps({"seed": seed, "phase": p.name,
                                        **jax.tree_util.tree_map(float, raw)}) + "\n")


if __name__ == "__main__":
    main()
