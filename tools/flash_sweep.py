"""Sweep flash-attention block sizes on the flagship bench config.

Usage (on the chip; one candidate process at a time, each holds it):

    python tools/flash_sweep.py [--steps 8] [--blocks 256,384,512,768]

Runs the bench.py llama_1b step once per (bq=bk) candidate and prints a
table — feeds the answer back into ops/flash_attention._block_sizes.
"""

import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--blocks", default="256,384,512,768")
    args = p.parse_args()

    results = {}
    for blk in [int(b) for b in args.blocks.split(",")]:
        cmd = [sys.executable, os.path.join(REPO, "tools", "perf_probe.py"),
               "--steps", str(args.steps), "--flash-block", str(blk)]
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=600, cwd=REPO)
        except subprocess.TimeoutExpired:
            results[blk] = "TIMEOUT"
            print(f"block {blk:4d}: TIMEOUT")
            continue
        line = next((ln for ln in out.stdout.splitlines()
                     if "tokens/s/chip" in ln), None)
        if line is None:
            tail = (out.stderr or out.stdout).strip().splitlines()[-3:]
            line = "FAILED: " + " | ".join(tail)
        results[blk] = line
        print(f"block {blk:4d}: {line}")
    best = max((b for b, l in results.items() if "tokens" in l),
               key=lambda b: float(results[b].split()[0]), default=None)
    if best is not None:
        print(f"best block: {best}")


if __name__ == "__main__":
    main()
