"""Bytes of every all-reduce in the compiled step (10^6 bytes a step and
chip), counted from ``compiled.as_text()``: exact, the same every run.
The op count goes on an earlier line."""
UNIT, LAYER, MOVES, SOURCE = "MB", "Gradient plane", "scaling_eff", "program_counter"

from harness import hlo


def read(ctx):
    if ctx.main.chips < 2:
        return None
    ops = hlo.all_reduces(ctx.hlo_text())
    ctx.say(f"all-reduce ops in the compiled step: {len(ops)}, largest "
            f"{max(ops)[0] if ops else 0} bytes")
    return sum(b for b, _ in ops) / 1e6
