"""Model FLOP utilisation over the whole window: samples per second per
chip times the FLOPs a sample needs (flops.py: by shapes, two per
multiply-add, nothing recomputed) over the chip's bf16 peak."""
UNIT, LAYER, MOVES, SOURCE = "%", "Model", "throughput", "host_clock"


def read(ctx):
    return (100.0 * ctx.main.samples_per_s_per_chip * ctx.flops_per_sample
            / ctx.peaks["bf16_flops_per_s"])
