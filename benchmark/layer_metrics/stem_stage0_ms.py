"""Device time under ``hvd_stem`` and ``hvd_stage0``, every pass, ms a
step (harness/scopes over hlo.scopes): ResNet's conv1, its BN and the
max-pool, and conv2_x's three blocks.  About a fifth of the model's
multiply-adds (0.8 of 4.1 GMAC an image forward) on the 3- and 64-channel
convolutions and the largest activations: a share of the step well over a
fifth says the early layers run below the rest.  None where the program
opens neither scope (the parent of PR 36)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    parts = [scopes.ms(ctx, scope=s) for s in ("hvd_stem", "hvd_stage0")]
    return sum(p for p in parts if p) or None
