"""Device time under ``hvd_head``, every pass, ms a step (harness/scopes
over hlo.scopes): the trunk's final norm, the head's product whole or in
chunks of ``loss_chunk`` rows (each chunk's logits again in the backward
pass), the log-sum-exp and the gradient summed into the head (the
embedding's where tied).  BERT's four labels and ResNet's ``fc`` open the
scope too and are microseconds: a row of the printed table, not listed
here.  None where the program opens no such scope (the parent of PR 36)."""
UNIT, LAYER, MOVES, SOURCE = "ms", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    return scopes.ms(ctx, scope="hvd_head")
