"""The share of the step's device time that lies in the model and in no
part of it: the rows of the scope table under ``hvd_forward`` alone, every
pass, over the table's total (harness/scopes).  With every sublayer named
(``hvd_embed``, ``hvd_attention``, ``hvd_mlp``, ``hvd_head``, the mixers,
``hvd_stem`` / ``hvd_stage<i>``) what is left is the residual adds, a
layer's first norm and weight casts where the model keeps them outside,
and what an ``objective=`` from outside computes; a model file that forgets
its names reads high (the parent of PR 36: 46-96)."""
UNIT, LAYER, MOVES, SOURCE = "%", "Model", "throughput", "device_trace"

from harness import scopes


def read(ctx):
    t = scopes.table(ctx)
    if t is None or not t.total_s:
        return None
    alone = sum(s for (scope, _), s in t.rows.items() if scope == "hvd_forward")
    return 100.0 * alone / t.total_s
