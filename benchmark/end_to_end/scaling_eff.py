"""Per-chip rate of the main phase over the per-chip rate of the
one-chip phase of the same run, same per-chip batch: weak scaling, both
read on one machine in one process."""


def read(ctx):
    base = ctx.phases.get("base")
    if base is None:
        return None
    return ctx.main.samples_per_s_per_chip / base.samples_per_s_per_chip
