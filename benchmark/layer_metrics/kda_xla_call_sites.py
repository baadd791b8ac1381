"""Call sites of the chunked delta-rule scan that the timed program built
on XLA's own loop over the chunks (``hvd_kda_scan_total{path="xla"}``: the
same chunk functions under ``lax.scan``, where the shapes or the backend
keep the Pallas kernels off): 0 when every site runs ``hvd_kda_chunk_fwd``
/ ``hvd_kda_chunk_bwd``.  ``None`` where the program has no such
counter."""
UNIT, LAYER, MOVES, SOURCE = "count", "Kernels", "throughput", "program_counter"


def read(ctx):
    try:
        from horovod_tpu import metrics
    except ImportError:
        return None
    family = metrics.registry().to_dict().get("hvd_kda_scan_total")
    if not family:
        return None
    return sum(s["value"] for s in family.get("series", [])
               if s["labels"]["path"] == "xla")
