"""Call sites of the chunked state-space scan that the timed program built
on XLA's own products (``hvd_ssd_kernel_total{path="xla"}``: the chunked
form in jax.numpy, where the shapes or the backend keep the Pallas
kernels off): 0 when every site runs ``hvd_ssd_chunk_fwd`` /
``hvd_ssd_chunk_bwd``.  ``None`` where the program has no such counter."""
UNIT, LAYER, MOVES, SOURCE = "count", "Kernels", "throughput", "program_counter"


def read(ctx):
    try:
        from horovod_tpu import metrics
    except ImportError:
        return None
    family = metrics.registry().to_dict().get("hvd_ssd_kernel_total")
    if not family:
        return None
    return sum(s["value"] for s in family.get("series", [])
               if s["labels"]["path"] == "xla")
