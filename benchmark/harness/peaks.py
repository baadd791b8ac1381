"""Published peaks of the chips this benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  A kind that is not here is an error,
never priced as another chip.

"TPU v5 lite": Google Cloud documentation, "TPU v5e": 197 TFLOP/s in
bf16 (a multiply-add counts as two), 16 GB of HBM at 819 GB/s.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def for_kind(device_kind):
    if device_kind not in PEAKS:
        raise LookupError(
            f"no published peaks on record for device_kind {device_kind!r}; "
            f"add a row to benchmark/peaks.py with its source")
    return PEAKS[device_kind]
