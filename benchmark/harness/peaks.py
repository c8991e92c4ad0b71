"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it. A device that is not here is an error,
never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip.
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "flops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            f"with its source to benchmark/harness/peaks.py")
    return PEAKS[device_kind]
