"""Published peaks of the chips this benchmark knows, keyed by the exact
``device_kind`` JAX reports. A device that is not here is an error."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
    # 1,600 Gbit/s of chip-to-chip interconnect per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            "no published peaks for device_kind %r; add a row with its "
            "source to benchmarks/lib/peaks.py (known: %s)"
            % (device_kind, sorted(PEAKS)))
    return PEAKS[device_kind]
