"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error, never
a default: a share of a peak that is not the device's own means nothing."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"benchmarks/harness/peaks.py with its source") from None
