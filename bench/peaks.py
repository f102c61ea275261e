"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  Source: Google Cloud documentation, "TPU
v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
A device that is not here is an error, never a default."""

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
