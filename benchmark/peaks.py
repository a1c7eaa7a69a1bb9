"""Published per-chip peaks, keyed by JAX's ``device_kind``.  A device that is
not here is an error, not a default.  The benchmark's own table: the yardstick
is under ``paths`` and moves with no file of the program."""

DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s of HBM bandwidth,
    # 197 TFLOP/s in bf16, 16 GB of HBM per chip.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
                    "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.py with its source "
            f"(known: {sorted(DEVICE_PEAKS)})") from None
