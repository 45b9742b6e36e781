"""Peaks of the chips the benchmark may run on, keyed by `device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
HBM at 819 GB/s per chip. A device that is not in the table is an error,
never a default: a utilisation against a guessed peak means nothing.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"perfbench/peaks.py with its source")
    return PEAKS[device_kind]
