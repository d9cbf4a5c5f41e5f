"""The table of peaks, keyed by the exact ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).  A device that is
not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud docs, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no peaks known for device kind {device_kind!r}; "
            f"add it to benchmark/harness/peaks.py with its source") from None
