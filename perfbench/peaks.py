"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
A device that is not in the table is an error, never a default: a share
of a peak against a guessed peak is not a measurement.
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"device_kind {device_kind!r} has no row in perfbench/peaks.py; "
            "add its published peaks, with their source, before measuring")
    return PEAKS[device_kind]
