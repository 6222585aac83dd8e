"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A kind that is not listed is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (copied from ``bench.py``'s
``PEAK_BF16_FLOPS``, which stays as it is; see PERF.md, Open questions).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]
