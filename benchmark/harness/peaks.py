"""Published peaks of the chips this benchmark has run on, by
``device_kind`` as JAX reports it. A kind that is not here is an error: a
share of a guessed peak is not a measurement."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,  # not the 393e12 of int8
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}: add it to "
            "benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]
