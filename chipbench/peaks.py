"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` that JAX reports. A device missing here is an error."""
from __future__ import annotations

PEAKS = {
    # Google Cloud TPU v5e documentation ("TPU v5e"): per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py "
                       "with their source") from None
