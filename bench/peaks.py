"""Peak rates of each accelerator the benchmark runs on, keyed by JAX's
``device_kind``.  A device kind that is not here is an error: no rate is
ever assumed.

TPU v5e ("TPU v5 lite" to JAX): 197e12 bf16 FLOP/s and 819e9 B/s of HBM
bandwidth per chip, 16 GB of HBM — Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
