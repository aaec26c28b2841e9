"""Per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and
819 GB/s of HBM bandwidth per chip, 16 GB of HBM. A v5e reports itself as
"TPU v5 lite" (older runtimes: "TPU v5e"). A kind that is not in the table
is an error: scoring a chip against another chip's peaks gives a wrong
share.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    name: str
    bf16_flops: float  # FLOP/s per chip
    hbm_bytes_s: float  # bytes/s per chip
    hbm_bytes: float  # bytes per chip


TPU_V5E = Peaks("tpu-v5e", bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9)

PEAKS_BY_DEVICE_KIND = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS_BY_DEVICE_KIND)}"
        ) from None
