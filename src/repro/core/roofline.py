"""Three-term roofline model for the dry-run artifacts (TPU v5e target).

Per the assignment brief, for each (architecture x shape x mesh) cell we
derive from the compiled module (all inputs per device, post-SPMD):

* compute term    = HLO_FLOPs / peak_FLOPs_per_chip
* memory term     = HLO_bytes / HBM_bandwidth_per_chip
* collective term = collective_bytes / ICI_link_bandwidth

(The brief's formulas divide totals by ``chips x per-chip-rate``; XLA's
``cost_analysis`` is already per device, so the division by chip count has
already happened.)

Hardware constants live in :data:`PEAKS_BY_DEVICE_KIND`, keyed by JAX's
``device_kind``; :func:`device_peaks` picks the entry for the device a run
is on.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

__all__ = [
    "HardwareSpec",
    "TPU_V5E",
    "PEAKS_BY_DEVICE_KIND",
    "device_peaks",
    "RooflineTerms",
    "roofline_terms",
    "model_flops",
    "dtype_width",
    "tensor_bytes",
    "gemm_bytes",
    "gemm_intensity",
]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float  # FLOP/s per chip (bf16)
    hbm_bw: float  # bytes/s per chip
    ici_link_bw: float  # bytes/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and 819 GB/s of
# HBM bandwidth per chip. The ICI figure is a per-link planning estimate.
TPU_V5E = HardwareSpec(
    name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9, ici_link_bw=50e9
)

# Per-chip peaks keyed by ``jax.Device.device_kind``. A v5e reports itself
# as "TPU v5 lite" (older runtimes: "TPU v5e").
PEAKS_BY_DEVICE_KIND: Dict[str, HardwareSpec] = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def device_peaks(device=None) -> HardwareSpec:
    """Peaks of ``device`` (default: the first JAX device).

    A TPU whose kind is not in :data:`PEAKS_BY_DEVICE_KIND` raises: scoring
    it against another chip's peaks would report a wrong utilization. A
    non-TPU device (the CPU test runs) is scored against the v5e as a
    labelled reference; nothing from it is a device number.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return TPU_V5E
    try:
        return PEAKS_BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for TPU device kind {device.device_kind!r}; "
            f"known: {sorted(PEAKS_BY_DEVICE_KIND)}"
        ) from None


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """Roofline seconds per term for one compiled step (per device)."""

    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    hw: HardwareSpec
    model_flops_per_device: Optional[float] = None  # 6*N*D / chips

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Roofline-model step time: the max of the three terms (full overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the roofline step time.

        ``model_flops / peak`` over the bound: 1.0 means every roofline-limited
        second does useful model math at peak. This is the reported perf score.
        """
        if not self.model_flops_per_device:
            return self.compute_s / self.bound_s if self.bound_s else 0.0
        return (self.model_flops_per_device / self.hw.peak_flops) / self.bound_s

    @property
    def useful_compute_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/dispatch/padding waste."""
        if not self.model_flops_per_device or not self.flops_per_device:
            return float("nan")
        return self.model_flops_per_device / self.flops_per_device

    def summary(self) -> Dict[str, object]:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "bound_s": self.bound_s,
            "roofline_fraction": self.roofline_fraction,
            "useful_compute_ratio": self.useful_compute_ratio,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
        }


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    collective_bytes_per_device: float,
    *,
    hw: HardwareSpec = TPU_V5E,
    model_flops_total: Optional[float] = None,
    n_chips: Optional[int] = None,
) -> RooflineTerms:
    model_per_dev = None
    if model_flops_total is not None and n_chips:
        model_per_dev = model_flops_total / n_chips
    return RooflineTerms(
        compute_s=flops_per_device / hw.peak_flops,
        memory_s=bytes_per_device / hw.hbm_bw,
        collective_s=collective_bytes_per_device / hw.ici_link_bw,
        flops_per_device=flops_per_device,
        bytes_per_device=bytes_per_device,
        collective_bytes_per_device=collective_bytes_per_device,
        hw=hw,
        model_flops_per_device=model_per_dev,
    )


# ---------------------------------------------------------------------------
# Dtype-aware byte accounting
# ---------------------------------------------------------------------------
#
# Every byte term derives its operand width from the ACTUAL dtype — never an
# assumed 4-byte word. With the mixed-precision subsystem a GEMM can stream
# int8 A/B panels against an fp32 C and a bf16 output in one call; assuming
# one width would overstate quantized traffic ~4x and make the reported
# arithmetic intensity (and therefore the memory roofline term) meaningless.

# Widths for string dtype names that numpy may not know without ml_dtypes.
_NAMED_WIDTHS = {
    "int8": 1, "uint8": 1, "bool": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1, "float8_e4m3": 1,
    "float8_e4m3fnuz": 1, "float8_e5m2fnuz": 1,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "float32": 4, "int32": 4, "uint32": 4,
    "float64": 8, "int64": 8, "uint64": 8,
}


def dtype_width(dtype) -> int:
    """Bytes per element of ``dtype`` (a dtype object, array dtype, or name)."""
    itemsize = getattr(dtype, "itemsize", None)
    if itemsize:
        return int(itemsize)
    name = str(getattr(dtype, "name", dtype))
    if name in _NAMED_WIDTHS:
        return _NAMED_WIDTHS[name]
    import numpy as np

    return int(np.dtype(dtype).itemsize)


def tensor_bytes(*arrays) -> int:
    """Total bytes of arrays (or ShapeDtypeStructs) at their ACTUAL dtypes."""
    total = 0
    for a in arrays:
        if a is None:
            continue
        size = getattr(a, "size", None)
        if size is None:
            size = 1
            for d in a.shape:
                size *= d
        total += int(size) * dtype_width(a.dtype)
    return total


def gemm_bytes(
    m: int,
    k: int,
    n: int,
    *,
    a_dtype,
    b_dtype=None,
    out_dtype=None,
    c_dtype=None,
    scale_elems: int = 0,
) -> int:
    """Minimal HBM traffic of one ``[M,K] @ [K,N] (+C) -> [M,N]`` GEMM:
    each operand read once, the output written once, each at its own width.

    ``scale_elems`` adds fp32 side-band elements (quantization scales —
    ``M + N`` for the per-row/per-channel q8 backends).
    """
    a_w = dtype_width(a_dtype)
    b_w = dtype_width(b_dtype if b_dtype is not None else a_dtype)
    o_w = dtype_width(out_dtype if out_dtype is not None else a_dtype)
    total = m * k * a_w + k * n * b_w + m * n * o_w
    if c_dtype is not None:
        total += m * n * dtype_width(c_dtype)
    return total + 4 * scale_elems


def gemm_intensity(m: int, k: int, n: int, **dtype_kw) -> float:
    """Arithmetic intensity (FLOPs/byte) of the GEMM at honest widths."""
    return (2.0 * m * k * n) / gemm_bytes(m, k, n, **dtype_kw)


def model_flops(
    n_params: int,
    tokens: int,
    *,
    kind: str = "train",
    n_params_active: Optional[int] = None,
) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference fwd), N = active params.

    For MoE models pass ``n_params_active`` (shared + routed*top_k experts plus
    dense layers); for decode shapes ``tokens`` is the global batch (one token
    per sequence per step).
    """
    n = n_params_active if n_params_active is not None else n_params
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens
