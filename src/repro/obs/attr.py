"""Live utilization attribution: from "GEMMs ran" to "GEMMs ran *this well*".

O-POPE's headline number is utilization (99.97% of FPU cycles doing useful
MACs), and PR 7's registry records *that* GEMMs ran — this module closes the
gap by scoring *how well*, continuously, on the serving hot loop instead of
only in offline benches.

The mechanics respect the zero-cost contract: per-call device timing is
impossible under ``jit`` (the registry entry points run once at trace time),
so attribution works at the granularity a real wall-clock bracket exists:

1. A timed span owner (the continuous-batching engine's decode step, a
   bench loop) traces its compiled function under :class:`capture_gemms`;
   ``kernels.ops`` appends one :class:`GemmRecord` per registry call it
   traced — shapes, actual dtypes, resolved backend, tile source.
2. :func:`aggregate` folds the records into a :class:`StepWorkload`:
   per-(backend, family, shape-bucket, tile-source) FLOP/byte totals costed
   with :mod:`repro.core.roofline` (``gemm_bytes`` at honest widths, the
   peaks of the device the process runs on; CPU runs score against the
   TPU-v5e reference).
3. Each subsequent execution of that compiled step calls
   :func:`observe_step` with its measured wall seconds. The step time is
   attributed to the workload entries in proportion to their roofline-bound
   seconds, yielding per-entry ``gemm.achieved_gflops`` and
   ``gemm.roofline_fraction`` histograms plus a ``gemm.device_seconds``
   counter — the ranking feed for ``repro-stats top``.

Every observation of a *tuned* entry is also forwarded to
``ops._note_util_observation`` — the drift side of the auto-retune seam:
``ops.on_util_gap`` fires for shapes the tuning table covers but that keep
underperforming the threshold (sibling of ``on_miss_streak``, which only
sees shapes the table *misses*).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.roofline import HardwareSpec, device_peaks, gemm_bytes

from . import metrics as _metrics

__all__ = [
    "GemmRecord",
    "WorkloadEntry",
    "StepWorkload",
    "capture_gemms",
    "record_call",
    "capturing",
    "shape_bucket",
    "aggregate",
    "observe_step",
    "GFLOPS_BUCKETS",
    "FRACTION_BUCKETS",
]

# GFLOP/s bucket edges: wide enough to cover CPU interpret-mode kernels
# (sub-GFLOP/s) through compiled TPU GEMMs (tens of TFLOP/s).
GFLOPS_BUCKETS: Tuple[float, ...] = (
    0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0, 300.0,
    1e3, 3e3, 1e4, 3e4, 1e5, 3e5,
)

# Roofline-fraction edges: log-spaced below 0.1 (CPU runs scored against the
# TPU-v5e reference live here) and fine near 1.0 (where the paper's claim
# lives).
FRACTION_BUCKETS: Tuple[float, ...] = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 0.001, 0.0025, 0.005,
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0,
)


@dataclasses.dataclass(frozen=True)
class GemmRecord:
    """One registry GEMM call as captured at trace time by ``kernels.ops``."""

    shape_family: str  # "dense" | "grouped"
    backend: str
    family: str  # numerics family: "fp" | "q8"
    m: int
    k: int
    n: int
    g: int  # 0 for dense
    a_dtype: str
    b_dtype: str
    out_dtype: str
    tile_source: str  # "tuned" | "heuristic"
    tile_key: Tuple  # ops.TileKey — opaque here, passed back on util gaps


def _pow2_bucket(x: int) -> int:
    """Round up to the next power of two (M varies with live batch size;
    bucketing it keeps label cardinality bounded on a serving process)."""
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


def shape_bucket(rec: GemmRecord) -> str:
    """Stable label for a GEMM shape class: M pow2-bucketed, K/N/G exact
    (weights don't change shape at runtime; the activation row count does)."""
    mb = _pow2_bucket(rec.m)
    if rec.shape_family == "grouped":
        return f"grouped:{rec.g}x{mb}x{rec.k}x{rec.n}"
    return f"dense:{mb}x{rec.k}x{rec.n}"


def _record_cost(
    rec: GemmRecord, hw: HardwareSpec
) -> Tuple[float, float, float]:
    """(flops, bytes, roofline_s) of one record at honest dtype widths."""
    groups = max(rec.g, 1)
    flops = 2.0 * rec.m * rec.k * rec.n * groups
    scale_elems = (rec.m + rec.n) if rec.family == "q8" else 0
    nbytes = groups * gemm_bytes(
        rec.m, rec.k, rec.n,
        a_dtype=rec.a_dtype, b_dtype=rec.b_dtype, out_dtype=rec.out_dtype,
        scale_elems=scale_elems,
    )
    roofline_s = max(flops / hw.peak_flops, nbytes / hw.hbm_bw)
    return flops, float(nbytes), roofline_s


@dataclasses.dataclass
class WorkloadEntry:
    """Aggregated cost of one (backend, family, bucket, tile) class."""

    backend: str
    family: str
    bucket: str
    tile_source: str
    calls: int = 0
    flops: float = 0.0
    bytes: float = 0.0
    roofline_s: float = 0.0
    tile_key: Optional[Tuple] = None  # one representative key for retune


# Keyed by (backend, family, bucket, tile_source).
StepWorkload = Dict[Tuple[str, str, str, str], WorkloadEntry]


# --------------------------------------------------------------------------
# Capture (fed by kernels.ops._note_gemm_call; mirrors ops.capture_shapes)
# --------------------------------------------------------------------------

_CAPTURE: List[list] = []


def capturing() -> bool:
    """Cheap guard ``kernels.ops`` checks before building a record."""
    return bool(_CAPTURE)


def record_call(rec: GemmRecord) -> None:
    for records in _CAPTURE:
        records.append(rec)


class capture_gemms:
    """Context manager collecting every :class:`GemmRecord` the registry
    emits while active. Nestable; tracing triggers the records, so wrapping
    a ``jit`` call captures exactly the GEMMs of that compiled step (and
    nothing on cache hits — which is the signal the serving engine uses to
    know *when* a step traced)."""

    def __enter__(self) -> List[GemmRecord]:
        self._records: List[GemmRecord] = []
        _CAPTURE.append(self._records)
        return self._records

    def __exit__(self, *exc):
        # Identity-based detach, as in ops.capture_shapes: equal contents
        # must not make one capture pop another's list.
        for i in range(len(_CAPTURE) - 1, -1, -1):
            if _CAPTURE[i] is self._records:
                del _CAPTURE[i]
                break
        return False


# --------------------------------------------------------------------------
# Aggregation + attribution
# --------------------------------------------------------------------------


def aggregate(
    records: Sequence[GemmRecord], *, hw: Optional[HardwareSpec] = None
) -> StepWorkload:
    """Fold captured records into per-class cost totals, roofline-costed
    against ``hw`` (default: the peaks of the device this process runs on,
    :func:`repro.core.roofline.device_peaks`)."""
    hw = hw or device_peaks()
    workload: StepWorkload = {}
    for rec in records:
        bucket = shape_bucket(rec)
        key = (rec.backend, rec.family, bucket, rec.tile_source)
        entry = workload.get(key)
        if entry is None:
            entry = workload[key] = WorkloadEntry(
                backend=rec.backend, family=rec.family, bucket=bucket,
                tile_source=rec.tile_source, tile_key=rec.tile_key,
            )
        flops, nbytes, roofline_s = _record_cost(rec, hw)
        entry.calls += 1
        entry.flops += flops
        entry.bytes += nbytes
        entry.roofline_s += roofline_s
    return workload


def observe_step(workload: StepWorkload, seconds: float) -> None:
    """Attribute one measured execution of ``workload`` to its entries.

    ``seconds`` (host-wall time of the compiled step) is split across the
    entries in proportion to their roofline-bound seconds — the best
    proportional estimate available without per-kernel device profiling —
    then each share scores its entry's ``gemm.achieved_gflops`` and
    ``gemm.roofline_fraction`` and accrues ``gemm.device_seconds``. Tuned
    entries additionally feed ``ops.on_util_gap`` drift detection.
    """
    if seconds <= 0.0 or not workload or not _metrics.enabled():
        return
    total_roofline = sum(e.roofline_s for e in workload.values())
    if total_roofline <= 0.0:
        return
    for entry in workload.values():
        share = entry.roofline_s / total_roofline
        attributed = seconds * share
        if attributed <= 0.0:
            continue
        achieved_gflops = entry.flops / attributed / 1e9
        fraction = entry.roofline_s / attributed
        labels = dict(
            backend=entry.backend, family=entry.family,
            bucket=entry.bucket, tile=entry.tile_source,
        )
        _metrics.histogram(
            "gemm.achieved_gflops", buckets=GFLOPS_BUCKETS, **labels
        ).observe(achieved_gflops)
        _metrics.histogram(
            "gemm.roofline_fraction", buckets=FRACTION_BUCKETS, **labels
        ).observe(fraction)
        _metrics.counter("gemm.device_seconds", **labels).inc(attributed)
        if entry.tile_key is not None:
            # Lazy import: ops imports repro.obs, so the reverse edge must
            # stay out of module scope.
            from repro.kernels import ops as _ops

            _ops._note_util_observation(
                entry.tile_key, fraction, entry.tile_source
            )
