"""Operations, bytes and least time of GEMMs, the same for every family.

Which GEMMs a layer runs, and the operations and bytes of a whole token,
prompt or model, belong to the configuration's family module
(``bench/reference/<family>.py``): its ``kernel_gemms`` lists the layer
GEMMs that run through the ``opope_gemm`` kernel, and only those, since
the trace reduction's GEMM time counts that kernel alone. Only real rows
count as work: a padded row or lane costs time and is never added to the
operations.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

BF16 = 2


class Gemm(NamedTuple):
    name: str
    k: int
    n: int
    extra_mn: int  # full [M, N] epilogue operands (residual, gate's mul)
    layers: int  # layers that run this GEMM in one pass over the model


def gemm_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, extra_mn: int = 0, itemsize: int = BF16) -> float:
    """A [m, k] and B [k, n] read once, C [m, n] written once, and each full
    epilogue operand [m, n] read once."""
    return float(itemsize) * (m * k + k * n + m * n * (1 + extra_mn))


def least_seconds(flops: float, nbytes: float, peaks) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks.bf16_flops
    t_m = nbytes / peaks.hbm_bytes_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def gemms_least_seconds(family, cfg: Dict, m: int, peaks) -> Tuple[float, Dict[str, float]]:
    """Least time of every kernel GEMM (``family.kernel_gemms``) of one pass
    over all layers at ``m`` real rows, each GEMM bounded on its own; and
    the seconds under each bound. GEMMs are summed per layer count, then
    multiplied by it."""
    per: Dict[int, Dict[str, float]] = {}  # layers -> seconds under each bound
    for g in family.kernel_gemms(cfg):
        t, bound = least_seconds(
            gemm_flops(m, g.k, g.n), gemm_bytes(m, g.k, g.n, g.extra_mn), peaks
        )
        by = per.setdefault(g.layers, {"total": 0.0, "compute": 0.0, "memory": 0.0})
        by["total"] += t
        by[bound] += t
    out = {"total": 0.0, "compute": 0.0, "memory": 0.0}
    for layers, by in per.items():
        for k, v in by.items():
            out[k] += v * layers
    total = out.pop("total")
    return total, out
