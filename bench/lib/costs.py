"""Operations and bytes of the model's GEMMs and of a whole step, computed
from the configuration's own shapes.

The GEMMs of one decoder layer are the ones the served model runs through
its kernels: the Q, K and V projections, the attention output projection
(with the residual added in its epilogue), the MLP up projection, the gate
projection (with ``silu(.) * up`` in its epilogue) and the down projection
(with the residual). The LM head is counted in the step's operations, not
among the kernel GEMMs. Only real rows count as work: a padded row or lane
costs time and is never added to the operations.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

BF16 = 2


class Gemm(NamedTuple):
    name: str
    k: int
    n: int
    extra_mn: int  # full [M, N] epilogue operands (residual, gate's mul)


def layer_gemms(cfg: Dict) -> List[Gemm]:
    d, hd = cfg["d_model"], cfg["head_dim"]
    q, kv, ff = cfg["n_heads"] * hd, cfg["n_kv"] * hd, cfg["d_ff"]
    return [
        Gemm("q", d, q, 0),
        Gemm("k", d, kv, 0),
        Gemm("v", d, kv, 0),
        Gemm("o", q, d, 1),
        Gemm("up", d, ff, 0),
        Gemm("gate", d, ff, 1),
        Gemm("down", ff, d, 1),
    ]


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


def gemms_least_seconds(cfg: Dict, m: int, peaks) -> Tuple[float, Dict[str, float]]:
    """Least time of every kernel GEMM of one pass over all layers at ``m``
    real rows, each GEMM bounded on its own; and the seconds under each
    bound."""
    total, by_bound = 0.0, {"compute": 0.0, "memory": 0.0}
    for g in layer_gemms(cfg):
        t, bound = least_seconds(
            gemm_flops(m, g.k, g.n), gemm_bytes(m, g.k, g.n, g.extra_mn), peaks
        )
        total += t
        by_bound[bound] += t
    layers = cfg["n_layers"]
    return total * layers, {k: v * layers for k, v in by_bound.items()}


def matmul_params_per_layer(cfg: Dict) -> int:
    return sum(g.k * g.n for g in layer_gemms(cfg))


def token_flops(cfg: Dict, context: int, lm_head: bool) -> float:
    """Operations of one token through the model: every matmul, attention
    over ``context`` positions (scores and the weighted sum), and the LM
    head where the token produces logits."""
    f = 2.0 * cfg["n_layers"] * matmul_params_per_layer(cfg)
    f += 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * context
    if lm_head:
        f += 2.0 * cfg["vocab"] * cfg["d_model"]
    return f


def prompt_flops(cfg: Dict, plen: int) -> float:
    """A whole prompt prefilled: token ``p`` attends over ``p + 1``
    positions; logits only for the last token."""
    base = 2.0 * cfg["n_layers"] * matmul_params_per_layer(cfg) * plen
    attn = 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * (
        plen * (plen + 1) / 2.0
    )
    return base + attn + 2.0 * cfg["vocab"] * cfg["d_model"]


def weight_bytes(cfg: Dict) -> float:
    """Bytes of the served bf16 weights (norm parameters are f32)."""
    d, v, layers = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    mats = layers * matmul_params_per_layer(cfg) + (1 if cfg["tie_embeddings"] else 2) * v * d
    norms = (2 * layers + 1) * d * (2 if cfg["norm"] == "layernorm" else 1)
    return BF16 * mats + 4 * norms


def kv_bytes_per_token(cfg: Dict) -> int:
    return cfg["n_layers"] * 2 * cfg["n_kv"] * cfg["head_dim"] * BF16
