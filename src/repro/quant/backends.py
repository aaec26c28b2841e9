"""Quantized GEMM backends, registered through ``repro.kernels.ops``.

Two execution paths, one numerics contract (int8 dynamic symmetric
quantization — per-row scales on A, per-output-channel scales on B — exact
int32 accumulation, scales and the optional C operand applied in fp32 at the
accumulator, single final cast):

* ``xla_q8``   — ``lax.dot_general`` on the int8 values with
  ``preferred_element_type=int32``; the portable reference, available
  everywhere.
* ``pallas_q8`` — the O-POPE kernel with int8 operand streams and an int32
  resident accumulator (:mod:`repro.quant.pallas_q8`): same outer-product
  dataflow, a quarter of the fp32 path's operand traffic. Off the TPU it
  degrades to ``pallas_q8_interpret`` (same body, CPU interpreter) and then
  ``xla_q8`` — never to a full-precision path, so a degraded quantized
  request keeps quantized numerics. On a TPU a compile refusal raises.

Because int32 accumulation of int8 products is exact (no reassociation
error), ``xla_q8`` and ``pallas_q8`` agree bit-for-bit on the accumulator and
to fp32 rounding on the output — asserted in tests.

Both register ``grad_backend="xla"``: a backward pass through a quantized
matmul runs full-precision fp32-accumulated GEMMs on the saved (unquantized)
residuals. That is the paper's "training still requires higher-precision
floating-point" rule, enforced structurally — no caller can accidentally
backpropagate through int8.

Each backend also registers its **grouped member** (``[G,M,K] @ [G,K,N]``,
served by :func:`repro.kernels.ops.grouped_matmul`) with **per-group scales**
(A per-(group, row), B per-(group, column)): quantization error inside group
``g`` is bounded by group ``g``'s own amax, so one outlier expert in an MoE
stack cannot crush every other expert's resolution.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.lax as lax
import jax.numpy as jnp

from repro.kernels import ops
from repro.obs import audit

from .pallas_q8 import opope_gemm_q8, opope_gemm_q8_grouped, q8_block_shape
from .quantize import quantize

__all__ = ["register_quant_backends"]


def _quantize_operands(a: jax.Array, b: jax.Array):
    """Dynamic per-row (A) / per-output-channel (B) int8 quantization.

    Row/column granularity is the finest that still factorizes out of the
    GEMM: ``C[m,n] = sa[m] * sb[n] * sum_k qa[m,k] * qb[k,n]``.
    """
    aq = quantize(a, "int8", axis=0)  # scale [M, 1]
    bq = quantize(b, "int8", axis=1)  # scale [1, N]
    return aq, bq


def _a_values_scale(a):
    """The (int8 values, [M, 1] fp32 scale) of the A operand.

    A **pre-quantized** activation (anything with ``.q``/``.scale`` — the
    product of an upstream ``requant_int8`` epilogue) skips the dynamic
    quantization pass entirely: its values are consumed as-is and its
    per-tensor (or per-row) scale is broadcast to the kernel's [M, 1]
    layout. This is the "no round trip" half of the re-quant lane — layer
    N's writeback already put A on the int8 grid.
    """
    if hasattr(a, "q") and hasattr(a, "scale"):
        q = a.q
        s = jnp.asarray(a.scale, jnp.float32)
        s = s.reshape(-1, 1) if s.size == q.shape[0] else s.reshape(1, 1)
        return q, jnp.broadcast_to(s, (q.shape[0], 1))
    aq = quantize(a, "int8", axis=0)
    return aq.q, aq.scale


def _quantize_grouped_operands(a: jax.Array, b: jax.Array):
    """Per-group dynamic quantization of a grouped operand pair.

    A [G, M, K] gets per-(group, row) scales [G, M, 1]; B [G, K, N] gets
    per-(group, column) scales [G, 1, N] — the grouped generalization of the
    2-D granularity: within each group the scale outer product still
    factorizes out of the GEMM, and no amax is shared across groups (one
    outlier expert must not crush every other expert's resolution).
    """
    aq = quantize(a, "int8", axis=(0, 1))  # scale [G, M, 1]
    bq = quantize(b, "int8", axis=(0, 2))  # scale [G, 1, N]
    return aq, bq


def _xla_q8(a, b, c, out_dtype):
    a_vals, a_scale = _a_values_scale(a)
    bq = quantize(b, "int8", axis=1)  # scale [1, N]
    acc = lax.dot_general(
        a_vals, bq.q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * (a_scale * bq.scale)
    if c is not None:
        out = out + c.astype(jnp.float32)  # [M, N] tile or [N] bias row
    return out.astype(out_dtype)


def _xla_q8_grouped(a, b, c, out_dtype):
    aq, bq = _quantize_grouped_operands(a, b)
    acc = lax.dot_general(
        aq.q, bq.q, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * (aq.scale * bq.scale)
    if c is not None:
        cf = c.astype(jnp.float32)
        out = out + (cf[:, None, :] if c.ndim == 2 else cf)
    return out.astype(out_dtype)


def _pallas_q8_fn(interpret: bool):
    name = "pallas_q8_interpret" if interpret else "pallas_q8"

    def run(a, b, c, out_dtype, ep_steps=(), ep_ops=()):
        a_vals, a_scale = _a_values_scale(a)
        bq = quantize(b, "int8", axis=1)
        # Through the registry's shared resolution path (tuning table first,
        # q8_block_shape heuristic second), keyed at itemsize=1 — the width
        # of the streamed panels, not the caller-visible dtype.
        bm, bn, bk = ops._tile_for(
            a_vals.shape[0], a_vals.shape[1], b.shape[1], 1,
            family="dense", backend=name,
        )
        return opope_gemm_q8(
            a_vals, a_scale, bq.q, bq.scale, c,
            block_m=bm, block_n=bn, block_k=bk,
            out_dtype=out_dtype, interpret=interpret,
            epilogue=ep_steps, epilogue_operands=ep_ops,
        )

    return run


def _pallas_q8_grouped_fn(interpret: bool):
    name = "pallas_q8_interpret" if interpret else "pallas_q8"

    def run(a, b, c, out_dtype, ep_steps=(), ep_ops=()):
        aq, bq = _quantize_grouped_operands(a, b)
        bm, bn, bk = ops._tile_for(
            a.shape[1], a.shape[2], b.shape[2], 1,
            family="grouped", groups=a.shape[0], backend=name,
        )
        return opope_gemm_q8_grouped(
            aq.q, aq.scale, bq.q, bq.scale, c,
            block_m=bm, block_n=bn, block_k=bk,
            out_dtype=out_dtype, interpret=interpret,
            epilogue=ep_steps, epilogue_operands=ep_ops,
        )

    return run


@functools.lru_cache(maxsize=None)
def _pallas_q8_compiles() -> bool:
    """Probe once whether the compiled int8 Pallas path lowers here (same
    rules as ``ops._pallas_compiles``: off the TPU "unavailable", on a TPU
    a compiler refusal raises)."""
    if ops._platform() != "tpu":
        return False
    a = jax.ShapeDtypeStruct((32, 128), jnp.int8)
    sa = jax.ShapeDtypeStruct((32, 1), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 128), jnp.int8)
    sb = jax.ShapeDtypeStruct((1, 128), jnp.float32)
    return ops._compile_probe(
        "int8 Pallas GEMM",
        lambda: opope_gemm_q8.lower(a, sa, b, sb, interpret=False),
    )


@functools.lru_cache(maxsize=None)
def _pallas_q8_grouped_compiles() -> bool:
    """Probe the compiled grouped int8 grid separately (per-member
    availability): off the TPU a grouped-only failure degrades
    grouped_matmul along the q8 chain without demoting the 2-D member."""
    if not _pallas_q8_compiles():
        return False
    ag = jax.ShapeDtypeStruct((2, 32, 128), jnp.int8)
    sag = jax.ShapeDtypeStruct((2, 32, 1), jnp.float32)
    bg = jax.ShapeDtypeStruct((2, 128, 128), jnp.int8)
    sbg = jax.ShapeDtypeStruct((2, 1, 128), jnp.float32)
    return ops._compile_probe(
        "grouped int8 Pallas GEMM",
        lambda: opope_gemm_q8_grouped.lower(ag, sag, bg, sbg, interpret=False),
    )


def register_quant_backends() -> None:
    """Register (or re-register) the quantized backends. Idempotent.

    Every member declares ``family="q8"`` and a fallback chain that stays
    inside the family (``xla_q8`` — the always-available terminal — falls
    back to the interpreter q8 kernel, never to a full-precision path), plus
    a grouped GEMM member with per-group scales.
    """
    ops.register_backend(
        "xla_q8", _xla_q8,
        fallback=("pallas_q8_interpret",),
        grad_backend="xla",
        grouped=_xla_q8_grouped,
        family="q8",
    )
    ops.register_backend(
        "pallas_q8",
        _pallas_q8_fn(interpret=False),
        available=_pallas_q8_compiles,
        fallback=("pallas_q8_interpret", "xla_q8"),
        grad_backend="xla",
        grouped=_pallas_q8_grouped_fn(interpret=False),
        grouped_available=_pallas_q8_grouped_compiles,
        family="q8",
        tile_fn=q8_block_shape,
        epilogue_fused=True,
    )
    ops.register_backend(
        "pallas_q8_interpret",
        _pallas_q8_fn(interpret=True),
        fallback=("xla_q8",),
        grad_backend="xla",
        grouped=_pallas_q8_grouped_fn(interpret=True),
        family="q8",
        tile_fn=q8_block_shape,
        epilogue_fused=True,
    )
    # Shadow-audit drift policy for the family (obs.audit, REPRO_AUDIT=N):
    # per-row/per-channel int8 keeps max error within a few quantization
    # steps of the reference's max magnitude — well under 5% on any real
    # activation/weight distribution. Breaching it means a wrong scale, an
    # overflow, or a kernel bug, not ordinary quantization noise.
    audit.set_policy("q8", rel_err=0.05)


register_quant_backends()
