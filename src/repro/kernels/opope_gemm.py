"""O-POPE GEMM as a Pallas TPU kernel.

This is the TPU-native embodiment of the paper's dataflow (DESIGN.md §3):

* **Output-stationary**: the fp32 accumulator tile lives in VMEM scratch for
  the whole K loop — the analogue of the paper's accumulator registers. It is
  written to the (HBM-backed) output window exactly once, on the last K step.
* **Outer-product K streaming**: the grid is ``(m, n, k)`` with ``k`` the
  innermost, ``arbitrary`` (sequential) dimension; each step performs a
  rank-``block_k`` panel update — the MXU generalization of the paper's
  rank-1 updates (a rank-1 grid step would starve the 128x128 MXU; the
  *dataflow* is identical, the panel width is sized to the unit).
* **Pipeline registers as buffers**: Mosaic's automatic multiple-buffering of
  the ``BlockSpec`` input streams plays the role of the FPU pipeline
  registers: A/B panels for step ``k+1`` are DMA'd while step ``k`` computes,
  with no explicitly managed buffers — the same "the pipeline is the buffer"
  insight, one level up the memory hierarchy.
* **Accumulator preload (C operand)**: like the paper's engine, the kernel can
  preload an initial C tile into the accumulator (``c=``). This fuses
  ``A @ B + C`` (residual adds, bias grids, K-split partial accumulation)
  into the GEMM epilogue with zero extra HBM round-trip.
* **Mixed precision**: inputs fp8/bf16/f32, accumulation always fp32
  (``preferred_element_type``), output cast configurable — mirroring the
  paper's FP8→FP16 / FP16→FP32 widening MAC configurations.

Block shapes are multiples of the TPU tile (8x128 lanes; 128-aligned MXU
dims). Shape padding is applied outside the ``pallas_call`` and reported via
:func:`padding_waste` — the software analogue of the paper's tile-quantization
utilization loss (§III-C).

:func:`opope_gemm_stacked` is the serving entry for a layer's weight inside a
stacked ``[L, K, N]`` parameter: a scalar-prefetched layer index picks the B
panels straight out of the stack, and the ragged K and N edges are handled
inside the kernel, so no slice or pad of the weight is ever written to HBM.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.kernels import epilogue as _ep

__all__ = [
    "opope_gemm",
    "opope_gemm_stacked",
    "default_block_shape",
    "validate_block_shape",
    "padding_waste",
    "VMEM_BUDGET_BYTES",
]

# VMEM working-set budget for one grid step: the resident fp32/int32
# accumulator tile plus double-buffered A/B panels must fit in roughly half
# of a core's 16 MiB VMEM (the other half is Mosaic's pipelining headroom) —
# the TPU analogue of the paper's 64 kB compute half of the TCDM. Shared by
# the heuristic below and the autotuner's candidate validation.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    """One (m, n, k) grid step: rank-block_k update of the resident tile."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _writeback():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gemm_preload_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, k_steps: int):
    """As :func:`_gemm_kernel` but the accumulator is preloaded from C —
    the paper's accumulator-preload path (Fig. 2/3). The C tile is either a
    full (bm, bn) block or a (1, bn) bias row broadcast down the M dimension
    at preload time (no [M, N] operand ever materializes in HBM)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(
            c_ref[...].astype(jnp.float32), acc_ref.shape
        )

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _writeback():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gemm_epilogue_kernel(*refs, k_steps: int, steps, has_c: bool):
    """Epilogue-fused grid step: the op pipeline runs on the resident fp32
    tile at writeback, before the single cast — the result never round-trips
    HBM between the GEMM and its post-ops.

    ``refs`` in pallas_call order: a, b, (c if ``has_c``), one ref per
    operand-taking epilogue step, o, acc scratch. Epilogue operand blocks are
    streamed by kind — (1, 1) scalar, (1, bn) row, (bm, bn) full — and
    broadcast against the tile inside :func:`repro.kernels.epilogue.apply_epilogue`.
    """
    a_ref, b_ref = refs[0], refs[1]
    idx = 3 if has_c else 2
    c_ref = refs[2] if has_c else None
    ep_refs = refs[idx:-2]
    o_ref, acc_ref = refs[-2], refs[-1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if c_ref is None:
            acc_ref[...] = jnp.zeros_like(acc_ref)
        else:
            acc_ref[...] = jnp.broadcast_to(
                c_ref[...].astype(jnp.float32), acc_ref.shape
            )

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _writeback():
        acc = _ep.apply_epilogue(
            acc_ref[...], steps, tuple(r[...] for r in ep_refs)
        )
        o_ref[...] = acc.astype(o_ref.dtype)


def default_block_shape(
    m: int, k: int, n: int, elem_bytes: int = 2
) -> Tuple[int, int, int]:
    """Pick (block_m, block_n, block_k) the way `core.tiling.choose_tile` does
    for the TCDM, with VMEM (16 MiB/core, ~half usable with double buffering)
    as the budget: C tile fp32 + double-buffered A/B panels must fit, MXU dims
    128-aligned, and block_k at least 2x the MXU side so the output tile swap
    hides under compute (the paper's K >= 2p condition, one level up)."""
    bm = min(256, max(128, 8 * math.ceil(m / 8) if m < 128 else 128))
    bn = min(256, 128 * max(1, math.ceil(min(n, 256) / 128)))
    bk = min(512, 128 * max(2, math.ceil(min(k, 512) / 128)))
    while (
        bm * bn * 4 + 2 * (bm * bk + bk * bn) * elem_bytes > VMEM_BUDGET_BYTES
        and bk > 128
    ):
        bk //= 2
    return bm, bn, bk


def validate_block_shape(
    bm: int,
    bn: int,
    bk: int,
    *,
    elem_bytes: int = 2,
    m_align: int = 8,
    budget_bytes: int = VMEM_BUDGET_BYTES,
) -> bool:
    """Whether ``(bm, bn, bk)`` is a legal O-POPE block shape on this kernel.

    The kernel's hard constraints, checked before any tuned tile (a table
    entry is untrusted input — hand-edited files, tables tuned for another
    kernel revision) is allowed near a ``pallas_call``:

    * ``bm`` positive and ``m_align``-aligned (8 = fp sublane tile; the int8
      kernels need 32),
    * ``bn``, ``bk`` positive multiples of 128 (MXU lane dimension),
    * accumulator tile + double-buffered A/B panels fit the VMEM budget.
    """
    if bm <= 0 or bn <= 0 or bk <= 0:
        return False
    if bm % m_align or bn % 128 or bk % 128:
        return False
    return bm * bn * 4 + 2 * (bm * bk + bk * bn) * elem_bytes <= budget_bytes


def padding_waste(m: int, k: int, n: int, bm: int, bn: int, bk: int) -> float:
    """Fraction of MACs wasted on pad — the paper's quantization loss."""
    mp = math.ceil(m / bm) * bm
    kp = math.ceil(k / bk) * bk
    np_ = math.ceil(n / bn) * bn
    return 1.0 - (m * k * n) / (mp * kp * np_)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m",
        "block_n",
        "block_k",
        "out_dtype",
        "interpret",
        "epilogue",
    ),
)
def opope_gemm(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: bool = False,
    epilogue: Tuple[str, ...] = (),
    epilogue_operands: Tuple[jax.Array, ...] = (),
) -> jax.Array:
    """``O = A @ B (+ C)`` with the O-POPE dataflow. a: [M,K], b: [K,N].

    ``epilogue`` names a pipeline of registered post-ops (static; see
    :mod:`repro.kernels.epilogue`) applied to the resident fp32 accumulator
    at writeback, before the single final cast; ``epilogue_operands`` carries
    one canonical-dense-shape array per operand-taking step — scalar ``(1,1)``,
    row ``(1,N)``, full ``(M,N)`` — streamed per-tile by kind.

    ``interpret=True`` runs the kernel body in the Pallas interpreter (CPU) —
    used for all correctness tests in this container; on a real TPU the same
    call lowers through Mosaic.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad GEMM shapes {a.shape} @ {b.shape}")
    m, k = a.shape
    _, n = b.shape
    out_dtype = jnp.dtype(out_dtype or a.dtype)

    bm, bn, bk = min(block_m, _rup(m, 8)), min(block_n, _rup(n, 128)), min(
        block_k, _rup(k, 128)
    )
    mp, kp, np_ = _rup(m, bm), _rup(k, bk), _rup(n, bn)
    a_p = _pad2(a, mp, kp)
    b_p = _pad2(b, kp, np_)
    k_steps = kp // bk

    grid = (mp // bm, np_ // bn, k_steps)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [a_p, b_p]
    if c is not None and c.shape not in ((n,), (m, n)):
        raise ValueError(f"C preload shape {c.shape} != {(n,)} or {(m, n)}")
    ep_specs, ep_operands = _streamed_operands(
        c, epilogue, epilogue_operands, m=m, n=n, mp=mp, np_=np_, bm=bm, bn=bn
    )
    in_specs += ep_specs
    operands += ep_operands
    if epilogue:
        kernel = functools.partial(
            _gemm_epilogue_kernel,
            k_steps=k_steps,
            steps=epilogue,
            has_c=c is not None,
        )
    elif c is not None:
        kernel = functools.partial(_gemm_preload_kernel, k_steps=k_steps)
    else:
        kernel = functools.partial(_gemm_kernel, k_steps=k_steps)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="opope_gemm",  # the kernel's op name in a profile
    )(*operands)
    return out[:m, :n]


def _streamed_operands(
    c, epilogue, epilogue_operands, *, m, n, mp, np_, bm, bn
):
    """BlockSpecs and operands for the C preload and the epilogue steps,
    padded to ``(mp, np_)``. The index maps ignore trailing scalar-prefetch
    refs, so both GEMM entries share them.

    C is an [N] bias streamed as one (1, bn) row per N tile and broadcast
    into the accumulator at preload — O(N) HBM traffic instead of an O(M*N)
    materialized operand — or a full [M, N] tile stream. Epilogue operands
    are blocked by kind: (1, 1) scalar, (1, bn) row, (bm, bn) full.
    Zero-pad is safe throughout: every built-in op maps 0 -> 0 on the pad
    region or the pad is sliced off before anyone reads it."""
    specs, operands = [], []
    row = pl.BlockSpec((1, bn), lambda i, j, kk, *_: (0, j))
    full = pl.BlockSpec((bm, bn), lambda i, j, kk, *_: (i, j))
    if c is not None:
        if c.ndim == 1:
            specs.append(row)
            operands.append(_pad2(c[None, :], 1, np_))
        else:
            specs.append(full)
            operands.append(_pad2(c, mp, np_))
    it = iter(epilogue_operands)
    for name in epilogue:
        kind = _ep.op_kind(name)
        if kind == "none":
            continue
        x = next(it)
        if kind == "scalar":
            specs.append(pl.BlockSpec((1, 1), lambda i, j, kk, *_: (0, 0)))
            operands.append(x.reshape(1, 1))
        elif kind == "row":
            specs.append(row)
            operands.append(_pad2(x.reshape(1, n), 1, np_))
        else:  # full
            specs.append(full)
            operands.append(_pad2(x.reshape(m, n), mp, np_))
    return specs, operands


def _gemm_stacked_kernel(
    layer_ref, *refs, k_steps: int, k_rem: int, steps, has_c: bool
):
    """One grid step of :func:`opope_gemm_stacked`: the epilogue kernel's
    preload, rank update and writeback, with a ragged K edge.

    ``layer_ref`` (the scalar-prefetched layer index) is read by the index
    maps only. When K is not a multiple of the K tile, the last K step holds
    ``k_rem`` real columns of the A panel and rows of the B panel; the rest
    of each panel is whatever the buffer held, and is zeroed before the dot.
    """
    del layer_ref
    a_ref, b_ref = refs[0], refs[1]
    c_ref = refs[2] if has_c else None
    ep_refs = refs[3 if has_c else 2:-2]
    o_ref, acc_ref = refs[-2], refs[-1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if c_ref is None:
            acc_ref[...] = jnp.zeros_like(acc_ref)
        else:
            acc_ref[...] = jnp.broadcast_to(
                c_ref[...].astype(jnp.float32), acc_ref.shape
            )

    def update(a, b):
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    if k_rem:
        @pl.when(k < k_steps - 1)
        def _inner():
            update(a_ref[...], b_ref[...])

        @pl.when(k == k_steps - 1)
        def _edge():
            a, b = a_ref[...], b_ref[...]
            a_col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
            b_row = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
            update(
                jnp.where(a_col < k_rem, a, jnp.zeros_like(a)),
                jnp.where(b_row < k_rem, b, jnp.zeros_like(b)),
            )
    else:
        update(a_ref[...], b_ref[...])

    @pl.when(k == k_steps - 1)
    def _writeback():
        acc = _ep.apply_epilogue(
            acc_ref[...], steps, tuple(r[...] for r in ep_refs)
        )
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m",
        "block_n",
        "block_k",
        "out_dtype",
        "interpret",
        "epilogue",
    ),
)
def opope_gemm_stacked(
    a: jax.Array,
    b: jax.Array,
    layer: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: bool = False,
    epilogue: Tuple[str, ...] = (),
    epilogue_operands: Tuple[jax.Array, ...] = (),
) -> jax.Array:
    """``O = A @ B[layer] (+ C)``; a: [M, K], b: [L, K, N], layer: int32.

    The same dataflow, C preload and epilogue lane as :func:`opope_gemm`,
    reading layer ``layer`` of a stacked weight in place: the layer index is
    scalar-prefetched and B's index map selects ``(layer, kk, j)``, so the
    kernel's DMAs read the stack directly and no ``[K, N]`` slice exists.
    The weight is never padded: the grid is ``cdiv(N, bn)`` by
    ``cdiv(K, bk)``, the last N block writes back only its real columns,
    and the last K step zeroes the panels' rows and columns beyond K. Only
    A and full-tile operands are padded, to whole M tiles. An out-of-range
    ``layer`` is clamped, as ``lax.dynamic_index_in_dim`` clamps.
    """
    if a.ndim != 2 or b.ndim != 3 or a.shape[1] != b.shape[1]:
        raise ValueError(f"bad stacked GEMM shapes {a.shape} @ {b.shape}")
    m, k = a.shape
    n_layers, _, n = b.shape
    if c is not None and c.shape not in ((n,), (m, n)):
        raise ValueError(f"C preload shape {c.shape} != {(n,)} or {(m, n)}")
    out_dtype = jnp.dtype(out_dtype or a.dtype)

    bm, bn, bk = min(block_m, _rup(m, 8)), min(block_n, _rup(n, 128)), min(
        block_k, _rup(k, 128)
    )
    mp = _rup(m, bm)
    k_steps = pl.cdiv(k, bk)
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, n_layers - 1)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk, l: (i, kk)),
        pl.BlockSpec((pl.Squeezed(), bk, bn), lambda i, j, kk, l: (l[0], kk, j)),
    ]
    ep_specs, ep_operands = _streamed_operands(
        c, epilogue, epilogue_operands, m=m, n=n, mp=mp, np_=n, bm=bm, bn=bn
    )
    kernel = functools.partial(
        _gemm_stacked_kernel,
        k_steps=k_steps,
        k_rem=k % bk,
        steps=epilogue,
        has_c=c is not None,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mp // bm, pl.cdiv(n, bn), k_steps),
            in_specs=in_specs + ep_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, l: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="opope_gemm",  # the kernel's op name in a profile
    )(layer.reshape(1), _pad2(a, mp, k), b, *ep_operands)
    return out[:m]


def _rup(x: int, mult: int) -> int:
    return mult * math.ceil(x / mult)


def _pad2(x: jax.Array, d0: int, d1: int) -> jax.Array:
    if x.shape == (d0, d1):
        return x
    return jnp.pad(x, ((0, d0 - x.shape[0]), (0, d1 - x.shape[1])))
