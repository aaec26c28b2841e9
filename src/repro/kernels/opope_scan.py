"""State-resident chunked linear scan (Pallas) — the mamba/xLSTM recurrence.

Beyond-paper kernel (§Perf, jamba hillclimb): the jnp chunked scan
materializes [chunk, D] discretized tensors in HBM at every associative-scan
stage — the dominant HBM-traffic term of jamba's train cell before the fix.
Here the recurrence state is the output-stationary accumulator held in VMEM
scratch across grid steps (the O-POPE discipline), and each grid step
consumes one chunk panel of (decay, update) inputs:

    h[t] = decay[t] * h[t-1] + update[t]

The kernel emits all states (needed by the SSM output projection). Chunks
are the grid's ``arbitrary`` dimension, so Mosaic pipelines panel DMAs
behind the VPU exactly as it pipelines GEMM panels behind the MXU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

__all__ = ["opope_chunked_scan"]


def _scan_kernel(a_ref, b_ref, o_ref, h_ref, *, chunk: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        # Row t is read from the refs (pl.ds), not by indexing a loaded
        # [chunk, D] value: Mosaic has no dynamic_slice on values.
        row = pl.ds(t, 1)
        h = (
            a_ref[row, :].astype(jnp.float32) * h
            + b_ref[row, :].astype(jnp.float32)
        )
        o_ref[row, :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def opope_chunked_scan(
    decay: jax.Array,
    update: jax.Array,
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """All-states linear scan. decay/update: [S, D] -> states [S, D] (f32)."""
    s, d = decay.shape
    ck = min(chunk, s)
    sp = ck * math.ceil(s / ck)
    a_p = jnp.pad(decay, ((0, sp - s), (0, 0)))
    b_p = jnp.pad(update, ((0, sp - s), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=ck),
        grid=(sp // ck,),
        in_specs=[
            pl.BlockSpec((ck, d), lambda j: (j, 0)),
            pl.BlockSpec((ck, d), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((ck, d), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(a_p, b_p)
    return out[:s]
