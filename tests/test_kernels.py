"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.opope_gemm import default_block_shape, opope_gemm, padding_waste
from repro.kernels.ref import reference_matmul

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


SHAPES = [
    (128, 256, 128),
    (64, 512, 128),
    (100, 200, 96),  # unaligned in every dim
    (33, 77, 130),
    (1, 128, 128),  # degenerate rows
    (256, 1, 64),  # K=1
]
DTYPES = [
    # (in, out, tol): bf16 output quantizes to ~2^-8 relative of |result|,
    # which for K=512 sums reaches ~0.15 absolute.
    (jnp.float32, jnp.float32, 1e-4),
    (jnp.bfloat16, jnp.float32, 5e-2),
    (jnp.bfloat16, jnp.bfloat16, 2e-1),
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("in_dt,out_dt,tol", DTYPES)
def test_gemm_matches_oracle(m, k, n, in_dt, out_dt, tol):
    a, b = _rand((m, k), in_dt), _rand((k, n), in_dt)
    got = opope_gemm(a, b, block_m=64, block_n=128, block_k=128,
                     out_dtype=out_dt, interpret=True)
    want = reference_matmul(a, b, out_dtype=out_dt)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _err(got, want) < tol


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (100, 200, 96)])
def test_gemm_c_preload(m, k, n):
    """The paper's accumulator-preload path: O = A@B + C fused."""
    a, b = _rand((m, k), jnp.float32), _rand((k, n), jnp.float32)
    c = _rand((m, n), jnp.float32)
    got = opope_gemm(a, b, c, block_m=64, block_n=128, block_k=128,
                     interpret=True)
    want = reference_matmul(a, b, c)
    assert _err(got, want) < 1e-4


def test_gemm_fp8_widening():
    """FP8 inputs with widening accumulation (paper's FP8->FP16 MAC)."""
    a = _rand((64, 128), jnp.float8_e4m3fn)
    b = _rand((128, 64), jnp.float8_e4m3fn)
    got = opope_gemm(a, b, out_dtype=jnp.bfloat16, block_m=64, block_n=64,
                     block_k=128, interpret=True)
    want = reference_matmul(a, b, out_dtype=jnp.bfloat16)
    assert _err(got, want) < 0.25  # fp8 quantization noise


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (100, 200, 96)])
def test_gemm_bias_row_preload(m, k, n):
    """[N] bias streams as one row per N tile and broadcasts at preload —
    no [M, N] C operand is ever materialized."""
    a, b = _rand((m, k), jnp.float32), _rand((k, n), jnp.float32)
    bias = _rand((n,), jnp.float32)
    got = opope_gemm(a, b, bias, block_m=64, block_n=128, block_k=128,
                     interpret=True)
    want = reference_matmul(a, b, bias)
    assert _err(got, want) < 1e-4


# The stacked-weight entry at every layer of a small [L, K, N] stack, with
# K and N ragged at 128-wide tiles (the interpreter fills the out-of-range
# parts of each edge panel with NaN, so an unmasked edge shows), under each
# C preload and epilogue the models use: (C kind, epilogue steps).
STACKED = {
    "plain": (None, ()),
    "bias_preload": ("row", ()),
    "c_preload": ("full", ()),
    "silu_mul": (None, ("silu", "mul")),
    "bias_silu_mul": ("row", ("silu", "mul")),
    "residual": (None, ("residual",)),
    "bias_epilogue": (None, ("bias",)),
}


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(STACKED))
def test_stacked_gemm_matches_oracle(case, dt):
    from repro.kernels.opope_gemm import opope_gemm_stacked

    n_layers, m, k, n = 3, 5, 200, 300
    c_kind, steps = STACKED[case]
    a, b = _rand((m, k), dt), _rand((n_layers, k, n), dt)
    c = {None: None, "row": _rand((n,), dt), "full": _rand((m, n), dt)}[c_kind]
    ep_ops = {"mul": _rand((m, n), dt), "residual": _rand((m, n), dt),
              "bias": _rand((1, n), dt)}
    operands = tuple(ep_ops[s] for s in steps if s in ep_ops)
    tol = 1e-4 if dt == jnp.float32 else 2e-1
    for layer in range(n_layers):
        got = opope_gemm_stacked(
            a, b, jnp.int32(layer), c, block_m=128, block_n=128,
            block_k=128, interpret=True, epilogue=steps,
            epilogue_operands=operands,
        )
        acc = reference_matmul(a, b[layer], c, out_dtype=jnp.float32)
        for s in steps:
            acc = {
                "silu": lambda x: x * jax.nn.sigmoid(x),
                "mul": lambda x: x * ep_ops["mul"].astype(jnp.float32),
                "residual": lambda x: x + ep_ops["residual"].astype(jnp.float32),
                "bias": lambda x: x + ep_ops["bias"].astype(jnp.float32),
            }[s](acc)
        want = acc.astype(dt)
        assert got.shape == (m, n) and got.dtype == want.dtype
        assert _err(got, want) < tol, (case, layer)


def test_linear_bias_grad_is_column_sum():
    ops.set_default_backend("pallas_interpret")
    try:
        x = _rand((4, 8, 64), jnp.float32)
        w = _rand((64, 48), jnp.float32)
        bias = _rand((48,), jnp.float32)
        f = lambda x, w, b: jnp.sum(ops.linear(x, w, b) ** 2)
        gx, gw, gb = jax.grad(f, argnums=(0, 1, 2))(x, w, bias)
        f2 = lambda x, w, b: jnp.sum((jnp.einsum("bsk,kn->bsn", x, w) + b) ** 2)
        gx2, gw2, gb2 = jax.grad(f2, argnums=(0, 1, 2))(x, w, bias)
        assert _err(gx, gx2) < 1e-2
        assert _err(gw, gw2) < 1e-2
        assert _err(gb, gb2) < 1e-3
    finally:
        ops.set_default_backend("auto")


def test_ops_linear_bias_via_preload():
    ops.set_default_backend("pallas_interpret")
    try:
        x = _rand((4, 32, 64), jnp.float32)
        w = _rand((64, 48), jnp.float32)
        bias = _rand((48,), jnp.float32)
        y = ops.linear(x, w, bias)
        want = np.einsum("bsk,kn->bsn", np.asarray(x), np.asarray(w)) + np.asarray(bias)
        assert float(np.max(np.abs(np.asarray(y) - want))) < 1e-4
    finally:
        ops.set_default_backend("auto")


def test_ops_vjp_matches_xla_grads():
    ops.set_default_backend("pallas_interpret")
    try:
        a = _rand((32, 64), jnp.float32)
        w = _rand((64, 48), jnp.float32)
        f = lambda a, w: jnp.sum(ops.matmul(a, w) ** 2)
        ga, gw = jax.grad(f, argnums=(0, 1))(a, w)
        f2 = lambda a, w: jnp.sum((a @ w) ** 2)
        ga2, gw2 = jax.grad(f2, argnums=(0, 1))(a, w)
        assert _err(ga, ga2) < 1e-2 and _err(gw, gw2) < 1e-2
    finally:
        ops.set_default_backend("auto")


def test_xla_backend_bitwise_matches_reference():
    a, b = _rand((64, 128), jnp.bfloat16), _rand((128, 32), jnp.bfloat16)
    got = ops.matmul(a, b, backend="xla")
    want = reference_matmul(a, b)
    assert _err(got, want) == 0.0


def test_padding_waste_mirrors_paper_quantization():
    # aligned: no waste; ragged: waste matches closed form
    assert padding_waste(256, 512, 256, 128, 128, 128) == 0.0
    w = padding_waste(100, 200, 96, 64, 128, 128)
    assert 0 < w < 1
    bm, bn, bk = default_block_shape(1024, 4096, 1024)
    assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
