"""Compile the main path's kernels for a described TPU v5e, at chatglm3-6b
widths.

Nothing runs: the TPU compiler installed beside JAX compiles for a chip that
is described, not attached, and refuses what Mosaic would refuse on the
chip (unaligned slices, primitives it cannot lower, VMEM overruns, programs
that do not fit HBM). Each test asserts that the compiled program holds a
Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around these
compiles (an entry compiled for a described chip cannot be read back here).
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

GiB = 1 << 30
CFG = get_config("chatglm3-6b")
D, FF = CFG.d_model, CFG.d_ff
QKV_N = (CFG.n_heads + 2 * CFG.n_kv) * CFG.head_dim_
DECODE_M, PREFILL_M = 4, 2048  # 4 decode slots; a 4 x 512 prompt bucket
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_pallas(monkeypatch):
    """Steer ``pallas`` to the compiled kernels on this CPU host: its probe
    answers "available", so the registry lowers Mosaic kernels instead of
    degrading to the interpreter."""
    import dataclasses

    b = ops._REGISTRY["pallas"]
    monkeypatch.setitem(
        ops._REGISTRY, "pallas",
        dataclasses.replace(
            b, available=lambda: True, grouped_available=lambda: True
        ),
    )


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _assert_kernel(compiled, at_least=1):
    n = compiled.as_text().count("tpu_custom_call")
    assert n >= at_least, f"{n} Mosaic kernels in the compiled program"


# (name, K, N, epilogue kind): the chatglm3-6b GEMMs of one layer.
GEMMS = [
    ("qkv", D, QKV_N, None),
    ("qkv_bias", D, QKV_N, "bias"),
    ("mlp_gate_silu_mul", D, FF, "silu_mul"),
    ("mlp_down_residual", FF, D, "residual"),
    ("attn_out_residual", D, D, "residual"),
]


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M], ids=["decode", "prefill"])
@pytest.mark.parametrize("name,k,n,ep", GEMMS, ids=[g[0] for g in GEMMS])
def test_gemm_compiles(one_chip, compiled_pallas, m, name, k, n, ep):
    a, b = _spec((m, k), BF16, one_chip), _spec((k, n), BF16, one_chip)
    if ep is None:
        fn, specs = (lambda a, b: ops.matmul(a, b, backend="pallas")), (a, b)
    elif ep == "bias":
        fn = lambda a, b, c: ops.matmul(a, b, c, backend="pallas")  # noqa: E731
        specs = (a, b, _spec((n,), BF16, one_chip))
    elif ep == "silu_mul":
        fn = lambda a, b, up: ops.matmul(  # noqa: E731
            a, b, backend="pallas", epilogue=["silu", ("mul", up)])
        specs = (a, b, _spec((m, n), BF16, one_chip))
    else:
        fn = lambda a, b, r: ops.matmul(  # noqa: E731
            a, b, backend="pallas", epilogue=[("residual", r)])
        specs = (a, b, _spec((m, n), BF16, one_chip))
    _assert_kernel(_compile(fn, *specs))


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M], ids=["decode", "prefill"])
def test_backward_gemms_compile(one_chip, compiled_pallas, m):
    """The custom_vjp backward of the MLP up projection: dA = dO @ B^T
    ([M, FF] @ [FF, D]) and dB = A^T @ dO ([D, M] @ [M, FF])."""

    def loss(a, b):
        return ops.matmul(a, b, backend="pallas").astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=(0, 1))
    compiled = _compile(
        grad, _spec((m, D), BF16, one_chip), _spec((D, FF), BF16, one_chip)
    )
    # jax.grad drops the forward GEMM (only the gradients are returned)
    _assert_kernel(compiled, at_least=2)


def test_grouped_gemm_compiles(one_chip):
    from repro.kernels.opope_grouped import opope_gemm_grouped

    fn = lambda a, b: opope_gemm_grouped(a, b, interpret=False)  # noqa: E731
    _assert_kernel(_compile(
        fn, _spec((4, 256, D), BF16, one_chip), _spec((4, D, 1024), BF16, one_chip)
    ))


def test_q8_gemm_compiles(one_chip):
    from repro.quant.pallas_q8 import opope_gemm_q8

    fn = lambda a, sa, b, sb: opope_gemm_q8(  # noqa: E731
        a, sa, b, sb, block_m=32, block_n=256, block_k=512, interpret=False)
    _assert_kernel(_compile(
        fn,
        _spec((32, D), jnp.int8, one_chip),
        _spec((32, 1), jnp.float32, one_chip),
        _spec((D, QKV_N), jnp.int8, one_chip),
        _spec((1, QKV_N), jnp.float32, one_chip),
    ))


@pytest.mark.parametrize("s,t", [(512, 512), (1, 512)], ids=["prefill", "decode"])
def test_attention_compiles(one_chip, s, t):
    from repro.kernels.opope_attention import opope_attention

    hd = CFG.head_dim_
    fn = lambda q, k, v: opope_attention(q, k, v, interpret=False)  # noqa: E731
    _assert_kernel(_compile(
        fn, _spec((s, hd), BF16, one_chip), _spec((t, hd), BF16, one_chip),
        _spec((t, hd), BF16, one_chip),
    ))


def test_chunked_scan_compiles(one_chip):
    from repro.kernels.opope_scan import opope_chunked_scan

    fn = lambda a, b: opope_chunked_scan(a, b, interpret=False)  # noqa: E731
    _assert_kernel(_compile(
        fn, _spec((512, D), jnp.float32, one_chip),
        _spec((512, D), jnp.float32, one_chip),
    ))


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_chatglm3_step_compiles_and_fits(one_chip, compiled_pallas, step):
    """The whole 28-layer chatglm3-6b serving step, as the continuous engine
    runs it (32 slots of 2048, the chat cell's pool; a 4 x 512 prefill
    bucket), on the compiled kernels: parameters plus temporaries fit one
    v5e's 16 GiB. The decode step's temporaries stay below the 2.24 GB they
    took while each layer's weights were copied out of the stack before its
    GEMMs (compile for a described v5e)."""
    from repro.models import api

    assert ops.resolve_backend("auto") == "pallas"

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(
        functools.partial(api.init_params, CFG), jax.random.key(0)))
    i32 = jnp.int32
    if step == "decode":
        caches = on_chip(jax.eval_shape(
            lambda: api.init_state(CFG, 32, 2048, jnp.bfloat16)))
        fn = jax.jit(
            lambda p, c, t, pos: api.decode_at(CFG, p, t, c, pos),
            donate_argnums=(1,),
        )
        specs = (params, caches, _spec((32, 1), i32, one_chip),
                 _spec((32,), i32, one_chip))
    else:
        fn = jax.jit(lambda p, t, n: api.prefill_bucketed(CFG, p, t, n))
        specs = (params, _spec((4, 512), i32, one_chip),
                 _spec((4,), i32, one_chip))
    compiled = fn.lower(*specs).compile()
    _assert_kernel(compiled, at_least=4)
    ma = compiled.memory_analysis()
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert need < 16 * GiB, f"{need / GiB:.2f} GiB > 16 GiB"
    if step == "decode":
        # weights (11.6 GiB, full width, bf16) and the 1.75 GiB pool
        assert 14 * GiB > ma.argument_size_in_bytes > 13 * GiB
        assert ma.temp_size_in_bytes < 2.24e9, ma.temp_size_in_bytes
    else:
        assert 12 * GiB > ma.argument_size_in_bytes > 11 * GiB


def test_engine_program_and_kernel_names(one_chip, compiled_pallas):
    """The names a profile shows, which the benchmark's trace reduction
    (``bench/lib/trace.py``) keys on: the engine's compiled step programs
    map to their kinds, their GEMM kernels are named ``opope_gemm``, and the
    pool's join scatter maps to no kind. Two chatglm3-6b layers at published
    widths."""
    import dataclasses
    import re
    import sys

    from repro.models import api
    from repro.serve import ContinuousEngine
    from repro.serve.cache import init_slot_caches, scatter_slots

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
    try:
        from lib import trace
    finally:
        sys.path.pop(0)

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), tree)

    cfg = dataclasses.replace(CFG, n_layers=2)
    params = on_chip(jax.eval_shape(
        functools.partial(api.init_params, cfg), jax.random.key(0)))
    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=4, max_len=256)
    pool = on_chip(jax.eval_shape(
        lambda: init_slot_caches(cfg, 4, 256, eng.cache_dtype)))
    prompt = on_chip(jax.eval_shape(
        lambda: api.init_state(cfg, 2, 128, eng.cache_dtype)))
    i32 = jnp.int32
    programs = {
        "decode": eng._decode.lower(
            params, pool, _spec((4, 1), i32, one_chip), _spec((4,), i32, one_chip),
            _spec((4,), jnp.bool_, one_chip),
            on_chip(jax.eval_shape(lambda: jax.random.key(0)))),
        "prefill": eng._prefill.lower(
            params, _spec((2, 128), i32, one_chip), _spec((2,), i32, one_chip)),
        None: scatter_slots.lower(pool, prompt, _spec((2,), i32, one_chip), None),
    }
    custom_call = re.compile(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*custom_call_target="tpu_custom_call"', re.M)
    for kind, lowered in programs.items():
        text = lowered.compile().as_text()
        module = re.match(r"HloModule (\S+?),", text).group(1)
        assert trace.module_kind(module) == kind, module
        kernels = custom_call.findall(text)
        if kind is not None:
            assert kernels and all(k.startswith(trace.GEMM_KERNEL) for k in kernels)


# The configurations as the benchmark serves them (chatglm3-6b with its
# published Q/K/V bias, stablelm-12b with its published rotary fraction);
# the test cuts each to two layers.
ENGINE_CFGS = {
    "chatglm3-6b": lambda: dataclasses.replace(CFG, qkv_bias=True),
    "stablelm-12b.pp4": lambda: dataclasses.replace(
        get_config("stablelm-12b"), rope_frac=0.25),
}


def _weight_shapes(params) -> set:
    """Every layer GEMM weight's ``[K, N]``, and each padded to any mix of
    128-, 256- and 512-wide tiles."""
    def rup(x, t):
        return -(-x // t) * t

    out = set()
    for leaf in jax.tree.leaves(params["blocks"]):
        if leaf.ndim != 3:
            continue
        k, n = leaf.shape[1:]
        out |= {(rup(k, tk), rup(n, tn))
                for tk in (1, 128, 256, 512) for tn in (1, 128, 256, 512)}
    return out


@pytest.mark.parametrize("arch", sorted(ENGINE_CFGS))
def test_engine_steps_read_weights_in_place(one_chip, compiled_pallas, arch):
    """The engine's decode and prefill programs feed each layer's weights
    to the GEMM kernels straight from the stacked parameters: no
    instruction outside a kernel produces an array of a layer weight's
    shape, its own or padded to the kernel's tiles (a slice of the stack or
    a pad before the kernel would), and every Mosaic kernel is
    ``opope_gemm``."""
    import re

    from repro.models import api
    from repro.serve import ContinuousEngine
    from repro.serve.cache import init_slot_caches

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), tree)

    cfg = dataclasses.replace(ENGINE_CFGS[arch](), n_layers=2)
    params = on_chip(jax.eval_shape(
        functools.partial(api.init_params, cfg), jax.random.key(0)))
    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=4, max_len=256)
    pool = on_chip(jax.eval_shape(
        lambda: init_slot_caches(cfg, 4, 256, eng.cache_dtype)))
    i32 = jnp.int32
    programs = {
        "decode": eng._decode.lower(
            params, pool, _spec((4, 1), i32, one_chip), _spec((4,), i32, one_chip),
            _spec((4,), jnp.bool_, one_chip),
            on_chip(jax.eval_shape(lambda: jax.random.key(0)))),
        "prefill": eng._prefill.lower(
            params, _spec((2, 128), i32, one_chip), _spec((2,), i32, one_chip)),
    }
    weights = _weight_shapes(params)
    instr = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* (\S+?)\((.*)$", re.M)
    for kind, lowered in programs.items():
        text = lowered.compile().as_text()
        copies, kernels = [], []
        for name, dims, op, rest in instr.findall(text):
            if 'custom_call_target="tpu_custom_call"' in rest:
                kernels.append(name)
                continue
            shape = tuple(int(d) for d in dims.split(",") if d)
            while shape and shape[0] == 1:
                shape = shape[1:]  # a one-layer slice keeps its layer axis
            if shape in weights:
                copies.append(f"{name} = {op}{list(shape)}")
        assert not copies, f"{kind}: weight-sized copies {copies}"
        assert kernels and all(k.startswith("opope_gemm") for k in kernels), kernels
