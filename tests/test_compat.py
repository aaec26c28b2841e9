"""Each compat helper exercised against the installed JAX."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.kernels import ops


class TestVersion:
    def test_parses_installed_version(self):
        v = compat.jax_version()
        assert len(v) >= 2 and all(isinstance(p, int) for p in v)
        assert v >= (0, 4)


class TestCompilerParams:
    def test_object_constructs_with_dimension_semantics(self):
        params = compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")
        )
        from jax.experimental.pallas import tpu as pltpu

        assert isinstance(params, pltpu.CompilerParams)
        assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")

    def test_kernel_using_shim_runs(self):
        from repro.kernels.opope_gemm import opope_gemm

        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)
        out = opope_gemm(a, b, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 16.0)


class TestMesh:
    def test_axis_types_tuple_or_none(self):
        types = compat.get_mesh_axis_types(3, "auto")
        assert types == (jax.sharding.AxisType.Auto,) * 3
        assert compat.get_mesh_axis_types(1, "explicit") == (
            jax.sharding.AxisType.Explicit,
        )

    def test_make_mesh_single_device(self):
        mesh = compat.make_mesh((1,), ("data",), axis_types="auto")
        assert mesh.axis_names == ("data",)
        assert compat.mesh_axis_sizes(mesh) == {"data": 1}

    def test_set_mesh_installs_ambient_mesh(self):
        mesh = compat.make_mesh((1,), ("data",))
        with compat.set_mesh(mesh):
            ambient = compat.current_abstract_mesh()
            assert ambient is not None
            assert tuple(ambient.axis_names) == ("data",)
            assert compat.mesh_axis_sizes(ambient)["data"] == 1

    def test_no_mesh_means_none_or_empty(self):
        assert compat.current_abstract_mesh() is None
        assert compat.mesh_axis_sizes(None) == {}

    def test_constrain_under_ambient_mesh(self):
        from repro.distributed.hints import constrain

        mesh = compat.make_mesh((1,), ("model",))
        with compat.set_mesh(mesh):
            y = jax.jit(lambda x: constrain(x, None, "model"))(
                jnp.ones((4, 8), jnp.float32)
            )
        np.testing.assert_allclose(np.asarray(y), 1.0)

    def test_constrain_no_mesh_is_noop(self):
        from repro.distributed.hints import constrain

        x = jnp.ones((4, 8), jnp.float32)
        np.testing.assert_allclose(np.asarray(constrain(x, "model", None)), 1.0)


class TestCostAnalysis:
    def _compiled(self):
        return (
            jax.jit(lambda x: jnp.tanh(x @ x))
            .lower(jax.ShapeDtypeStruct((16, 16), jnp.float32))
            .compile()
        )

    def test_dict_from_compiled(self):
        ca = compat.normalize_cost_analysis(self._compiled())
        assert isinstance(ca, dict)
        assert ca.get("flops", 0) > 0

    def test_dict_from_raw_result(self):
        raw = self._compiled().cost_analysis()
        assert compat.normalize_cost_analysis(raw)["flops"] > 0

    def test_list_dict_and_none_forms(self):
        # the installed JAX returns a dict (or None where a backend reports
        # nothing); the helper hands back a fresh dict either way
        raw = {"flops": 3.0}
        out = compat.normalize_cost_analysis(raw)
        assert out == {"flops": 3.0} and out is not raw
        assert compat.normalize_cost_analysis(None) == {}
        assert compat.normalize_cost_analysis({}) == {}

    def test_memory_analysis_has_peak(self):
        ma = compat.normalize_memory_analysis(self._compiled())
        for key in (
            "argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
            "peak_bytes",
        ):
            assert key in ma and ma[key] >= 0
        assert ma["argument_bytes"] > 0
        compiled = self._compiled()
        assert compat.normalize_memory_analysis(compiled)["peak_bytes"] == (
            compiled.memory_analysis().peak_memory_in_bytes
        )


class TestBackendRegistry:
    def test_builtins_registered(self):
        names = ops.registered_backends()
        for name in ("pallas", "pallas_interpret", "xla"):
            assert name in names

    def test_auto_resolves_to_available_backend(self):
        resolved = ops.resolve_backend("auto")
        assert resolved in ops.available_backends()

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError):
            ops.resolve_backend("not-a-backend")
        with pytest.raises(ValueError):
            ops.set_default_backend("not-a-backend")

    def test_unavailable_backend_degrades_not_raises(self):
        ops.register_backend(
            "always_broken", lambda a, b, c, dt: a, available=False
        )
        try:
            with pytest.warns(RuntimeWarning):
                resolved = ops.resolve_backend("always_broken")
            assert resolved in ("pallas_interpret", "xla")
        finally:
            ops._REGISTRY.pop("always_broken")

    def test_registered_backend_is_callable_through_matmul(self):
        calls = []

        def doubling(a, b, c, out_dtype):
            calls.append(a.shape)
            return (2.0 * (a @ b)).astype(out_dtype)

        ops.register_backend("doubling", doubling)
        try:
            a = jnp.ones((4, 8), jnp.float32)
            b = jnp.ones((8, 4), jnp.float32)
            out = ops.matmul(a, b, backend="doubling")
            np.testing.assert_allclose(np.asarray(out), 16.0)
            assert calls
        finally:
            ops._REGISTRY.pop("doubling")

    def test_tile_cache_keys_on_shape_and_dtype(self):
        ops._tile_for.cache_clear()
        t1 = ops._tile_for(256, 512, 256, 2)
        t2 = ops._tile_for(256, 512, 256, 2)
        t3 = ops._tile_for(256, 512, 256, 4)
        assert t1 == t2
        assert isinstance(t3, tuple) and len(t3) == 3
        info = ops._tile_for.cache_info()
        assert info.hits >= 1 and info.misses == 2

    def test_matmul_default_backend_matches_reference(self):
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
        got = ops.matmul(a, b)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(a) @ np.asarray(b), rtol=1e-5, atol=1e-5
        )
