"""Backend registry degradation chain: explicit-request fallback
``pallas -> pallas_interpret -> xla`` with the RuntimeWarning contract, plus
``set_default_backend("auto")`` round-trips. Probes are monkeypatched so the
chain is exercised deterministically regardless of the host platform; the
TPU rules (a refused compile raises, no interpreter fallback) are exercised
by monkeypatching the platform.
"""

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import reference_matmul


def _force_unavailable(monkeypatch, *names):
    for name in names:
        b = ops._REGISTRY[name]
        monkeypatch.setitem(
            ops._REGISTRY, name, dataclasses.replace(b, available=lambda: False)
        )


def _force_available(monkeypatch, name):
    b = ops._REGISTRY[name]
    monkeypatch.setitem(
        ops._REGISTRY, name, dataclasses.replace(b, available=lambda: True)
    )


def test_explicit_pallas_degrades_to_interpreter(monkeypatch):
    _force_unavailable(monkeypatch, "pallas")
    with pytest.warns(RuntimeWarning, match="degrading to 'pallas_interpret'"):
        assert ops.resolve_backend("pallas") == "pallas_interpret"


def test_explicit_request_degrades_past_interpreter_to_xla(monkeypatch):
    _force_unavailable(monkeypatch, "pallas", "pallas_interpret")
    with pytest.warns(RuntimeWarning, match="degrading to 'xla'"):
        assert ops.resolve_backend("pallas") == "xla"
    # a degraded interpreter request also lands on xla
    with pytest.warns(RuntimeWarning, match="degrading to 'xla'"):
        assert ops.resolve_backend("pallas_interpret") == "xla"


def test_degraded_matmul_still_resolves_and_computes(monkeypatch):
    _force_unavailable(monkeypatch, "pallas", "pallas_interpret")
    a = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    b = jnp.asarray(np.ones((4, 2), np.float32))
    with pytest.warns(RuntimeWarning):
        got = ops.matmul(a, b, backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(reference_matmul(a, b)))


def test_no_available_backend_raises(monkeypatch):
    _force_unavailable(monkeypatch, "pallas", "pallas_interpret", "xla")
    with pytest.raises(RuntimeError, match="no available matmul backend"):
        ops.resolve_backend("pallas")


def test_probe_exceptions_count_as_unavailable(monkeypatch):
    def boom():
        raise OSError("probe exploded")

    b = ops._REGISTRY["pallas"]
    monkeypatch.setitem(
        ops._REGISTRY, "pallas", dataclasses.replace(b, available=boom)
    )
    with pytest.warns(RuntimeWarning):
        assert ops.resolve_backend("pallas") == "pallas_interpret"
    assert "pallas" not in ops.available_backends()


def test_auto_follows_reregistered_probe(monkeypatch):
    # "auto" consults the registry probe, so a re-registered pallas backend
    # brings its own availability rule.
    _force_available(monkeypatch, "pallas")
    assert ops.resolve_backend("auto") == "pallas"
    _force_unavailable(monkeypatch, "pallas")
    assert ops.resolve_backend("auto") == "xla"


def test_set_default_backend_auto_roundtrip():
    assert ops.default_backend() in ops.registered_backends()
    try:
        ops.set_default_backend("xla")
        assert ops.default_backend() == "xla"
        assert ops.resolve_backend(None) == "xla"
        ops.set_default_backend("auto")
        # auto resolves to a real backend on every platform
        assert ops.default_backend() in ("pallas", "xla")
    finally:
        ops.set_default_backend("auto")


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown matmul backend"):
        ops.resolve_backend("tpu_v7")
    with pytest.raises(ValueError, match="unknown matmul backend"):
        ops.set_default_backend("tpu_v7")


def test_register_backend_requires_callable():
    with pytest.raises(TypeError):
        ops.register_backend("broken", fn=None)


# ---------------------------------------------------------------------------
# On a TPU nothing falls back off the compiled path (platform monkeypatched)
# ---------------------------------------------------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    """Resolution as it runs on a TPU: the platform reads "tpu" and the
    memoized compile probes start (and end) cold."""
    from repro.quant import backends as qb

    probes = (
        ops._pallas_compiles, ops._pallas_grouped_compiles,
        qb._pallas_q8_compiles, qb._pallas_q8_grouped_compiles,
    )
    for p in probes:
        p.cache_clear()
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    yield
    for p in probes:
        p.cache_clear()


def test_tpu_failing_compile_probe_raises_with_compiler_message(on_tpu):
    # This host has no Mosaic: the real compile of the probe GEMM fails,
    # which on a "tpu" platform must surface instead of resolving to xla.
    with pytest.raises(RuntimeError, match="failed to compile on this TPU") as e:
        ops.resolve_backend("auto")
    assert e.value.__cause__ is not None  # the compiler's own error
    with pytest.raises(RuntimeError, match="failed to compile on this TPU"):
        ops.resolve_backend("pallas")
    with pytest.raises(RuntimeError, match="int8 Pallas GEMM failed"):
        ops.resolve_backend("pallas_q8")


def test_tpu_raising_probe_propagates(on_tpu, monkeypatch):
    def boom():
        raise OSError("probe exploded")

    b = ops._REGISTRY["pallas"]
    monkeypatch.setitem(
        ops._REGISTRY, "pallas", dataclasses.replace(b, available=boom)
    )
    with pytest.raises(OSError, match="probe exploded"):
        ops.resolve_backend("pallas")


def test_tpu_never_degrades_onto_an_interpreter(on_tpu, monkeypatch):
    _force_unavailable(monkeypatch, "pallas")
    with pytest.warns(RuntimeWarning, match="degrading to 'xla'"):
        assert ops.resolve_backend("pallas") == "xla"
    with pytest.warns(RuntimeWarning, match="degrading to 'xla'"):
        assert ops.resolve_grouped_backend("pallas") == "xla"
    _force_unavailable(monkeypatch, "pallas_q8")
    with pytest.warns(RuntimeWarning, match="degrading to 'xla_q8'"):
        assert ops.resolve_backend("pallas_q8") == "xla_q8"
    # named explicitly, the interpreters still resolve
    assert ops.resolve_backend("pallas_interpret") == "pallas_interpret"
    assert ops.resolve_backend("pallas_q8_interpret") == "pallas_q8_interpret"


def test_tpu_auto_takes_compiled_pallas(on_tpu, monkeypatch):
    _force_available(monkeypatch, "pallas")
    assert ops.resolve_backend("auto") == "pallas"


@pytest.mark.parametrize(
    "requested,resolved",
    [
        ("auto", "xla"),
        ("pallas", "pallas_interpret"),
        ("pallas_interpret", "pallas_interpret"),
        ("xla", "xla"),
        ("pallas_q8", "pallas_q8_interpret"),
        ("pallas_q8_interpret", "pallas_q8_interpret"),
        ("xla_q8", "xla_q8"),
    ],
)
def test_cpu_resolution_unchanged(requested, resolved):
    """Off the TPU the real probes answer "unavailable" without raising and
    the fallback chains degrade as they always have."""
    assert ops._platform() != "tpu"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        assert ops.resolve_backend(requested) == resolved
