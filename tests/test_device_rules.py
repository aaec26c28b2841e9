"""Where the compile cache goes, and which peaks a device is scored against."""

import types

import jax
import pytest

from repro.core import roofline
from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert first == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").is_file()


@pytest.mark.parametrize("env_set", [True, False])
def test_enable_sets_only_the_fixed_path(
    monkeypatch, tmp_path, env_set, restore_cache_dir
):
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    if env_set:
        # JAX reads the variable itself; the code sets no other path
        assert got == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == restore_cache_dir
    else:
        assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_kinds_map_to_v5e_peaks(kind):
    hw = roofline.device_peaks(_device("tpu", kind))
    assert hw is roofline.TPU_V5E
    assert (hw.peak_flops, hw.hbm_bw) == (197e12, 819e9)


def test_unknown_tpu_kind_raises():
    with pytest.raises(ValueError, match="TPU v9"):
        roofline.device_peaks(_device("tpu", "TPU v9"))


def test_cpu_scores_against_v5e_reference():
    assert roofline.device_peaks(_device("cpu", "cpu")) is roofline.TPU_V5E
    assert roofline.device_peaks() is roofline.TPU_V5E  # this host
