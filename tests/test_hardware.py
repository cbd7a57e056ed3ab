"""The per-chip peak table and the compile-cache placement."""

import jax
import pytest

from repro import compile_cache
from repro.hardware import TARGET_DEVICE_KIND, chip_peaks
from repro.kernels.spmv import autotune as AT


def test_v5e_peaks_resolve_by_device_kind():
    peaks = chip_peaks("TPU v5 lite")
    assert peaks.flops_bf16 == 197e12
    assert peaks.hbm_bytes_per_s == 819e9
    assert chip_peaks() == peaks and TARGET_DEVICE_KIND == "TPU v5 lite"


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        AT.modeled_push_cost(e_pad=4096, n=512, device_kind="TPU v9")


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch,
                                               cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was  # JAX reads the env


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.DEFAULT_CACHE_DIR)
    assert path.endswith("/.jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
