"""The platform helper and the persistent compile cache (repro.device)."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import device


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_on_cpu_asks_jax():
    assert device.on_cpu() == (jax.default_backend() == "cpu")


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = device.enable_compile_cache()
    assert path == str(device.DEFAULT_CACHE_DIR)
    assert device.DEFAULT_CACHE_DIR.parent.joinpath("src", "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_env_var_wins(monkeypatch, tmp_path,
                                    restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert device.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other path
    assert jax.config.jax_compilation_cache_dir is None
