"""Start-up helpers of the entry-point scripts: the persistent compilation
cache placement and the serving-mesh device check."""
import jax
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import make_serving_mesh


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    root = compile_cache.DEFAULT_CACHE_DIR.parent
    assert (root / "pyproject.toml").is_file()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_serving_mesh_short_of_devices_names_the_real_count():
    have = len(jax.devices())
    with pytest.raises(RuntimeError) as err:
        make_serving_mesh(have + 1)
    msg = str(err.value)
    assert f"has {have} cpu device(s)" in msg
    assert "JAX_PLATFORMS=cpu" in msg
