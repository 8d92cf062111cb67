"""Launch helpers shared by every entry point: the mesh builder and the
persistent compilation cache's placement."""
import os

import jax
import pytest
from jax.sharding import AxisType

from repro.launch import compile_cache
from repro.launch.mesh import make_cold_mesh, make_mesh


def test_make_mesh_builds_auto_axes():
    """Explicit axes (``jax.make_mesh``'s default) refuse the block-cyclic
    unshard reshape; every mesh the repo builds is Auto."""
    for mesh in (make_mesh((1,), ("model",)),
                 make_cold_mesh(contributors=1, replicas=1, model=1)):
        assert set(mesh.axis_types) == {AxisType.Auto}


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_follows_the_environment(monkeypatch, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env


def test_compile_cache_defaults_to_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
