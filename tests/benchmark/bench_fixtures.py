"""Fixtures shared by the rehearsals of benchmark/run.py in this directory
(not a conftest.py: other test files import the one in tests/ by that name)."""

import os

import jax
import pytest


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """The runs of this module compile the same programs again and again;
    share them through a persistent cache of the module's own, and leave the
    session as conftest.py set it up (cache off)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = str(tmp_path_factory.mktemp("jax_cache"))
    saved_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    saved_dir = jax.config.jax_compilation_cache_dir
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir  # setup_compile_cache() then sets nothing
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield cache_dir
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", saved_dir)
    compilation_cache.reset_cache()
    if saved_env is None:
        del os.environ["JAX_COMPILATION_CACHE_DIR"]
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = saved_env
