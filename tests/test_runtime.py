"""Platform-derived runtime settings (``repro.runtime``)."""
from __future__ import annotations

import os

import jax
import pytest

from repro import runtime


def test_pallas_interpret_follows_the_backend():
    # the suite runs on the CPU, where the kernels interpret
    assert jax.default_backend() == "cpu"
    assert runtime.pallas_interpret() is True
    assert runtime.pallas_interpret(None) is True


@pytest.mark.parametrize("explicit", [True, False])
def test_pallas_interpret_explicit_choice_wins(explicit):
    assert runtime.pallas_interpret(explicit) is explicit


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_dir(env_dir, monkeypatch):
    """The environment's directory is left to JAX; without one the cache
    goes to the fixed ``<repo>/.jax_cache``."""
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = runtime.enable_compile_cache()
        if env_dir is None:
            want = os.path.join(runtime.REPO_ROOT, ".jax_cache")
            assert got == want
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.isfile(os.path.join(runtime.REPO_ROOT,
                                               "chip_smoke.py"))
        else:
            assert got == env_dir
            # nothing set here: JAX's own reading of the variable stands
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
