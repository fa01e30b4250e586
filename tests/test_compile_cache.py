"""The entry points' compilation-cache directory: the environment's when
``JAX_COMPILATION_CACHE_DIR`` is set (and then nothing is set in code),
else a fixed, git-ignored directory inside the checkout."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env", [None, "/somewhere/else"])
def test_compile_cache_dir(monkeypatch, env):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)

    got = compile_cache.enable_compile_cache()

    if env is None:
        assert got == str(REPO / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", got)]
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    else:
        assert got == env
        assert updates == []
