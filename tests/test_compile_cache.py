"""The entry points' persistent compile cache lands in one fixed place:
``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's git-ignored
``.jax_cache``."""

from pathlib import Path

import jax

from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_dir_is_the_cache_and_nothing_else_is_set(monkeypatch,
                                                      tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_git_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert Path(path) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
