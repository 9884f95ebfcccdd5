"""Placement of the persistent compilation cache (``repro.runtime``)."""

import jax

from repro.runtime import compile_cache


def _restoring(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_environment(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def run():
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        got = compile_cache.use_compile_cache()
        return got, jax.config.jax_compilation_cache_dir

    assert _restoring(run) == (str(tmp_path), str(tmp_path))


def test_cache_dir_defaults_to_checkout(monkeypatch):
    """Without the variable the cache sits at ``<checkout>/.jax_cache``, a
    fixed path (no pid, temp name or time in it) that git ignores."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def run():
        got = compile_cache.use_compile_cache()
        return got, jax.config.jax_compilation_cache_dir

    got, configured = _restoring(run)
    root = compile_cache.DEFAULT_DIR.parent
    assert got == configured == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "runtime" / "compile_cache.py").is_file()
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
