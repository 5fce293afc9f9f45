"""The entry points' persistent compilation cache directory."""
import jax

from repro.launch import compile_cache


def test_env_var_names_the_cache(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.cache_dir()
    assert first == compile_cache.cache_dir()
    root = compile_cache.DEFAULT_DIR.parent
    assert first == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").exists()


def test_enable_points_jax_at_the_cache(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
