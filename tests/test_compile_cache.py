"""Placement of the persistent compilation cache (utils/compile_cache.py):
env var set -> that directory, and nothing else is set; unset -> the fixed
<checkout>/.jax_cache.  jax.config is stubbed: the helper must not re-point
the cache of the pytest process itself."""

import os

import pytest

from sparkucx_tpu.utils.compile_cache import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCacheHelper:
    @pytest.fixture
    def updates(self, monkeypatch):
        import jax

        seen = {}
        monkeypatch.setattr(jax.config, "update", lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_env_var_wins_and_directory_is_left_alone(self, updates, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "given"))
        assert enable_compile_cache() == str(tmp_path / "given")
        assert "jax_compilation_cache_dir" not in updates
        assert not (tmp_path / "given").exists()  # nothing done to it
        # JAX's 1.0 s default would store none of this program's executables
        assert updates == {"jax_persistent_cache_min_compile_time_secs": 0.0}

    def test_unset_uses_the_fixed_checkout_path(self, updates, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path
