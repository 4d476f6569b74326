"""Where the persistent compilation cache lives (util/compile_cache.py)."""

import os
import tempfile

import jax

from veneur_tpu.util import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        # environment variable set -> JAX reads it; the code sets no
        # directory, not even a deployment's explicit one
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV_VAR, "/srv/xla-cache")
        assert compile_cache.enable("/etc/veneur/cache") == "/srv/xla-cache"
        assert jax.config.jax_compilation_cache_dir is None
        env = {compile_cache.ENV_VAR: "/srv/xla-cache"}
        compile_cache.child_env_dir(env)
        assert env[compile_cache.ENV_VAR] == "/srv/xla-cache"

        # unset -> <checkout>/.jax_cache: a fixed path (the directory is
        # part of the cache key), never a temp name or the home directory
        monkeypatch.delenv(compile_cache.ENV_VAR)
        got = compile_cache.enable()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert not got.startswith(tempfile.gettempdir() + os.sep)
        assert os.path.dirname(got) != os.path.expanduser("~")
        assert ".cache" not in got.split(os.sep)
        env = {}
        compile_cache.child_env_dir(env)
        assert env[compile_cache.ENV_VAR] == got

        # a deployment's YAML may still name a directory
        assert compile_cache.enable("/var/lib/veneur/xla") \
            == "/var/lib/veneur/xla"
        assert jax.config.jax_compilation_cache_dir == "/var/lib/veneur/xla"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_config_has_no_home_directory_default():
    from veneur_tpu import config as config_mod

    assert config_mod.Config().compilation_cache_dir == ""
