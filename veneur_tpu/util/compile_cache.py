"""One place decides where JAX's persistent compilation cache lives.

The directory is part of the cache's key, so it must not move between
runs: never a temp name, a pid or the time.  Order of precedence:

1. `JAX_COMPILATION_CACHE_DIR` in the environment: JAX reads it itself
   and this code sets no directory at all (the machine's operator, or
   the harness that runs the program, placed the cache).
2. an explicit directory (a deployment's `compilation_cache_dir`).
3. `<checkout>/.jax_cache` (git-ignored).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def child_env_dir(env: dict) -> None:
    """Give a child process's environment the same cache this process
    would use (children that enable no cache in code still hit it)."""
    env.setdefault(ENV_VAR, DEFAULT_DIR)


def enable(configured: str = "",
           min_compile_secs: float | None = None) -> str:
    """Turn the persistent compilation cache on and return the
    directory in use.  `configured` is a deployment's explicit choice
    (may be empty); the environment variable wins over it."""
    import jax

    if min_compile_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_secs))
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    path = (os.path.abspath(os.path.expanduser(configured))
            if configured else DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
