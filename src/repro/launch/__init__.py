"""Launchers: production mesh, sharding rules, dry-run, train/serve."""
import os
from pathlib import Path

# the checkout root: <root>/src/repro/launch/__init__.py
_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and it
    is left alone; otherwise the cache is `.jax_cache/` at the checkout
    root.  The path is part of what a cache hit needs, so it never
    depends on a temporary directory, a pid or the time.  Returns the
    directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
