"""Where JAX's persistent compilation cache lives, decided in one place.

The cache key includes the directory, so a directory that moves never
hits: every entry point (``chip_smoke.py``, the serving server and worker
mains, ``benchmarks/``, the tools) calls :func:`configure_compile_cache` and
nothing else in the repo names a cache directory.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  child processes inherit it — code sets nothing.
* unset: ``<checkout>/.jax_compile_cache`` (listed in ``.gitignore``), a
  fixed path next to the package, never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_compile_cache")


def configure_compile_cache() -> str:
    """Point this process at the persistent compilation cache and return
    the directory in use.  Initialises no JAX backend."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def count_cache_entries(path: str) -> int:
    """Entries in a cache directory (0 when it does not exist yet)."""
    try:
        return sum(1 for _ in os.scandir(path))
    except FileNotFoundError:
        return 0
