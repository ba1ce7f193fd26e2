"""How ray_tpu sits on the installed JAX (0.9): the one ``shard_map``
spelling the kernels import, and the persistent compilation cache every
device program shares."""

from __future__ import annotations

import os

from jax import shard_map  # noqa: F401 - re-exported for ops/ and parallel/

#: ``<checkout>/.jax_cache``, from this file's own location. JAX keys cache
#: entries by the directory too, so a path made from a pid, a time or
#: ``tempfile`` would never hit.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where this process keeps compiled programs:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and no
    directory is set in code), else ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.
    Called wherever ray_tpu first builds a device program, so a second run
    of the same command compiles nothing.

    The minimum-compile-time threshold drops to zero so kernel-sized
    programs (a Pallas kernel compiles in 1-2 s, JAX's default cut-off is
    1 s) are kept too. A process whose default backend is the CPU caches
    nothing: XLA's CPU loader logs an error-level machine-feature line for
    every entry it reads back, and CPU compiles are not what chip time is
    lost on.
    """
    import jax
    if jax.default_backend() == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
