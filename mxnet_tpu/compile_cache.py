"""JAX's persistent compilation cache, placed from outside.

The entry points call :func:`enable` before their first compile
(``chip_smoke.py``, ``bench.py``, ``tools/serve.py``,
``tools/tpu_train_check.py``); ``import mxnet_tpu`` does not — a library
import must not start writing files.  The directory is part of the
cache key's stability: it never moves, so it is never derived from a
temporary name, a pid or a time.
"""
import os

__all__ = ["enable"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it as
    the flag's value, and no other directory is set here; where it is
    not, the cache lives at ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # a cold sealed machine pays for every program it compiles, the
    # sub-second ones of the eager path included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
