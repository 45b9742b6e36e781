"""Where compiled programs are kept between processes.

A cold TPU compile of a model step is tens of seconds, and every entry
point (``chip_smoke.py``, ``bench.py``'s worker, the examples, the
``tools/`` drivers) runs in a fresh process, often on a fresh machine. JAX's
persistent compilation cache answers those compiles from disk — but the
directory is part of the cache key, so it has to be the same every time.

Policy: the directory is placed from OUTSIDE. Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this module
sets nothing. Where it is not, one fixed directory at the root of the
checkout is used (listed in ``.gitignore``); no tempfile, pid or time ever
enters the path. On the CPU backend nothing is set either: a CPU compile
of a test-sized program costs about what a cache read does, and XLA:CPU
logs a machine-feature error for every entry it loads.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> "str | None":
    """Turn the persistent compilation cache on before the first compile
    and return the directory it lives in, or None where none is in use
    (see the module docstring). Starts the backend to learn which one
    it is.

    With the in-checkout directory every program is cached, however
    quick its compile, so a second run of the same entry point compiles
    nothing the first one did."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CHECKOUT_CACHE_DIR
