"""Backend-configuration helpers shared by the entry points.

Kept separate from utils.config (which must stay importable without
JAX): everything here touches ``jax.config`` and must run BEFORE
backend initialization.
"""

from __future__ import annotations

import os


def request_cpu_devices(n: int) -> None:
    """Force the CPU backend with ``n`` virtual devices.

    Must run before any backend use; a ``RuntimeError`` (backend already
    initialized) propagates to the caller, who knows whether a
    preconfigured backend is acceptable.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


#: the fixed in-checkout cache directory (knn_tpu/utils/compat.py is two
#: packages below the checkout root).  Fixed on purpose: the directory
#: is part of what a later process must find again, so it is never a
#: tempfile, pid or timestamp path.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile, and return the directory it will use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there
    and this function sets no path (JAX reads the variable itself) —
    the machine's owner placed it.  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Either way the size and compile-time
    thresholds drop to zero, so the small per-bucket serving programs
    are cached next to the big kernels.  Called by ``chip_smoke.py``,
    ``benchmark/system.py`` and ``knn_tpu.cli.main``; the test suite runs with
    JAX's cache switched off (tests/conftest.py).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
