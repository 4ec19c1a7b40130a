"""Where compiled programs are kept between processes.

JAX's persistent compilation cache is the difference between a process
that compiles a 24-layer step from cold and one that loads it. The
directory is part of the cache's key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when the operator (or the machine)
sets it — JAX reads that variable itself and this module then sets
nothing — and otherwise the cache lives at one fixed, git-ignored path
under the checkout. ``common/exe_cache.py`` (opt-in
``HOROVOD_EXE_CACHE``) is a different store and is not touched here.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Idempotent; called from ``hvd.init()`` and
    ``hvd.serve()``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # A process that already compiled something decided "no cache"
        # at that first compile; make it look again.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    return DEFAULT_DIR
