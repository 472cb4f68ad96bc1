"""The persistent XLA compile cache: one helper, one fixed location.

A cold compile of the flagship train step or the decode step takes tens of
seconds on a TPU, and a process that finds its executables in the cache skips
it. The directory is part of how an entry is found again, so it carries no
host, backend, pid or time component.
"""

from __future__ import annotations

import os

#: Root of the checkout: the directory that holds the ``analytics_zoo_tpu``
#: package. The cache the program writes (compiled executables) lives
#: under it, in a path ``.gitignore`` lists.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Make JAX keep compiled executables across processes; returns the
    directory in effect.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it into
    ``jax_compilation_cache_dir`` and this changes nothing. Otherwise the
    cache is ``<checkout>/.jax_cache``. Called when a ``ZooContext`` is built
    (``init_zoo_context``, or the lazy default), by the serving stack,
    ``benchmark/run.py`` and ``chip_smoke.py``; nothing else sets the
    directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
