"""Where JAX keeps its persistent compile cache for this checkout.

The entry points that compile — the server (``python -m
tpubloom.server``), ``bench.py``, ``chip_smoke.py`` and the migration
tool — call :func:`configure` before their first compile, so a restart
finds its compiled kernels again. Importing :mod:`tpubloom` never calls
it: tests must not write compiles into the cache.

``$JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set here
(JAX reads that variable itself). Otherwise the cache lives at the fixed
:data:`CHECKOUT_CACHE_DIR` — never a temp name, a pid or a time, since
the path is part of what makes a cached entry findable again.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (gitignored); the geometry-probe results of
#: :mod:`tpubloom.ops.sweep` live under it too
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure() -> str:
    """Put JAX's persistent compile cache in its place; return the path."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
