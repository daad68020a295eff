"""Where the entry scripts keep JAX's persistent compilation cache.

Called from ``chip_smoke.py`` and ``bench.py``'s ``main`` — never at
package import, so importing the library changes no JAX setting.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and no other cache is set; otherwise the cache lives at
    the fixed ``<repo>/.jax_cache/`` — the path is part of the cache's
    key, so a directory that moves never hits."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
