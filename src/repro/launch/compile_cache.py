"""JAX's persistent compilation cache, at one fixed place.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins.
Otherwise the cache is ``.jax_cache/`` at the root of the checkout,
which git ignores.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

# the checkout this package runs from, when it runs from its source tree
_SOURCE_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache(checkout: Optional[pathlib.Path] = None) -> Optional[str]:
    """Turn the persistent cache on; returns the directory it uses.

    ``checkout`` defaults to the source tree this package was imported
    from.  An installed package has none, and then the cache stays off
    unless ``JAX_COMPILATION_CACHE_DIR`` is set.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if checkout is None:
        if not (_SOURCE_ROOT / "pyproject.toml").is_file():
            return None
        checkout = _SOURCE_ROOT
    path = str(pathlib.Path(checkout) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
