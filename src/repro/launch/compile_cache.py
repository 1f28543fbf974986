"""Where the entry points keep JAX's persistent compilation cache.

A 32-layer prefill or decode program takes tens of seconds to compile
for the chip; with the cache on, a second process that builds the same
program loads it instead.  The cache's path is part of what makes a hit,
so it never moves between runs:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  this module sets no other directory;
* otherwise the cache lives in ``.cache/jax_compile`` at the root of the
  checkout (listed in ``.gitignore``) — never a path built from a
  temporary name, a pid or the time.

Called by ``repro.launch.serve``, ``benchmarks/run.py`` and
``chip_smoke.py`` before they compile anything.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The checkout this module was loaded from (src/repro/launch/ -> root).
CHECKOUT = Path(__file__).resolve().parents[3]


def default_dir(root: Optional[Path] = None) -> Path:
    """The fixed in-checkout cache directory used when the environment
    names none.

    >>> default_dir(Path("/work/repo"))
    PosixPath('/work/repo/.cache/jax_compile')
    """
    return (root or CHECKOUT) / ".cache" / "jax_compile"


def enable_compile_cache(root: Optional[Path] = None) -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.
    Must run before the first compile it should cover."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(default_dir(root))
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
