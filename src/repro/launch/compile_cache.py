"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) call :func:`use_compile_cache` once at start-up;
importing a module never turns the cache on, so library users and the
CPU tests are unaffected.

* ``$JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
  set here.
* Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
  The directory is part of the cache key, so it is one fixed path — never
  a temporary name, a pid or a timestamp — and a later run of the same
  checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]
