"""JAX's persistent compilation cache, configured in one place.

Entry points that compile at deployment size (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before their
first compile.  The cache path is part of each entry's key, so it is fixed:
``$JAX_COMPILATION_CACHE_DIR`` when set, otherwise ``.jax_cache/`` at the
root of the checkout.  Tests leave the cache off.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
  """The cache directory: ``$JAX_COMPILATION_CACHE_DIR``, else the checkout's
  ``.jax_cache/``."""
  return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
  """Turn the persistent compilation cache on; returns its directory.

  Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
  other directory is set here.
  """
  import jax
  path = compile_cache_dir()
  if not os.environ.get(ENV_VAR):
    jax.config.update("jax_compilation_cache_dir", path)
  jax.config.update("jax_enable_compilation_cache", True)
  return path
