"""Where JAX keeps its persistent compilation cache for this repo's
entry points (``chip_smoke.py``, ``examples/wami_pallas.py``,
``benchmarks/fleet_dse.py``, ``benchmarks/run.py``).

The cache is keyed by its path, so the path must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself, and nothing
    here sets another directory;
  * otherwise — a fixed directory inside the checkout,
    ``<repo>/.jax_cache`` (listed in ``.gitignore``).

The measured kernels compile in well under a second, below JAX's
default one-second floor for caching an entry, so the floor is set to
zero.  Nothing calls :func:`enable_compile_cache` at import: tests and
library users keep JAX's own configuration.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

__all__ = ["ENV_VAR", "REPO_CACHE_DIR", "compile_cache_dir",
           "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def compile_cache_dir(env: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory in effect: ``JAX_COMPILATION_CACHE_DIR`` when
    it is set in ``env`` (default ``os.environ``), else the fixed
    in-checkout path."""
    env = os.environ if env is None else env
    return env.get(ENV_VAR) or REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
