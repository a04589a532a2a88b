"""Where JAX's persistent compilation cache lives: one rule, one helper.

Every process of this repo that compiles for a device (the server,
``chip_smoke.py``, ``bench.py``, the test suite) calls :func:`configure`
before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  this code sets no directory of its own — the cache can be placed from
  outside (a mounted volume, a driver's directory) and nothing here
  overrides it;
- unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``). A fixed
  path, because the path is part of the cache key: a directory named
  from a pid, a temporary name or the time never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: this file is <checkout>/llm_sharding_demo_tpu/utils/
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Apply the rule above; returns the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
