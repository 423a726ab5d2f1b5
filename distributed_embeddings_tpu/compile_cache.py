"""Where JAX's persistent compilation cache lives, for every entry point.

The directory is part of the cache key's lookup, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads the
variable itself; nothing is set in code), otherwise the cache sits at
``<checkout>/.jax_cache`` — a fixed, git-ignored path next to the
package. This module is the only code that sets
``jax_compilation_cache_dir``.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> str:
  """Turn the persistent compilation cache on; returns its directory.

  The cache key includes the ops' metadata
  (``jax_compilation_cache_include_metadata_in_key``): an executable carries
  the op names the profiler shows (the name stack with the scopes of
  ``telemetry/scopes.py``), and JAX's default key is taken after they are
  stripped, so a cache that ignores them serves a trace with another
  build's names, or with none. The price is a compile when traced source
  moves; a warm run loads as before.
  """
  import jax
  jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
  env_dir = os.environ.get(ENV_VAR)
  if env_dir:
    return env_dir
  jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
  return DEFAULT_DIR


def cache_entries(cache_dir: str) -> int:
  """Compiled programs stored under ``cache_dir`` (0 = a cold cache)."""
  try:
    names = os.listdir(cache_dir)
  except FileNotFoundError:
    return 0
  return sum(1 for n in names if n.endswith("-cache"))
