"""The compile cache keys on what the profiler prints: an executable carries
its ops' names, so the cache may not serve one built under other scopes.
(That ``enable_compile_cache`` sets the flag on both of its paths is checked
beside its other properties, in ``test_chip_smoke.py``.)"""

import os
import subprocess
import sys

from distributed_embeddings_tpu import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_CHILD = """
import sys
import jax, jax.numpy as jnp
from distributed_embeddings_tpu.compile_cache import enable_compile_cache
enable_compile_cache()  # the directory comes from the environment
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
def f(x):
  with jax.named_scope(sys.argv[1]):
    return jnp.sin(x) * 2.0
print(jax.jit(f).lower(jnp.ones((8,), jnp.float32)).compile().as_text())
"""


def _compiled_under(scope: str, cache_dir) -> str:
  """The compiled text of one function under ``scope``, from a process of
  its own that shares ``cache_dir`` with the others."""
  env = dict(os.environ, JAX_PLATFORMS="cpu",
             PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
  env[compile_cache.ENV_VAR] = str(cache_dir)
  done = subprocess.run([sys.executable, "-c", _CHILD, scope], env=env,
                        capture_output=True, text=True, timeout=300)
  assert done.returncode == 0, done.stderr[-2000:]
  return done.stdout


def test_a_step_compiled_under_new_scopes_is_not_served_the_old_names(
    tmp_path):
  first = _compiled_under("scope_a", tmp_path)
  assert "scope_a" in first
  entries = set(os.listdir(tmp_path))
  assert entries, "the first compile wrote no cache entry"
  assert _compiled_under("scope_a", tmp_path) == first
  assert set(os.listdir(tmp_path)) == entries   # the same names: a hit
  second = _compiled_under("scope_b", tmp_path)
  assert "scope_b" in second and "scope_a" not in second
