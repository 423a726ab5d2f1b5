"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests multi-worker behavior by launching real processes under
horovodrun against real GPUs (`/root/reference/tests/dist_model_parallel_test.py:97-103`).
JAX gives us a fake-backend capability the reference lacks: N virtual CPU
devices in one process via XLA flags, so distributed tests run anywhere.

Unit tests never touch a chip: ``JAX_PLATFORMS=cpu`` and the device count
are set in the environment before jax is imported (``chip_smoke.py`` is
the on-chip check).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
  os.environ["XLA_FLAGS"] = (
      flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from distributed_embeddings_tpu.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

# Persistent compilation cache.  The in-memory pjit cache is keyed on
# function identity, so the same DLRM step function re-traced in a
# different test module recompiles from scratch; the persistent cache is
# keyed on the HLO hash, so those duplicate compiles become disk hits.
# Where it lives is the helper's decision (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache); the two thresholds make the suite's many small
# programs eligible.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}")

# markers are registered in pyproject.toml [tool.pytest.ini_options]
# (with --strict-markers, so an unregistered marker fails collection)
