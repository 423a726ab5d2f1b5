"""The JAX names the library and its tests import from one place.

One installation is supported (the jax/jaxlib pinned in pyproject.toml),
so these are plain aliases — kept so call sites share one import:

- ``shard_map = jax.shard_map``. Its autodiff tracks varying vs
  replicated values, so differentiating a body that mixes a replicated
  (``P()``) param into device-varying math sums that param's grad across
  the axis itself, exactly once. Callers never psum replicated-param
  grads again (summing twice would double-count); model-parallel shards'
  grads are rank-local by construction and are never summed.
- ``enable_x64 = jax.enable_x64``.
- ``axis_size = jax.lax.axis_size``: a static Python int at trace time.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map
enable_x64 = jax.enable_x64
axis_size = jax.lax.axis_size

__all__ = ["shard_map", "enable_x64", "axis_size"]
