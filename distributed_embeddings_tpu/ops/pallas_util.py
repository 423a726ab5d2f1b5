"""What every ``pallas_call`` site in ``ops/pallas_*.py`` shares."""

from __future__ import annotations

import jax


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
  """``out_shape`` entry for a ``pallas_call`` result that varies over the
  mesh axes its ``operands`` vary over.

  Inside ``jax.shard_map`` (whose ``check_vma`` is on by default) a
  ``pallas_call`` refuses an ``out_shape`` that does not say how the
  result varies across the mesh — and every multi-chip step runs its
  kernels there. Outside a ``shard_map`` the set is empty."""
  vma = frozenset().union(
      *(jax.typeof(x).vma for x in jax.tree_util.tree_leaves(operands)))
  return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
