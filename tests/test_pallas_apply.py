"""Tests for the apply-scatter dispatch (`ops/packed_table.scatter_add_fused`
regime selection + `ops/pallas_apply` wrapper contracts).

The Pallas kernel itself needs a real TPU (its input/output aliasing has no
faithful interpret-mode equivalent) — `tools/smoke_pallas_apply.py` /
`make chip-smoke` covers it on hardware. Here we pin:
- the XLA fallback stays numerically exact for both regimes on CPU;
- wrapper argument validation;
- the env-var override logic.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_embeddings_tpu.ops.packed_table import (
    PackedLayout,
    scatter_add_fused,
)
from distributed_embeddings_tpu.ops.pallas_apply import apply_rows_cached


@pytest.mark.parametrize("prefer_pallas", [False, True])
@pytest.mark.parametrize("n_aux", [0, 1])
def test_scatter_add_fused_regimes_match(prefer_pallas, n_aux):
  """Both dispatch regimes must produce the same result (on CPU both lower
  to XLA scatter; on TPU one runs the Pallas kernel — tools/smoke covers
  that equivalence on hardware)."""
  layout = PackedLayout(rows=64, width=128, n_aux=n_aux)
  rng = np.random.default_rng(0)
  buf = jnp.asarray(rng.standard_normal(layout.shape), jnp.float32)
  ids = jnp.asarray(rng.integers(-2, layout.rows + 2, 200), jnp.int32)
  delta = jnp.asarray(rng.standard_normal((200, layout.stride)), jnp.float32)
  got = scatter_add_fused(layout, buf, ids, delta,
                          prefer_pallas=prefer_pallas)
  want = scatter_add_fused(layout, buf, ids, delta, prefer_pallas=False)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_dispatch_logic(monkeypatch):
  """Pin the env-override + regime selection by spying on the kernel entry
  (on the CPU CI backend the kernel can't run, so capability is stubbed)."""
  import distributed_embeddings_tpu.ops.packed_table as pt

  calls = []
  monkeypatch.setattr(pt, "_use_pallas_apply", lambda: True)
  monkeypatch.setattr(
      "distributed_embeddings_tpu.ops.pallas_apply.apply_rows_cached",
      lambda buf, ids, delta, **kw: calls.append(len(ids)) or buf)

  layout = PackedLayout(rows=32, width=128)       # rpp == 1
  narrow = PackedLayout(rows=32, width=16)        # rpp > 1
  buf = jnp.zeros(layout.shape, jnp.float32)
  nbuf = jnp.zeros(narrow.shape, jnp.float32)
  ids = jnp.asarray([1, 1, 5], jnp.int32)
  delta = jnp.ones((3, 128), jnp.float32)
  ndelta = jnp.ones((3, narrow.stride), jnp.float32)

  scatter_add_fused(layout, buf, ids, delta, prefer_pallas=True)
  assert len(calls) == 1, "prefer_pallas + rpp==1 must take the kernel"
  scatter_add_fused(layout, buf, ids, delta, prefer_pallas=False)
  assert len(calls) == 1, "prefer_pallas=False must keep XLA scatter"
  scatter_add_fused(narrow, nbuf, ids, ndelta, prefer_pallas=True)
  assert len(calls) == 2, ("rpp > 1 takes the kernel too: the lane "
                           "expansion feeds it physical-row updates")
  monkeypatch.setenv("DE_TPU_PALLAS_APPLY", "1")
  scatter_add_fused(layout, buf, ids, delta, prefer_pallas=False)
  assert len(calls) == 3, "DE_TPU_PALLAS_APPLY=1 must force the kernel"
  monkeypatch.setenv("DE_TPU_PALLAS_APPLY", "0")
  out = scatter_add_fused(layout, buf, ids, delta, prefer_pallas=True)
  assert len(calls) == 3, "DE_TPU_PALLAS_APPLY=0 must force XLA"
  assert float(out[1, 0]) == 2.0 and float(out[5, 0]) == 1.0


def test_apply_rows_cached_validates():
  buf = jnp.zeros((16, 128), jnp.float32)
  ids = jnp.zeros((4,), jnp.int32)
  with pytest.raises(ValueError, match="delta shape"):
    apply_rows_cached(buf, ids, jnp.zeros((4, 64), jnp.float32))
  with pytest.raises(ValueError, match="power of two"):
    apply_rows_cached(buf, ids, jnp.zeros((4, 128), jnp.float32), slots=48)
