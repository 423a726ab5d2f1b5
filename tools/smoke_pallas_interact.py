"""Real-TPU smoke test for the fused Pallas interaction kernels.

Checks the per-part fwd/bwd kernels (the DLRM hot path,
`ops/pallas_interact.py`) against the explicit XLA einsum form
(`pallas_interact.xla_reference` and its `jax.vjp`) ON THE REAL CHIP at
the bench feature shape (F=27, D=128) — interpret mode covers semantics
(tests/test_pallas_interact.py); this validates what Mosaic makes of the
body (strided stores and loads of the sample-major scratch, the batched
MXU dots of four samples a tile).

Run: python tools/smoke_pallas_interact.py   (leg B of chip_smoke.py)
Exit code 0 = pass; non-zero on any failure AND on a backend that is not
a TPU (there is nothing to validate off the chip).
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.compile_cache import enable_compile_cache
from distributed_embeddings_tpu.models.dlrm import _tril_select_np
from distributed_embeddings_tpu.ops.pallas_interact import (
    interact_parts_bwd,
    interact_parts_fwd,
    xla_reference,
)
from distributed_embeddings_tpu.parallel.mesh import require_tpu

F, D, B = 27, 128, 1024


def _xla_reference(flat, f, k):
  m_np, _ = _tril_select_np(f, k)
  return xla_reference(flat, m_np, f)


def main():
  print("device:", json.dumps(require_tpu("smoke_pallas_interact")), flush=True)
  rng = np.random.default_rng(5)
  parts = [jnp.asarray(rng.standard_normal((B, D)) * 0.3, jnp.bfloat16)
           for _ in range(F)]
  m_np, _ = _tril_select_np(F, -1)
  failed = []

  got = jax.jit(lambda ps: interact_parts_fwd(ps, m_np))(parts)
  flat = jnp.concatenate(parts, axis=1)
  want, vjp = jax.vjp(lambda y: _xla_reference(y, F, -1), flat)
  err = float(jnp.max(jnp.abs(got - want)))
  scale = float(jnp.max(jnp.abs(want)))
  ok = err <= 2e-2 * max(scale, 1.0)
  print(f"interact fwd vs XLA form           : "
        f"{'OK' if ok else 'FAIL'} (max err {err:.2e}, scale {scale:.1f})")
  if not ok:
    failed.append("fwd")

  d_acts = jnp.asarray(rng.standard_normal(want.shape), jnp.float32)
  (want_flat,) = vjp(d_acts)
  got_parts = jax.jit(
      lambda da, ps: interact_parts_bwd(da, ps, m_np))(d_acts, parts)
  werr = 0.0
  for p in range(F):
    w = np.asarray(want_flat[:, p * D:(p + 1) * D], np.float32)
    g = np.asarray(got_parts[p], np.float32)
    werr = max(werr, float(np.max(np.abs(g - w))))
  wscale = float(np.max(np.abs(np.asarray(want_flat))))
  ok = werr <= 4e-2 * max(wscale, 1.0)
  print(f"interact bwd vs XLA vjp            : "
        f"{'OK' if ok else 'FAIL'} (max err {werr:.2e}, scale {wscale:.1f})")
  if not ok:
    failed.append("bwd")

  if failed:
    print(f"FAILED: {failed}")
    sys.exit(1)
  print("interact smoke PASS")


if __name__ == "__main__":
  enable_compile_cache()
  main()
