"""Real-TPU smoke test for the expert layer's combine kernel.

Checks `ops/pallas_moe_combine.combine` (`de_moe_combine`) against XLA's
scatter-add ON THE REAL CHIP, at a small shape (256 tokens, one SMEM block of
`pos`) and at the shape of three MoE cells (8,192 tokens, top 8, a head of
32,768 rows of 2,048 float32), with a scale that has zeros and dead rows
that hold NaN; then the `custom_vjp` pair of `layers/moe.py` through
`jax.grad` against JAX's own transpose of the gather and the scatter-add.
Interpret mode covers semantics (`tests/test_pallas_moe_combine.py`); this
validates what Mosaic makes of the body: the one-row DMAs out of the tiled
view of `rows`, the waits by bytes, the scale's lane broadcast.

Run: python tools/smoke_pallas_moe_combine.py   (leg B of chip_smoke.py)
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
from distributed_embeddings_tpu.layers import moe
from distributed_embeddings_tpu.ops import pallas_moe_combine as pmc
from distributed_embeddings_tpu.parallel.mesh import require_tpu

D = 2048
# float32 sums of at most 8 terms in another order
TOLERANCE = 2e-5


def main():
  print("device:", json.dumps(require_tpu("smoke_pallas_moe_combine")),
        flush=True)
  rng = np.random.default_rng(43)
  failed = []
  for tokens, top_k, n_rows in ((256, 8, 1024), (8192, 8, 32768)):
    n = tokens * top_k
    order = rng.permutation(n).astype(np.int32)
    pos = np.empty(n, np.int32)
    pos[order] = np.arange(n, dtype=np.int32)
    n_live = n_rows // 4
    tok = jnp.asarray(order[:n_rows] // top_k)
    pos = jnp.asarray(pos.reshape(tokens, top_k))
    rows = jax.random.normal(jax.random.PRNGKey(tokens), (n_rows, D),
                             jnp.float32)
    p = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
    p = jnp.where(jnp.asarray(rng.random((tokens, top_k)) < 0.1), 0.0, p)
    live = jnp.arange(n_rows) < n_live
    p_sorted = jnp.take(p.reshape(n), jnp.asarray(order))[:n_rows]

    # the kernel alone: dead rows hold NaN, their scale is zero
    scale = jnp.where(pos < n_live, p, 0.0)
    got = jax.jit(pmc.combine)(
        jnp.where(live[:, None], rows, jnp.nan), pos, scale)
    want = jax.jit(lambda r: jnp.zeros((tokens, D), r.dtype).at[tok].add(
        jnp.where(live[:, None], r * p_sorted[:, None], 0)))(rows)
    err = float(jnp.max(jnp.abs(got - want)))
    size = float(jnp.max(jnp.abs(want)))
    ok = bool(jnp.all(jnp.isfinite(got))) and err <= TOLERANCE * max(size, 1.0)
    print(f"combine vs scatter-add  T={tokens:5d} R={n_rows:5d}: "
          f"{'OK' if ok else 'FAIL'} (max err {err:.2e}, scale {size:.1f})",
          flush=True)
    if not ok:
      failed.append(f"combine {tokens}")

    # the pair through jax.grad: h, the experts' rows (through w), p
    h = jax.random.normal(jax.random.PRNGKey(1), (tokens, D), jnp.float32)
    c = jax.random.normal(jax.random.PRNGKey(2), (tokens, D), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (D,), jnp.float32)
    n_live_j = jnp.int32(n_live)

    def plain(h, w, p):
      x = jnp.where(live[:, None], jnp.take(h, tok, axis=0), 0)
      y = jnp.tanh(x * w)
      p_c = jnp.take(p.reshape(n), jnp.asarray(order))[:n_rows]
      y = jnp.where(live[:, None], y * p_c[:, None], 0)
      return jnp.sum(jnp.zeros_like(h).at[tok].add(y) * c)

    def paired(h, w, p):
      y = jnp.tanh(moe._dispatch(False, h, tok, pos, n_live_j) * w)
      p_c = jnp.take(p.reshape(n), jnp.asarray(order))[:n_rows]
      out = moe._combine(False, y, p_c, tok, pos, jax.lax.stop_gradient(p),
                         n_live_j)
      return jnp.sum(out * c)

    want_g = jax.jit(jax.grad(plain, argnums=(0, 1, 2)))(h, w, p)
    got_g = jax.jit(jax.grad(paired, argnums=(0, 1, 2)))(h, w, p)
    for name, a, b in zip(("dh", "dw", "dp"), got_g, want_g):
      err = float(jnp.max(jnp.abs(a - b)))
      size = float(jnp.max(jnp.abs(b)))
      # dw sums 2,048 to 8,192 rows a lane: its order of summation differs
      ok = err <= (1e-3 if name == "dw" else TOLERANCE) * max(size, 1.0)
      print(f"  grad {name} through the pair          : "
            f"{'OK' if ok else 'FAIL'} (max err {err:.2e}, scale {size:.1f})",
            flush=True)
      if not ok:
        failed.append(f"{name} {tokens}")
  if failed:
    print("FAIL:", ", ".join(failed))
    sys.exit(1)
  print("PASS")


if __name__ == "__main__":
  enable_compile_cache()
  main()
