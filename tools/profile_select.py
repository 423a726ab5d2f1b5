"""Sub-row extraction/expansion: one-hot einsum vs VPU where-select.

The Tiny anatomy charges ~28 ms to the apply's lane expansion and ~25 ms to
the gather's sub-row extraction — both one-hot einsums over [n, rpp, stride]
that SHOULD be bandwidth-bound (~4 ms at these shapes). This measures the
einsum forms against pure where/select forms.

Usage: PYTHONPATH=/root/repo python -u tools/profile_select.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.models.synthetic import power_law_ids
from distributed_embeddings_tpu.ops.packed_table import PackedLayout

B = 65536
K_REPS = 5
LAYOUT = PackedLayout(rows=52_200_000, width=16, n_aux=1)


def _sync(x):
  leaf = jax.tree_util.tree_leaves(x)[0]
  float(jnp.asarray(leaf).ravel()[0])


def timeit(name, fn, buf, *args, donate=True, n_norm=None):
  step = jax.jit(fn, donate_argnums=(0,) if donate else ())
  carry = step(buf, *args)
  _sync(carry)

  def run(n, carry):
    t0 = time.perf_counter()
    for _ in range(n):
      carry = step(carry, *args)
    _sync(carry)
    return time.perf_counter() - t0, carry

  _, carry = run(1, carry)
  t1, carry = run(K_REPS, carry)
  t2, carry = run(2 * K_REPS, carry)
  dt = (t2 - t1) / K_REPS
  per = f"  {dt / n_norm * 1e9:6.1f} ns/elem" if n_norm else ""
  print(f"{name:52s}: {dt * 1e3:8.2f} ms{per}", flush=True)
  return carry


def main():
  rng = np.random.default_rng(0)
  ids_np = (power_law_ids(rng, B, 44, 25_000_000, 1.05).ravel()
            .astype(np.int32))
  n = ids_np.shape[0]
  rpp, stride = LAYOUT.rows_per_phys, LAYOUT.stride  # 4, 32
  grp = jnp.asarray((ids_np // rpp).astype(np.int32))
  sub = jnp.asarray((ids_np % rpp).astype(np.int32))
  delta32 = jnp.asarray(
      rng.standard_normal((n, stride)).astype(np.float32) * 1e-6)
  print(f"n={n}")

  # --- expansion [n,32] -> [n,128] ---------------------------------------
  def exp_einsum(d, s):
    oh = jax.nn.one_hot(s, rpp, dtype=d.dtype)
    return jnp.einsum("ns,nr->nrs", d, oh).reshape(-1, rpp * stride)

  def exp_where(d, s):
    # tile the 32-lane delta to 128 lanes, zero all but the sub window
    tiled = jnp.tile(d, (1, rpp))  # [n, 128]
    win = jax.lax.broadcasted_iota(jnp.int32, (1, rpp * stride), 1) // stride
    return jnp.where(win == s[:, None], tiled, 0.0)

  def run_exp(name, f):
    def step(c, d, s):
      s = s + jnp.minimum(c.astype(jnp.int32), 0)
      e = f(d, s)
      return c + jnp.tanh(jnp.sum(e)) * 0 + jnp.float32(0)
    timeit(name, step, jnp.zeros((), jnp.float32), delta32, sub,
           donate=False, n_norm=n)

  # numerics check
  a = exp_einsum(delta32[:1024], sub[:1024])
  b = exp_where(delta32[:1024], sub[:1024])
  print(f"  expand parity: {float(jnp.max(jnp.abs(a - b))):.2e}")

  # (expansion+scatter variants were measured on TPU and recorded in
  # docs/BENCHMARKS.md: einsum+scatter 22.2 ns/elem vs where+scatter
  # 25.3 — the einsum form fuses better into the scatter and was kept.)

  # --- extraction: gather + sub-row select + 10-hot combine --------------
  buf_g = jnp.zeros((LAYOUT.phys_rows + 1, 128), jnp.float32)
  ids10 = jnp.asarray(power_law_ids(rng, B, 10, 25_000_000, 1.05)
                      .astype(np.int32))
  n10 = B * 10

  def gather_extract_einsum(c, bg, idsb):
    idsb = idsb + jnp.minimum(c.astype(jnp.int32), 0)
    g = idsb // rpp
    s = idsb % rpp
    rows = jnp.take(bg, g, axis=0, mode="fill", fill_value=0)
    rows = rows[..., :rpp * stride].reshape(idsb.shape + (rpp, stride))
    oh = jax.nn.one_hot(s, rpp, dtype=rows.dtype)
    fused = jnp.einsum("...rs,...r->...s", rows, oh)
    z = jnp.sum(fused[..., :16], axis=1)
    return c + jnp.tanh(jnp.sum(z) * 1e-6) * 0 + jnp.float32(0)

  def gather_extract_where(c, bg, idsb):
    idsb = idsb + jnp.minimum(c.astype(jnp.int32), 0)
    g = idsb // rpp
    s = idsb % rpp
    rows = jnp.take(bg, g, axis=0, mode="fill", fill_value=0)  # [B,10,128]
    win = jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, rpp * stride), 2) // stride
    masked = jnp.where(win == s[..., None], rows[..., :rpp * stride], 0.0)
    fused = jnp.sum(masked.reshape(idsb.shape + (rpp, stride)), axis=-2)
    z = jnp.sum(fused[..., :16], axis=1)
    return c + jnp.tanh(jnp.sum(z) * 1e-6) * 0 + jnp.float32(0)

  def gather_bagsum_where(c, bg, idsb):
    # sum phys rows over the bag FIRST (sum commutes), then window-select
    # per occurrence is unnecessary for the COMBINED result only when all
    # bag members were distinct lanes; instead select-before-sum at phys
    # width then one reshape-sum per bag:
    idsb = idsb + jnp.minimum(c.astype(jnp.int32), 0)
    g = idsb // rpp
    s = idsb % rpp
    rows = jnp.take(bg, g, axis=0, mode="fill", fill_value=0)
    win = jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, rpp * stride), 2) // stride
    masked = jnp.where(win == s[..., None], rows[..., :rpp * stride], 0.0)
    bag = jnp.sum(masked, axis=1)  # [B, 128]
    z = jnp.sum(bag.reshape(B, rpp, stride)[..., :16], axis=1)
    return c + jnp.tanh(jnp.sum(z) * 1e-6) * 0 + jnp.float32(0)

  timeit("gather + extract einsum + combine (today)", gather_extract_einsum,
         jnp.zeros((), jnp.float32), buf_g, ids10, donate=False, n_norm=n10)
  timeit("gather + extract where + combine", gather_extract_where,
         jnp.zeros((), jnp.float32), buf_g, ids10, donate=False, n_norm=n10)
  timeit("gather + where-mask + bag-sum + window-sum", gather_bagsum_where,
         jnp.zeros((), jnp.float32), buf_g, ids10, donate=False, n_norm=n10)
  # 1-hot stream: extraction variants matter there too (no bag to amortize)
  ids1 = jnp.asarray(power_law_ids(rng, B * 10, 1, 25_000_000, 1.05)
                     .astype(np.int32))
  timeit("1-hot gather + extract einsum", gather_extract_einsum,
         jnp.zeros((), jnp.float32), buf_g, ids1, donate=False, n_norm=n10)
  timeit("1-hot gather + extract where", gather_extract_where,
         jnp.zeros((), jnp.float32), buf_g, ids1, donate=False, n_norm=n10)


if __name__ == "__main__":
  main()
