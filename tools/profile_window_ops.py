"""Window (2-D start) gather/scatter vs sub-row extract/expand, + compaction.

If XLA's TPU lowering keeps its ~10/20 ns per-row costs with a (row, lane)
start and a 32-lane window, the packed-table gather extraction einsum and
apply expansion einsum can be deleted entirely.

Usage: PYTHONPATH=/root/repo python -u tools/profile_window_ops.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.models.synthetic import power_law_ids
from distributed_embeddings_tpu.ops.packed_table import PackedLayout

B = 65536
ALPHA = 1.05
K_REPS = 5
LAYOUT = PackedLayout(rows=52_200_000, width=16, n_aux=1)


def _sync(x):
  jax.block_until_ready(x)


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
  print(f"{name:48s}: {dt * 1e3:8.2f} ms{per}", flush=True)
  return carry


def main():
  rng = np.random.default_rng(0)
  ids_np = (power_law_ids(rng, B, 44, 25_000_000, ALPHA).ravel()
            .astype(np.int32))
  n = ids_np.shape[0]
  rpp = LAYOUT.rows_per_phys
  stride = LAYOUT.stride
  grp_np = (ids_np // rpp).astype(np.int32)
  # (id % rpp) * stride < 128 lanes of one physical row
  lane_np = ((ids_np % rpp) * stride).astype(np.int32)  # graftlint: disable=GL106
  starts = jnp.stack(
      [jnp.asarray(grp_np), jnp.asarray(lane_np)], axis=1)  # [n, 2]
  print(f"n={n} rpp={rpp} stride={stride} phys_rows={LAYOUT.phys_rows}")

  bufw = jnp.zeros((LAYOUT.phys_rows + 1, 128), jnp.float32)

  # --- window gather: [n, 32] sub-rows straight out of the packed buffer
  gdn = jax.lax.GatherDimensionNumbers(
      offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0, 1))

  def win_gather(c, b, st):
    # carry-dependent starts (not provably zero) defeat constant folding
    # without touching the 6.7 GB operand
    st = st + jnp.minimum(c.astype(jnp.int32), 0)
    rows = jax.lax.gather(b, st, gdn, slice_sizes=(1, stride),
                          mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
    return c + jnp.tanh(jnp.sum(rows) * 1e-6) * 0 + jnp.float32(0)

  timeit("window-gather 2-D starts [n,32]", win_gather,
         jnp.zeros((), jnp.float32), bufw, starts, donate=False, n_norm=n)

  # --- plain row gather (floor reference)
  def row_gather(c, b, g):
    g = g + jnp.minimum(c.astype(jnp.int32), 0)
    rows = jnp.take(b, g, axis=0, mode="fill", fill_value=0)
    return c + jnp.tanh(jnp.sum(rows) * 1e-6) * 0 + jnp.float32(0)

  timeit("row-gather [n,128] (floor)", row_gather,
         jnp.zeros((), jnp.float32), bufw, jnp.asarray(grp_np),
         donate=False, n_norm=n)

  # --- window scatter-add
  sdn = jax.lax.ScatterDimensionNumbers(
      update_window_dims=(1,), inserted_window_dims=(0,),
      scatter_dims_to_operand_dims=(0, 1))
  upd32 = jnp.asarray(
      rng.standard_normal((n, stride)).astype(np.float32) * 1e-6)

  def win_scatter(b, st, u):
    return jax.lax.scatter_add(
        b, st, u, sdn, mode=jax.lax.GatherScatterMode.FILL_OR_DROP)

  c = timeit("window-scatter-add 2-D starts [n,32]", win_scatter, bufw,
             starts, upd32, n_norm=n)
  print(f"  checksum {float(jnp.sum(c[:64, :4])):.3e}")
  bufw = c

  # --- baseline: expansion einsum + full-row scatter (today's apply path)
  upd128 = jnp.asarray(
      rng.standard_normal((n, 128)).astype(np.float32) * 1e-6)

  def row_scatter(b, g, u):
    return b.at[g].add(u, mode="drop")

  bufw = timeit("row-scatter [n,128] (floor)", row_scatter, bufw,
                jnp.asarray(grp_np), upd128, n_norm=n)

  sub = jnp.asarray((ids_np % rpp).astype(np.int32))

  def expand_scatter(b, g, s, u):
    oh = jax.nn.one_hot(s, rpp, dtype=u.dtype)
    up = jnp.einsum("ns,nr->nrs", u, oh).reshape(-1, rpp * stride)
    return b.at[g].add(up, mode="drop")

  bufw = timeit("expand einsum + row-scatter (today)", expand_scatter, bufw,
                jnp.asarray(grp_np), sub, upd32, n_norm=n)
  del bufw

  # --- device compaction, non-foldable this time
  cold_cap = int(n * 0.55)

  def compact_step(c, ids_f):
    ids_f = ids_f + jnp.minimum(c, 0)
    is_cold = ids_f >= 4096
    csum = jnp.cumsum(is_cold.astype(jnp.int32))
    total = csum[-1]
    tgt = jnp.arange(1, cold_cap + 1, dtype=jnp.int32)
    src = jnp.searchsorted(csum, tgt)
    vals = jnp.take(ids_f, jnp.clip(src, 0, n - 1), mode="clip")
    vals = jnp.where(tgt <= total, vals, -1)
    return c + jnp.minimum(jnp.sum(vals == -12345), 0).astype(jnp.int32)

  timeit(f"device compaction cumsum+searchsorted+take (n={n})",
         compact_step, jnp.zeros((), jnp.int32), jnp.asarray(ids_np),
         donate=False, n_norm=n)


if __name__ == "__main__":
  main()
