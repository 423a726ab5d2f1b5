"""Quantify the narrow-minor-dim tile-padding tax on v5e.

Every [n, 16]/[n, 32] f32 intermediate is tile-padded to 128 lanes. If the
tax is real, a full-phys-width (128-lane) pipeline for narrow classes is
the remaining Tiny win; if not, the step is at its row-op floor.

Usage: PYTHONPATH=/root/repo python -u tools/profile_padding_tax.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

N = 2_883_584
K_REPS = 6


def _sync(x):
  leaf = jax.tree_util.tree_leaves(x)[0]
  float(jnp.asarray(leaf).ravel()[0])


def timeit(name, fn, *args, n_norm=None):
  step = jax.jit(fn)
  carry = step(jnp.zeros((), jnp.float32), *args)
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
  per = f"  {dt / n_norm * 1e9:6.1f} ns/row" if n_norm else ""
  print(f"{name:56s}: {dt * 1e3:8.2f} ms{per}", flush=True)


def main():
  rng = np.random.default_rng(0)

  # elementwise chain on [N, w]: 6 ops (mimics the adagrad rule math)
  for w in (16, 32, 128):
    x = jnp.asarray(rng.standard_normal((N, w)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((N, w)).astype(np.float32))

    def rule_math(c, a, b):
      a = a + jnp.minimum(c, 0.0)
      g2 = a * a
      acc = b + g2
      scaled = jnp.where(acc > 0, a * jax.lax.rsqrt(acc + 1e-7), 0.0)
      d = jnp.concatenate([-0.01 * scaled, g2], axis=-1)
      return c + jnp.tanh(jnp.sum(d) * 1e-6) * 0 + jnp.float32(0)

    timeit(f"adagrad rule math on [N,{w}] (+concat)", rule_math, x, y,
           n_norm=N)
    del x, y

  # combine: [G, 10, 32] -> sum axis 1 -> [G, 32]
  g10 = jnp.asarray(
      rng.standard_normal((65536, 10, 32)).astype(np.float32))

  def combine(c, r):
    r = r + jnp.minimum(c, 0.0)
    z = jnp.sum(r, axis=1)
    return c + jnp.tanh(jnp.sum(z) * 1e-6) * 0 + jnp.float32(0)

  timeit("combine sum [64k,10,32]->[64k,32]", combine, g10, n_norm=655360)
  del g10

  g10w = jnp.asarray(
      rng.standard_normal((65536, 10, 128)).astype(np.float32))
  timeit("combine sum [64k,10,128]->[64k,128]", combine, g10w,
         n_norm=655360)
  del g10w

  # broadcast of dz over hotness: [G, 32] -> [G*10, 32] (apply's g exp)
  dz = jnp.asarray(rng.standard_normal((65536, 32)).astype(np.float32))

  def bcast(c, d):
    d = d + jnp.minimum(c, 0.0)
    g = jnp.broadcast_to(d[:, None, :], (65536, 10, 32)).reshape(-1, 32)
    return c + jnp.tanh(jnp.sum(g * g) * 1e-6) * 0 + jnp.float32(0)

  timeit("dz broadcast [64k,32]->[655k,32] (+square)", bcast, dz,
         n_norm=655360)


if __name__ == "__main__":
  main()
