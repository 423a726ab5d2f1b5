"""XLA scatter-add regime matrix: ns/row vs (buffer size x id-stream mix).

Decides the planner's generation-assignment policy: which combinations of
buffer size and power-law id mix keep the backward scatter in its fast
regime.

Usage: PYTHONPATH=/root/repo python -u tools/profile_scatter_regimes.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.models.synthetic import power_law_ids

B = 65536
K_REPS = 5


def _sync(x):
  float(jnp.asarray(x).ravel()[0])


def timeit(name, buf, ids, upd, n):
  step = jax.jit(lambda b, g, u: b.at[g].add(u, mode="drop"),
                 donate_argnums=(0,))
  carry = step(buf, ids, upd)
  _sync(carry)

  def run(k, carry):
    t0 = time.perf_counter()
    for _ in range(k):
      carry = step(carry, ids, upd)
    _sync(carry)
    return time.perf_counter() - t0, carry

  _, carry = run(1, carry)
  t1, carry = run(K_REPS, carry)
  t2, carry = run(2 * K_REPS, carry)
  dt = (t2 - t1) / K_REPS
  print(f"{name:58s}: {dt * 1e3:8.2f} ms  {dt / n * 1e9:6.1f} ns/row",
        flush=True)
  del carry


def main():
  rng = np.random.default_rng(0)

  def stream_1hot(n_tables, vocab, rows_total):
    """n_tables 1-hot inputs, tables laid side by side (phys ids)."""
    parts = []
    step_off = rows_total // max(n_tables, 1)
    for t in range(n_tables):
      ids = power_law_ids(rng, B, 1, vocab, 1.05).ravel() // 4
      parts.append(ids + t * step_off)
    return np.concatenate(parts).astype(np.int32)

  def stream_10hot(vocab, off):
    # id + offset <= sum of profiled vocabs, < 2^31 at bench scale
    return (power_law_ids(rng, B, 10, vocab, 1.05)  # graftlint: disable=GL106
            .ravel() // 4 + off).astype(np.int32)

  cases = []
  for phys_rows, label in ((1_000_000, "0.5GB"), (4_150_000, "2.1GB"),
                           (8_300_000, "4.2GB")):
    rt = phys_rows  # phys rows
    # 9 x 1-hot over 1M-vocab tables (the slow fusion.8 stream shape)
    s = stream_1hot(9, 1_000_000, rt * 4)
    cases.append((f"9x1hot 1M-vocab -> {label}", phys_rows, s))
    # 1-hot over a vocab as big as the buffer
    s = stream_1hot(1, rt * 4, rt * 4)
    cases.append((f"1x1hot full-vocab -> {label}", phys_rows, s))
    # 10-hot heavy dup
    s = stream_10hot(min(25_000_000, rt * 4), 0)
    cases.append((f"1x10hot 25M-vocab -> {label}", phys_rows, s))
    # mixed: 9x1hot + 10hot
    s = np.concatenate([stream_1hot(9, 1_000_000, rt * 4),
                        stream_10hot(min(25_000_000, rt * 4), 0)])
    cases.append((f"9x1hot + 10hot mixed -> {label}", phys_rows, s))

  for name, phys_rows, ids_np in cases:
    n = ids_np.shape[0]
    ids = jnp.asarray(np.clip(ids_np, 0, phys_rows - 1))
    upd = jnp.asarray(rng.standard_normal((n, 128)).astype(np.float32) * 1e-6)
    buf = jnp.zeros((phys_rows, 128), jnp.float32)
    timeit(f"{name} (n={n})", buf, ids, upd, n)
    del ids, upd, buf


if __name__ == "__main__":
  main()
