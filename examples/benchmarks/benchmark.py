"""Embedding lookup microbenchmark.

Equivalent of `/root/reference/examples/benchmarks/benchmark.py:23-98`: times
the fused variable-hotness (CSR) lookup against the naive dense-padded
gather+reduce, forward / backward / SGD-apply, at vocab 1M x width 128,
batch 16384, hotness <= 500.

On the reference's GPUs the fused CSR kernel wins; on TPU the answer
INVERTS (measured round 5, v5e: padded-dense forward 11.3 ms = 10.8
ns/row at the gather floor vs csr_lookup 92.7 ms — XLA's ragged
segment-sum does not pipeline) — which is why the distributed engine
serves ragged inputs through sentinel-padded buckets rather than CSR.
Timing chains executions through a donated accumulator at two chain
lengths and differences them, so dispatch overhead cancels.

  python examples/benchmarks/benchmark.py [--platform cpu] [--hotness 64]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np


def parse_args():
  p = argparse.ArgumentParser()
  p.add_argument("--vocab", type=int, default=1_000_000)
  p.add_argument("--width", type=int, default=128)
  p.add_argument("--batch", type=int, default=16384)
  p.add_argument("--hotness", type=int, default=64,
                 help="max hotness (uniform 1..max per row)")
  p.add_argument("--steps", type=int, default=4,
                 help="chain length (two lengths, N and 2N, are differenced)")
  p.add_argument("--combiner", default="sum", choices=["sum", "mean"])
  p.add_argument("--platform", default=None)
  return p.parse_args()


def timeit(fn, params, ids0, steps=4):
  """Chained two-length differencing (the bench.py pattern): a donated
  accumulator consumes every output, so the chain has a true serial
  dependency; one scalar is fetched at the end and two lengths are
  differenced so dispatch overhead cancels. Returns ms per execution."""
  # params stays an ARGUMENT (closing over it would bake the 512 MB table
  # into the program as a constant)
  acc_step = jax.jit(lambda acc, p, i: acc + fn(p, i), donate_argnums=0)
  acc = acc_step(jnp.zeros_like(fn(params, ids0)), params, ids0)
  float(acc.ravel()[0])

  def run(k, a):
    t0 = time.perf_counter()
    for _ in range(k):
      a = acc_step(a, params, ids0)
    float(a.ravel()[0])
    return time.perf_counter() - t0, a

  t1, acc = run(steps, acc)
  t2, acc = run(2 * steps, acc)
  return max((t2 - t1) / steps, 1e-9) * 1000


def main():
  args = parse_args()
  if args.platform:
    jax.config.update("jax_platforms", args.platform)
  from distributed_embeddings_tpu.ops import RaggedIds, csr_lookup

  rng = np.random.default_rng(0)
  params = jnp.asarray(
      rng.standard_normal((args.vocab, args.width)), jnp.float32)
  lengths = rng.integers(1, args.hotness + 1, args.batch)
  nnz = int(lengths.sum())
  values = jnp.asarray(rng.integers(0, args.vocab, nnz), jnp.int32)
  row_splits = jnp.asarray(
      np.concatenate([[0], np.cumsum(lengths)]), jnp.int32)
  dense_ids = jnp.asarray(
      rng.integers(0, args.vocab, (args.batch, args.hotness)), jnp.int32)
  print(f"vocab={args.vocab} width={args.width} batch={args.batch} "
        f"avg_hotness={nnz / args.batch:.1f} nnz={nnz} on "
        f"{jax.devices()[0].platform}")

  fused_fwd = jax.jit(
      lambda p, v: csr_lookup(p, v, row_splits, args.combiner))
  naive_fwd = jax.jit(
      lambda p, i: jnp.sum(jnp.take(p, i, axis=0), axis=1)
      if args.combiner == "sum"
      else jnp.mean(jnp.take(p, i, axis=0), axis=1))

  def grad_of(fwd):
    return jax.jit(jax.grad(lambda p, i: jnp.sum(fwd(p, i) ** 2)))

  def sgd_of(fwd):
    g = jax.grad(lambda p, i: jnp.sum(fwd(p, i) ** 2))
    return jax.jit(lambda p, i: p - 0.01 * g(p, i), donate_argnums=0)

  rows = []
  for name, fwd, ids0 in [("fused_csr", fused_fwd, values),
                          ("padded_dense", naive_fwd, dense_ids)]:
    t_f = timeit(fwd, params, ids0, steps=args.steps)
    t_g = timeit(grad_of(fwd), params, ids0, steps=args.steps)
    sgd = sgd_of(fwd)

    def sgd_chain(k, p0, sgd=sgd, ids0=ids0):
      t0 = time.perf_counter()
      for _ in range(k):
        p0 = sgd(p0, ids0)
      float(p0.ravel()[0])
      return time.perf_counter() - t0, p0

    p = params + 0  # fresh buffer: sgd donates its input
    _, p = sgd_chain(1, p)  # compile
    d1, p = sgd_chain(args.steps, p)
    d2, p = sgd_chain(2 * args.steps, p)
    t_s = max((d2 - d1) / args.steps, 1e-9) * 1000
    rows.append((name, t_f, t_g, t_s))
    print(f"{name:>14}: forward {t_f:8.3f} ms  grad {t_g:8.3f} ms  "
          f"sgd-step {t_s:8.3f} ms")
  speedup = rows[1][3] / rows[0][3]
  print(f"fused vs padded sgd-step speedup: {speedup:.2f}x")
  print("note: on TPU the padded-dense form IS the fast form (gathers "
        "run ~10 ns/row regardless of padding waste; XLA's ragged "
        "segment-sum lowering does not pipeline) — the OPPOSITE of the "
        "reference's CUDA result, and why the distributed engine "
        "normalizes ragged inputs into sentinel-padded buckets "
        "internally (docs/ARCHITECTURE.md). csr_lookup is the "
        "API-parity/correctness form.")


if __name__ == "__main__":
  main()
