"""Measure the Pallas batched-DMA gather bound vs XLA's gather.

VERDICT r3 asked whether a Pallas gather issuing row DMAs from the
scalar core (the A100 kernel's smem-staged batched fetch, translated)
can beat XLA's gather on the zoo's streams. This prototype measures the
per-row cost of the most favorable Pallas shape: a straight
HBM->HBM row copy pipeline, one DMA per occurrence, no extraction work,
depth-N in flight, semaphore waits amortized N at a time — an upper
bound for any DMA-per-row gather design (a real one still pays masking /
sub-row handling).

Compares against jnp.take on the same id stream (uniform and the Tiny
power-law mix).

Measured (round 4, v5e, 1M ids / 1M rows, zipf-1.2 stream; chained
dependency harness): XLA take 11.9 ns/row, this kernel 13.8 ns/row,
bit-exact parity (an earlier same-args harness read 11.7 vs 11.3; the
uniform stream's chained timings were unstable and are not cited) — the scalar
core sustains ~one row DMA per 11 ns, the same rate XLA's gather
already streams at, so a DMA-per-row Pallas gather (however batched)
cannot deliver the 2-3x the zoo's gather share would need. The A100
kernel's ~6 ns/occ comes from 100+ parallel CTAs issuing smem-staged
fetches — there is no analogous parallel issue resource on v5e (one
scalar core; SparseCore on v4/v5p is that resource). Conclusion
recorded in docs/BENCHMARKS.md; the zoo's single-chip floor stands on
per-occurrence row-op costs, and the scaling story is sharding the
occurrence stream over the mesh.

Usage: python tools/proto_pallas_gather.py [n_ids] [rows]
"""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
ROWS = int(sys.argv[2]) if len(sys.argv) > 2 else 1 << 20
W = 128
DEPTH = 128  # in-flight row DMAs


def _gather_kernel(chunk, total, ids_ref, buf, out, sem):
  c = pl.program_id(0)

  def issue(j, _):
    idx = ids_ref[j]
    g = c * chunk + j  # global position: slot reuse crosses grid steps
    slot = jnp.bitwise_and(g, DEPTH - 1)
    # wait the slot's previous copy before reusing its semaphore
    @pl.when(g >= DEPTH)
    def _():
      pltpu.make_async_copy(
          buf.at[pl.ds(0, 1), :], out.at[pl.ds(0, 1), :],
          sem.at[slot]).wait()
    pltpu.make_async_copy(
        buf.at[pl.ds(idx, 1), :], out.at[pl.ds(g, 1), :],
        sem.at[slot]).start()
    return 0

  jax.lax.fori_loop(0, chunk, issue, 0)

  nc = pl.num_programs(0)

  @pl.when(pl.program_id(0) == nc - 1)
  def _drain():
    def wait_one(s, _):
      pltpu.make_async_copy(
          buf.at[pl.ds(0, 1), :], out.at[pl.ds(0, 1), :], sem.at[s]).wait()
      return 0
    # the outstanding window spans the last min(DEPTH, total) GLOBAL
    # positions (slot reuse crosses grid steps), not just this chunk's
    jax.lax.fori_loop(0, min(DEPTH, total), wait_one, 0)


def pallas_gather(buf, ids, chunk=8192):
  n = ids.shape[0]
  chunk = min(chunk, n)
  pad = (-n) % chunk
  if pad:  # tail chunk: pad with row 0 (dropped below), never truncate
    ids = jnp.concatenate([ids, jnp.zeros((pad,), ids.dtype)])
  kernel = functools.partial(_gather_kernel, chunk, n + pad)
  out = pl.pallas_call(
      kernel,
      grid=((n + pad) // chunk,),
      in_specs=[
          pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.SMEM),
          pl.BlockSpec(memory_space=pl.ANY),
      ],
      out_specs=pl.BlockSpec(memory_space=pl.ANY),
      out_shape=jax.ShapeDtypeStruct((n + pad, W), buf.dtype),
      scratch_shapes=[pltpu.SemaphoreType.DMA((DEPTH,))],
      compiler_params=pltpu.CompilerParams(has_side_effects=True),
  )(ids, buf)
  return out[:n]


def timeit(name, fn, buf, ids):
  # chain: each call's ids depend on the previous output so no caching /
  # reordering layer can collapse repeated executions
  step = jax.jit(lambda b, i, bump: fn(b, (i + bump) % b.shape[0]))
  # warm with the SAME operand type the timed loop passes (a weak-typed
  # Python int would compile a different cache entry and the recompile
  # would land inside the first timed run)
  out = step(buf, ids, jnp.zeros((), ids.dtype))
  jax.block_until_ready(out)

  def run(k, o):
    t0 = time.perf_counter()
    for _ in range(k):
      bump = (o[0, 0] * 0).astype(ids.dtype)
      o = step(buf, ids, bump)
    jax.block_until_ready(o)
    return time.perf_counter() - t0, o

  t1, out = run(8, out)
  t2, out = run(16, out)
  ns = (t2 - t1) / 8 / N * 1e9
  print(f"{name:36s}: {ns:6.1f} ns/row", flush=True)
  return out


def main():
  rng = np.random.default_rng(0)
  buf = jnp.asarray(rng.standard_normal((ROWS, W)), jnp.float32)
  streams = {
      "uniform": rng.integers(0, ROWS, N).astype(np.int32),
      "zipf(1.2)": (rng.zipf(1.2, N) % ROWS).astype(np.int32),
  }
  for sname, ids_np in streams.items():
    ids = jnp.asarray(ids_np)
    want = timeit(f"XLA take / {sname}",
                  lambda b, i: jnp.take(b, i, axis=0), buf, ids)
    got = timeit(f"pallas DMA-per-row / {sname}", pallas_gather, buf, ids)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"  parity: max err {err:.1e}")


if __name__ == "__main__":
  main()
