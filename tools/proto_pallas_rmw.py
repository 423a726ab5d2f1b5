"""Prototype: Pallas read-modify-write scatter vs XLA scatter-add.

Measures the per-row cost ceiling of DMA-pipelined random-row RMW on the
real chip. Correctness for duplicate ids is NOT handled here (timing uses
ids drawn without replacement per chunk); the production kernel gates on
this number being clearly under XLA's ~75 ns/row.

Usage: python tools/proto_pallas_rmw.py [n_ids] [rows] [depth] [chunk]
"""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_IDS = int(sys.argv[1]) if len(sys.argv) > 1 else 9 * 65536
ROWS = int(sys.argv[2]) if len(sys.argv) > 2 else 1 << 23
DEPTH = int(sys.argv[3]) if len(sys.argv) > 3 else 8
CHUNK = int(sys.argv[4]) if len(sys.argv) > 4 else 4096
W = 128
K = 8


def rmw_scatter(buf, ids, delta, depth=DEPTH, chunk=CHUNK):
  """buf[ids[i]] += delta[i] via per-row DMA RMW. Assumes no duplicate id
  is in flight within `depth` positions (prototype)."""
  n = ids.shape[0]
  assert n % chunk == 0

  def kernel(ids_ref, buf_in, delta_ref, buf_out, rbuf, wbuf, rsem, wsem):
    def start_read(j):
      idx = ids_ref[j]
      pltpu.make_async_copy(
          buf_in.at[pl.ds(idx, 1), :], rbuf.at[j % depth], rsem.at[j % depth]
      ).start()

    for j in range(depth):
      start_read(j)

    def body(j, _):
      slot = j % depth
      pltpu.make_async_copy(
          buf_in.at[pl.ds(0, 1), :], rbuf.at[slot], rsem.at[slot]).wait()

      @pl.when(j >= depth)
      def _():
        pltpu.make_async_copy(
            wbuf.at[slot], buf_out.at[pl.ds(0, 1), :], wsem.at[slot]).wait()

      wbuf[slot] = rbuf[slot] + delta_ref[pl.ds(j, 1), :]
      idx = ids_ref[j]
      pltpu.make_async_copy(
          wbuf.at[slot], buf_out.at[pl.ds(idx, 1), :], wsem.at[slot]).start()

      @pl.when(j + depth < chunk)
      def _():
        start_read(j + depth)

      return 0

    jax.lax.fori_loop(0, chunk, body, 0)

    def drain(j, _):
      pltpu.make_async_copy(
          wbuf.at[j % depth], buf_out.at[pl.ds(0, 1), :],
          wsem.at[j % depth]).wait()
      return 0

    jax.lax.fori_loop(max(0, chunk - depth), chunk, drain, 0)

  return pl.pallas_call(
      kernel,
      grid=(n // chunk,),
      in_specs=[
          pl.BlockSpec((chunk,), lambda i: (i,),
                       memory_space=pltpu.SMEM),  # ids chunk
          pl.BlockSpec(memory_space=pl.ANY),  # buf (aliased)
          pl.BlockSpec((chunk, W), lambda i: (i, 0)),  # delta
      ],
      out_specs=pl.BlockSpec(memory_space=pl.ANY),
      scratch_shapes=[
          pltpu.VMEM((DEPTH, 1, W), jnp.float32),
          pltpu.VMEM((DEPTH, 1, W), jnp.float32),
          pltpu.SemaphoreType.DMA((DEPTH,)),
          pltpu.SemaphoreType.DMA((DEPTH,)),
      ],
      out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
      input_output_aliases={1: 0},
      compiler_params=pltpu.CompilerParams(has_side_effects=True),
  )(ids, buf, delta)


def write_only(buf, ids, delta, depth=DEPTH, chunk=CHUNK):
  """Ceiling probe: random-row writes, no read/add."""
  n = ids.shape[0]

  def kernel(ids_ref, buf_in, delta_ref, buf_out, wsem):
    def body(j, _):
      slot = j % depth

      @pl.when(j >= depth)
      def _():
        pltpu.make_async_copy(
            delta_ref.at[pl.ds(0, 1), :], buf_out.at[pl.ds(0, 1), :],
            wsem.at[slot]).wait()

      idx = ids_ref[j]
      pltpu.make_async_copy(
          delta_ref.at[pl.ds(j, 1), :], buf_out.at[pl.ds(idx, 1), :],
          wsem.at[slot]).start()
      return 0

    jax.lax.fori_loop(0, chunk, body, 0)

    def drain(j, _):
      pltpu.make_async_copy(
          delta_ref.at[pl.ds(0, 1), :], buf_out.at[pl.ds(0, 1), :],
          wsem.at[j % depth]).wait()
      return 0

    jax.lax.fori_loop(max(0, chunk - depth), chunk, drain, 0)

  return pl.pallas_call(
      kernel,
      grid=(n // chunk,),
      in_specs=[
          pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.SMEM),
          pl.BlockSpec(memory_space=pl.ANY),
          pl.BlockSpec((chunk, W), lambda i: (i, 0)),
      ],
      out_specs=pl.BlockSpec(memory_space=pl.ANY),
      scratch_shapes=[pltpu.SemaphoreType.DMA((DEPTH,))],
      out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
      input_output_aliases={1: 0},
      compiler_params=pltpu.CompilerParams(has_side_effects=True),
  )(ids, buf, delta)


def timeit(name, fn, buf, ids, delta):
  step = jax.jit(fn, donate_argnums=(0,))
  carry = step(buf, ids, delta)
  jax.block_until_ready(carry)
  float(carry[0, 0])

  def run(n, carry):
    t0 = time.perf_counter()
    for _ in range(n):
      carry = step(carry, ids, delta)
    float(carry[0, 0])
    return time.perf_counter() - t0, carry

  _, carry = run(1, carry)  # absorb fetch-program compile
  t1, carry = run(K, carry)
  t2, carry = run(2 * K, carry)
  dt = (t2 - t1) / K
  print(f"{name:34s}: {dt * 1e3:8.2f} ms  {dt / N_IDS * 1e9:6.1f} ns/row",
        flush=True)
  return carry


def main():
  print(f"n_ids={N_IDS} rows={ROWS} depth={DEPTH} chunk={CHUNK}")
  key = jax.random.PRNGKey(0)
  rng = np.random.default_rng(0)
  buf = jnp.zeros((ROWS, W), jnp.float32)
  # per-chunk duplicate-free ids (prototype correctness assumption)
  ids_np = np.concatenate([
      rng.choice(ROWS, CHUNK, replace=False)
      for _ in range(N_IDS // CHUNK)]).astype(np.int32)
  ids = jnp.asarray(ids_np)
  delta = jax.random.normal(key, (N_IDS, W), jnp.float32)

  # correctness probe at small size (vs XLA scatter)
  small_buf = jnp.zeros((1 << 16, W), jnp.float32)
  sid = jnp.asarray(rng.choice(1 << 16, CHUNK, replace=False).astype(np.int32))
  sdelta = jax.random.normal(key, (CHUNK, W), jnp.float32)
  got = rmw_scatter(small_buf, sid, sdelta)
  want = jnp.zeros((1 << 16, W), jnp.float32).at[sid].add(sdelta)
  print("rmw correct:", bool(jnp.allclose(got, want, atol=1e-6)))

  buf = timeit("pallas rmw", rmw_scatter, buf, ids, delta)
  buf = timeit("pallas write-only", write_only, buf, ids, delta)

  def xla_scatter(buf, ids, delta):
    return buf.at[ids].add(delta, mode="drop")

  buf = timeit("xla scatter", xla_scatter, buf, ids, delta)


if __name__ == "__main__":
  main()
