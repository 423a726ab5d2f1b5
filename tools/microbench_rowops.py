"""Microbenchmark: indexed row ops on the real TPU chip.

Measures rows/s for the primitives that bound the sparse embedding path
(SURVEY §6 / bench.py): XLA gather (`jnp.take`), XLA scatter-add
(`.at[].add`), and a Pallas row-DMA gather with a D-deep in-flight window.

Timing: each measurement chains K iterations inside one jit
(data-dependent carry) and fetches a scalar; the separately measured fetch
round trip is subtracted.

Usage: python tools/microbench_rowops.py [n_ids] [rows] [width]
"""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_IDS = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 22
ROWS = int(sys.argv[2]) if len(sys.argv) > 2 else 1 << 22
WIDTH = int(sys.argv[3]) if len(sys.argv) > 3 else 128
K = 8  # chained iterations per measurement


def fetch_rtt():
  probe = jax.jit(lambda x: x + 1)(jnp.zeros(()))
  float(probe)  # force compile + first fetch
  t0 = time.perf_counter()
  for _ in range(4):
    float(jax.jit(lambda x: x + 2)(probe))
  return (time.perf_counter() - t0) / 4


def timed(make_chain, *args, rtt=0.0):
  """make_chain(*args) -> jit fn running K data-dependent iterations and
  returning a scalar. Returns seconds per iteration."""
  fn = make_chain(*args)
  float(fn(*args))  # compile + warm
  t0 = time.perf_counter()
  float(fn(*args))
  return (time.perf_counter() - t0 - rtt) / K


def chain_gather(gather):
  """Chain K gathers with a data-dependent id perturbation (defeats CSE)."""

  def make(table, ids):
    @jax.jit
    def run(table, ids):
      def body(carry, k):
        acc, ids = carry
        out = gather(table, ids)
        # fold a cheap data dependency into the next iteration's ids
        bump = (out[0, 0] > jnp.inf).astype(jnp.int32)  # always 0, data-dep
        return (acc + out[0, 0], ids + bump), None

      (acc, _), _ = jax.lax.scan(body, (jnp.zeros((), table.dtype), ids),
                                 jnp.arange(K))
      return acc

    return run

  return make


def chain_scatter():
  def make(table, ids, deltas):
    @jax.jit
    def run(table, ids, deltas):
      def body(t, k):
        return t.at[ids].add(deltas, mode="drop"), None

      t, _ = jax.lax.scan(body, table, jnp.arange(K))
      return t[0, 0]

    return run

  return make


def pallas_gather(table, ids, tile=512, depth=8):
  n = ids.shape[0]
  w = table.shape[1]

  def kernel(ids_ref, table_ref, out_ref, sem):
    i = pl.program_id(0)

    def dma(j):
      idx = ids_ref[i * tile + j]
      return pltpu.make_async_copy(
          table_ref.at[pl.ds(idx, 1), :],
          out_ref.at[pl.ds(j, 1), :],
          sem.at[j % depth])

    for j in range(depth):
      dma(j).start()

    def body(j, _):
      dma(j).wait()

      @pl.when(j + depth < tile)
      def _():
        dma(j + depth).start()

      return 0

    jax.lax.fori_loop(0, tile, body, 0)

  return pl.pallas_call(
      kernel,
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1,
          grid=(n // tile,),
          in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
          out_specs=pl.BlockSpec((tile, w), lambda i, ids: (i, 0)),
          scratch_shapes=[pltpu.SemaphoreType.DMA((depth,))],
      ),
      out_shape=jax.ShapeDtypeStruct((n, w), table.dtype),
  )(ids, table)


def report(name, dt):
  print(f"{name:20s}: {dt * 1e3:8.2f} ms  {dt / N_IDS * 1e9:6.2f} ns/row  "
        f"{N_IDS * WIDTH * 4 / dt / 1e9:6.0f} GB/s")


def main():
  dev = jax.devices()[0]
  print(f"device: {dev.device_kind} ({dev.platform}), n_ids={N_IDS} "
        f"rows={ROWS} width={WIDTH}")
  rtt = fetch_rtt()
  print(f"fetch RTT: {rtt * 1e3:.1f} ms")
  table = jax.random.normal(jax.random.PRNGKey(0), (ROWS, WIDTH), jnp.float32)
  ids = jax.random.randint(jax.random.PRNGKey(1), (N_IDS,), 0, ROWS,
                           jnp.int32)
  deltas = jax.random.normal(jax.random.PRNGKey(2), (N_IDS, WIDTH),
                             jnp.float32)

  # HBM bandwidth reference: chained whole-table scale
  @jax.jit
  def copy_chain(t):
    def body(t, _):
      return t * 1.0000001, None
    t, _ = jax.lax.scan(body, t, jnp.arange(K))
    return t[0, 0]

  float(copy_chain(table))
  t0 = time.perf_counter()
  float(copy_chain(table))
  dt = (time.perf_counter() - t0 - rtt) / K
  print(f"copy {ROWS}x{WIDTH}: {dt * 1e3:.2f} ms/iter -> "
        f"{2 * ROWS * WIDTH * 4 / dt / 1e9:.0f} GB/s (r+w)")

  take = lambda t, i: jnp.take(t, i, axis=0, mode="fill", fill_value=0)
  report("jnp.take", timed(chain_gather(take), table, ids, rtt=rtt))
  report(".at[].add", timed(chain_scatter(), table, ids, deltas, rtt=rtt))

  for tile, depth in [(512, 8), (512, 16), (1024, 16), (1024, 32),
                      (2048, 32)]:
    g = functools.partial(pallas_gather, tile=tile, depth=depth)
    try:
      dt = timed(chain_gather(g), table, ids, rtt=rtt)
    except Exception as e:  # noqa: BLE001
      print(f"pallas t{tile} d{depth}: FAILED {type(e).__name__}: "
            f"{str(e)[:160]}")
      continue
    report(f"pallas t{tile} d{depth}", dt)

  got = np.asarray(pallas_gather(table, ids[:1 << 16]))
  want = np.asarray(jnp.take(table, ids[:1 << 16], axis=0))
  print("pallas gather correct:", np.array_equal(got, want))


if __name__ == "__main__":
  main()
