"""The apply kernel's own microbenchmark: ns an occurrence, by kind of stream.

Times `ops/pallas_apply.apply_rows_cached` ON THE CHIP on one class buffer
of the one-chip DLRM cell's shape (tables of 2.49 M rows side by side, 128
lanes, 65,536 ids a table, in-kernel scale), with VMEM-resident heads and
without, on:

- ``powerlaw``: the cell's traffic (`benchmark/traffic.py::power_law_ids`,
  alpha 1.05, rank = id), per table, tables concatenated as the step does;
- ``uniform``: the same at alpha 0 (hashed ids: the head hardly engages,
  what is read here is what its per-occurrence test costs);
- ``all_head`` / ``all_hit`` / ``all_miss``: streams on which every
  occurrence is of one kind, which is what the three costs are read from.

Each timing is the host clock over a chain of donated calls that ends in
``block_until_ready`` (5 ms a call, so the dispatch does not show); each
variant's first result is checked against XLA's scatter-add. Prints one
JSON line per (stream, variant) and a table; the lines also go to
``chiprun_out/bench_pallas_apply.jsonl``.

Run: chiprun -- python tools/bench_pallas_apply.py [--head_rows 2048,8192]
Exits non-zero without a TPU.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.traffic import power_law_ids
from distributed_embeddings_tpu.compile_cache import enable_compile_cache
from distributed_embeddings_tpu.ops import pallas_apply
from distributed_embeddings_tpu.parallel.mesh import require_tpu

W = 128
OUT = os.path.join(os.path.dirname(__file__), "..", "chiprun_out",
                   "bench_pallas_apply.jsonl")


def streams(rng, tables, table_rows, per_table, head_rows):
  """name -> [tables * per_table] int32 physical rows of the class buffer."""
  offs = np.arange(tables) * table_rows

  def per(alpha):
    return np.concatenate([
        o + power_law_ids(rng, per_table, table_rows, alpha) for o in offs])

  n = tables * per_table
  out = {
      "powerlaw": per(1.05),
      "uniform": per(0.0),
      # every id inside a head, spread over it as the hot rows are
      "all_head": np.concatenate(
          [o + rng.integers(0, head_rows, per_table) for o in offs]),
      # the 128 rows just past the first head, each on a slot of its own and
      # read again and again: after the first round every one is a cache hit
      "all_hit": np.tile(head_rows + np.arange(128), n // 128 + 1)[:n],
      # distinct rows far apart: every one claims a slot (two row DMAs)
      "all_miss": np.concatenate(
          [o + head_rows + rng.permutation(table_rows - head_rows)[:per_table]
           for o in offs]),
  }
  return {k: v.astype(np.int32) for k, v in out.items()}


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--tables", type=int, default=2)
  ap.add_argument("--table_rows", type=int, default=2_492_000)
  ap.add_argument("--per_table", type=int, default=65536)
  ap.add_argument("--head_rows", default=str(pallas_apply.HEAD_ROWS),
                  help="comma-separated H to time (0 = no heads is always run)")
  ap.add_argument("--iters", type=int, default=12)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--streams", default="",
                  help="comma-separated stream names (default: all five)")
  args = ap.parse_args()
  device = require_tpu("bench_pallas_apply")
  print("device:", json.dumps(device), flush=True)

  rng = np.random.default_rng(args.seed)
  rows = args.tables * args.table_rows
  n = args.tables * args.per_table
  heads = [int(h) for h in args.head_rows.split(",") if int(h)]
  buf0 = jax.random.normal(jax.random.PRNGKey(args.seed), (rows, W),
                           jnp.float32)
  delta = jax.random.normal(jax.random.PRNGKey(args.seed + 1), (n, W),
                            jnp.float32)
  scale = jnp.float32(-0.125)
  offsets = [t * args.table_rows for t in range(args.tables)]
  all_streams = streams(rng, args.tables, args.table_rows, args.per_table,
                        max(heads or [8]))

  def variant(h):
    if not h:
      return jax.jit(lambda b, i: pallas_apply.apply_rows_cached(
          b, i, delta, scale=scale), donate_argnums=0), None
    starts = pallas_apply.head_block_starts(
        [(lo, lo + h) for lo in offsets], rows, head_rows=h)
    starts_j = jnp.asarray(starts, jnp.int32)
    return jax.jit(lambda b, i: pallas_apply.apply_rows_cached(
        b, i, delta, scale=scale, head_starts=starts_j, head_rows=h),
                   donate_argnums=0), starts

  xla = jax.jit(lambda b, i: b.at[i].add(scale * delta))
  lines = []
  wanted = [s for s in args.streams.split(",") if s] or list(all_streams)
  for name in wanted:
    ids = all_streams[name]
    ids_j = jnp.asarray(ids)
    want = np.asarray(xla(buf0, ids_j)[ids[:4096]])
    base_ns = None
    for h in [0] + heads:
      fn, starts = variant(h)
      buf = fn(buf0 + 0, ids_j)
      err = float(np.max(np.abs(np.asarray(buf[ids[:4096]]) - want)
                         / (1 + np.abs(want))))
      buf = fn(buf, ids_j)
      jax.block_until_ready(buf)
      t0 = time.perf_counter()
      for _ in range(args.iters):
        buf = fn(buf, ids_j)
      jax.block_until_ready(buf)
      ms = 1e3 * (time.perf_counter() - t0) / args.iters
      share = 0.0 if not h else float(np.mean(np.asarray(
          pallas_apply.head_slots(ids_j, jnp.asarray(starts, jnp.int32),
                                  rows, h)) >= 0))
      ns = 1e6 * ms / n
      base_ns = ns if not h else base_ns
      line = {"stream": name, "head_rows": h, "ms": round(ms, 4),
              "ns_per_occurrence": round(ns, 2), "head_share": round(share, 4),
              "vs_no_heads": round(ns / base_ns, 4), "rel_err": err,
              "occurrences": n, "rows": rows}
      lines.append(line)
      print(json.dumps(line), flush=True)
      if err > 2e-3:
        print("FAIL: kernel disagrees with XLA's scatter-add", flush=True)
        sys.exit(1)
  os.makedirs(os.path.dirname(OUT), exist_ok=True)
  with open(OUT, "a") as f:
    for line in lines:
      f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
  enable_compile_cache()
  main()
