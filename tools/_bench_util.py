"""Shared microbenchmark harness for the tools/ profilers.

Timing: chained steps at two chain lengths, each ended by
``jax.block_until_ready``, differenced so the dispatch overhead cancels.
With ``donate=True`` the first positional argument is
donated and the chain carries its successor.  Every differenced
measurement is also recorded into the process-wide telemetry registry
(histogram ``bench/<name>``), so the profilers share one metrics
surface with the rest of the stack instead of each keeping private
floats.
"""

import time

import jax

from distributed_embeddings_tpu.telemetry import get_registry


def timeit(name, fn, first, *args, donate=True, n_norm=None, reps=5):
  """Time ``fn(first, *args)`` chained; print ms (and ns/elem). Returns the
  final carry (with donation the input is consumed — keep the carry)."""
  step = jax.jit(fn, donate_argnums=(0,) if donate else ())
  carry = step(first, *args)
  jax.block_until_ready(carry)

  def run(n, carry):
    t0 = time.perf_counter()
    for _ in range(n):
      carry = step(carry, *args)
    jax.block_until_ready(carry)
    return time.perf_counter() - t0, carry

  _, carry = run(1, carry)
  t1, carry = run(reps, carry)
  t2, carry = run(2 * reps, carry)
  dt = (t2 - t1) / reps
  get_registry().histogram(f"bench/{name}").observe(dt)
  per = f"  {dt / n_norm * 1e9:6.1f} ns/elem" if n_norm else ""
  print(f"{name:56s}: {dt * 1e3:8.2f} ms{per}", flush=True)
  return carry


def parse_device_trace(tdir):
  """Parse a jax.profiler trace dir into per-op aggregates.

  Returns ``(tot_us_by_name, cnt_by_name, args_of, by_src_us,
  total_jit_us)`` over the TPU device pids. Shared by the tools/ trace
  scripts — the profile path layout and the process_name/'source'
  conventions are XLA-version-dependent and must be fixed in ONE place
  when they shift.
  """
  import glob
  import gzip
  import json
  from collections import defaultdict

  path = sorted(glob.glob(f"{tdir}/plugins/profile/*/*.trace.json.gz"))[-1]
  with gzip.open(path) as f:
    t = json.load(f)
  names = {}
  for e in t.get("traceEvents", []):
    if e.get("ph") == "M" and e.get("name") == "process_name":
      names[e["pid"]] = e["args"]["name"]
  dev_pids = {p for p, n in names.items() if "TPU" in n}
  tot = defaultdict(float)
  cnt = defaultdict(int)
  args_of = {}
  by_src = defaultdict(float)
  total_jit = 0.0
  for e in t.get("traceEvents", []):
    if e.get("ph") != "X" or e.get("pid") not in dev_pids:
      continue
    nm = e.get("name", "?")
    dur = e.get("dur", 0.0)
    tot[nm] += dur
    cnt[nm] += 1
    a = e.get("args")
    if a:
      args_of[nm] = a
      src = a.get("source", "")
      if src:
        by_src[src] += dur
    if nm.startswith("jit_"):
      total_jit += dur
  return tot, cnt, args_of, by_src, total_jit
