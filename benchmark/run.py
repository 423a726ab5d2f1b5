"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which touches JAX once. Set-up (plan, model, the benchmark's
weights filled on the device from the seed, a pool of host batches, the
cell's own step compiled), the one-step correctness check, then a closed
loop for ``--seconds`` as a trainer runs it: feed the next host batch,
dispatch the donated step, wait for the step dispatched ``steps_in_flight``
steps before (the traffic file's; three in the committed mixes, because one
step in flight made the one-chip rate bimodal: PERF.md, PR 25).
The last line of standard output is the result (`result_line.py`);
everything else goes on the lines above it.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # as close to the process's start as Python gets

import argparse
import dataclasses
import functools
import glob
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmark import result_line, specs  # noqa: E402

TRACE_SECONDS = 4.0   # a traced run measures this long: traces are large
STEP_MODULE = r"^jit_step_fn\("
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")


def say(text: str) -> None:
  print(text, flush=True)


@dataclasses.dataclass
class RunResult:
  correct: bool
  attempted: int
  failed: int
  values: Dict[str, float]
  device: Dict[str, Any]
  breakdown: Optional[Dict[str, Any]]


class CompileCounter:
  """Counts programs lowered or compiled, through ``jax.monitoring``."""

  def __init__(self):
    import jax
    self.n = 0
    jax.monitoring.register_event_duration_secs_listener(self._on)

  def _on(self, name, *_, **__):
    if name in COMPILE_EVENTS:
      self.n += 1


def memory_peak_bytes(devices) -> int:
  peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
  return int(max(peaks))


def find_chips(cell: specs.Cell):
  """The devices of this run, or SystemExit: there is no CPU mode."""
  import jax
  from benchmark import roofline
  devices = jax.devices()
  dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices)}
  if dev["platform"] != "tpu" or dev["kind"] not in roofline.PEAKS:
    raise SystemExit(f"benchmark: needs a TPU in the peaks table "
                     f"{sorted(roofline.PEAKS)}, found {dev}")
  if dev["count"] != cell.chips:
    raise SystemExit(f"benchmark: cell {cell.name} runs on {cell.chips} "
                     f"chip(s), this machine shows {dev['count']}")
  return devices, dev


def window(prog, state, step, pool, seconds: float, in_flight: int):
  """The measured loop: feed, dispatch, then wait for the step dispatched
  ``in_flight`` steps ago, so that many steps are queued on the device
  while the host prepares the next. -> (state, device losses, completion
  times, begin, end), all times on ``time.perf_counter``."""
  import jax
  ann = jax.profiler.TraceAnnotation
  losses, done = [], []
  begin = time.perf_counter()
  while True:
    k = len(losses)
    with ann("bench_feed"):
      fed = prog.put(pool[k % len(pool)])
    with ann("bench_dispatch"):
      state, loss = step(state, *fed)
    losses.append(loss)
    if k >= in_flight:
      with ann("bench_wait"):
        losses[k - in_flight].block_until_ready()
      done.append(time.perf_counter())
    if time.perf_counter() - begin >= seconds:
      break
  for loss in losses[len(done):]:  # drain: the steps still in flight
    with ann("bench_wait"):
      loss.block_until_ready()
    done.append(time.perf_counter())
  with ann("bench_wait"):
    jax.block_until_ready(state)
  end = time.perf_counter()
  done[-1] = end
  return state, losses, done, begin, end


def reduce_trace(cell: specs.Cell, trace_dir: str, ctx: Dict[str, Any]):
  """-> (per-layer values, window_s, busy_s, breakdown, steps traced)."""
  from benchmark import trace_reduce
  files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                 "*.xplane.pb"))
  if len(files) != 1:
    raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                       f"found {files}")
  red = trace_reduce.Reduced(trace_reduce.load_xplane(files[0]), STEP_MODULE)
  values = {}
  for m in cell.per_layer:
    v = cell.layer_reader(m["name"])(red, ctx)
    if v is not None:
      values[m["name"]] = v
  breakdown = {"device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}
  return values, red.window_s(), red.busy_s(), breakdown, red.n_steps()


def run_cell(cell: specs.Cell, seed: int, seconds: float, trace: bool,
             devices, dev: Dict[str, Any]) -> RunResult:
  """Everything of a run after the look for a chip."""
  import jax
  import numpy as np

  from benchmark import check, program, reference, traffic
  from distributed_embeddings_tpu.parallel import create_mesh

  t0 = time.perf_counter()
  family = cell.family()
  config, mix = cell.config, cell.traffic
  spec = family.model_spec(config)
  batch_size = int(mix["global_batch"])
  pool = traffic.make_pool(mix, spec.inputs, spec.n_numerical, seed,
                           traffic.family_labels(family, config))
  say(f"pool of {len(pool)} batches of {batch_size}: "
      f"{time.perf_counter() - t0:.1f}s")

  # the reference first: its arrays are gone before the state exists, so
  # the process's peak memory is the program's
  t_ref = time.perf_counter()
  logits = functools.partial(family.reference_logits, config)
  with jax.default_device(devices[0]):
    ref = reference.one_step(spec, logits, pool[0], seed)
  ref_s = time.perf_counter() - t_ref
  say(f"reference: one step in {ref_s:.1f}s, loss {ref.loss:.7g}; device "
      f"peak after it {memory_peak_bytes(devices) / 2**30:.2f} GiB")

  t1 = time.perf_counter()
  world = cell.chips
  mesh = create_mesh(world, devices=devices) if world > 1 else None
  parts = family.build_parts(config, world, batch_size)
  prog = program.Program(parts, spec, seed, mesh)
  counter = CompileCounter()
  t2 = time.perf_counter()
  state = jax.block_until_ready(prog.fill())
  t3 = time.perf_counter()
  say(f"plan and model {t2 - t1:.1f}s; state filled in {t3 - t2:.1f}s, "
      f"device peak {memory_peak_bytes(devices) / 2**30:.2f} GiB")
  step = prog.compile_step(state, pool[0])
  hlo = step.as_text()
  kernels = program.mosaic_kernels(hlo)
  say(f"step compiled or loaded in {time.perf_counter() - t3:.1f}s "
      f"({counter.n} programs lowered so far); mosaic kernels: "
      f"{' '.join(kernels) or 'none'}")

  t_chk = time.perf_counter()
  state, compared, loss0 = check.one_step(
      prog, state, step, pool[0], ref, config["check_limits"])
  check_s = time.perf_counter() - t_chk
  for c in compared:
    say(c.line())
  say(f"check: {check_s:.1f}s; device peak after it "
      f"{memory_peak_bytes(devices) / 2**30:.3f} GiB")
  del ref

  seconds = min(seconds, TRACE_SECONDS) if trace else seconds
  trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
  if trace:
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
  compiles_before = counter.n
  # set-up ends here. The reference and the check's read-backs and
  # comparisons are paid by every run but are not set-up of the system
  # under test; they are printed above and left out of setup_s
  setup_s = (time.time() - T_PROCESS) - ref_s - check_s
  state, losses, done, begin, end = window(
      prog, state, step, pool, seconds, int(mix["steps_in_flight"]))
  compiles = counter.n - compiles_before
  if trace:
    jax.profiler.stop_trace()
  peak = memory_peak_bytes(devices)
  losses = np.asarray(jax.device_get(losses), np.float64)
  n = len(losses)
  failed = int(np.sum(~np.isfinite(losses)))
  gaps_ms = np.diff(done) * 1e3
  head, tail = losses[:max(1, n // 10)], losses[-max(1, n // 10):]
  say(f"window: {n} steps in {end - begin:.3f}s; step ms median "
      f"{np.median(gaps_ms):.3f} p95 {np.percentile(gaps_ms, 95):.3f} max "
      f"{gaps_ms.max():.3f}; loss first {losses[0]:.5f} last "
      f"{losses[-1]:.5f}, mean of first tenth {head.mean():.5f} of last "
      f"tenth {tail.mean():.5f}; programs compiled in the window: {compiles}")
  say(f"set-up {setup_s:.1f}s (+ reference {ref_s:.1f}s, check "
      f"{check_s:.1f}s); device peak after the window {peak / 2**30:.3f} GiB")
  correct = all(c.ok for c in compared) and compiles == 0 and failed == 0
  device = dict(dev, memory_peak_bytes=peak)

  if not trace:
    values = {
        "train_samples_per_s": n * batch_size / (end - begin),
        "step_ms_p95": float(np.percentile(gaps_ms, 95)),
        "hbm_peak_gib": peak / 2**30,
        "setup_s": setup_s,
    }
    return RunResult(correct, n, failed, values, device, None)
  ctx = {"cell": cell, "device_kind": dev["kind"],
         "shapes": prog.apply_shapes(pool, hlo)}
  values, window_s, busy_s, breakdown, traced_steps = reduce_trace(
      cell, trace_dir, ctx)
  shutil.rmtree(trace_dir, ignore_errors=True)
  say(f"trace: {traced_steps} steps of {n} on the device timeline, window "
      f"{window_s:.4f}s busy {busy_s:.4f}s")
  device.update(window_s=window_s, busy_s=busy_s)
  return RunResult(correct, n, failed, values, device, breakdown)


def main(argv: Optional[List[str]] = None) -> int:
  p = argparse.ArgumentParser(description=__doc__)
  p.add_argument("--workload", required=True)
  p.add_argument("--seed", type=int, required=True)
  p.add_argument("--seconds", type=float, required=True)
  p.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = p.parse_args(argv)
  if not 0 <= args.seed < 2 ** 63:
    raise SystemExit("benchmark: --seed is a whole number from 0 to 2**63-1")
  cell = specs.load_cell(args.workload)

  import jax
  from distributed_embeddings_tpu.compile_cache import enable_compile_cache
  cache_dir = enable_compile_cache()
  # every program of a run, small ones too, comes from the cache after the
  # first run in a checkout
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  say(f"imports: {time.time() - T_PROCESS:.1f}s")
  t = time.perf_counter()
  devices, dev = find_chips(cell)
  say(f"device: {dev} up in {time.perf_counter() - t:.1f}s; compile cache: "
      f"{cache_dir}")
  traced = bool(args.trace)
  result = run_cell(cell, args.seed, args.seconds, traced, devices, dev)
  declared = cell.per_layer if traced else cell.end_to_end
  try:
    line = result_line.build(
        correct=result.correct, attempted=result.attempted,
        failed=result.failed, values=result.values, declared=declared,
        device=result.device, traced=traced, breakdown=result.breakdown)
  except result_line.InvalidResult as e:
    raise SystemExit(f"benchmark: no valid result line: {e}")
  print(line, flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
