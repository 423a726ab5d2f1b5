"""Readings the check's limits are set from, on the chip at the cell's own
size, many seeds in one process (set-up is most of a run):

    python benchmark/control.py --workload <cell> --seeds 1,2,3

Per seed it prints the gaps of the sound program (one step of the compiled
step against the float32 reference: what a run's check compares) and the
gaps of the control: the reference itself with its step's arithmetic in
bfloat16 (`reference.one_step(..., precision="bfloat16")`), the nearest
precision below the float32 the configurations state, put in the program's
place. It exits non-zero if the program is outside a limit on any seed or
the control is inside every limit on any seed. The benchmark's own runs do
not run this; `tests/benchmark` keeps both at toy size.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def main(argv=None) -> int:
  p = argparse.ArgumentParser(description=__doc__)
  p.add_argument("--workload", required=True)
  p.add_argument("--seeds", required=True)
  args = p.parse_args(argv)
  seeds = [int(s) for s in args.seeds.split(",")]

  import jax
  from benchmark import check, program, reference, run, specs, traffic
  from distributed_embeddings_tpu.compile_cache import enable_compile_cache
  from distributed_embeddings_tpu.parallel import create_mesh
  enable_compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  cell = specs.load_cell(args.workload)
  devices, dev = run.find_chips(cell)
  family = cell.family()
  spec = family.model_spec(cell.config)
  limits = cell.config["check_limits"]
  logits = functools.partial(family.reference_logits, cell.config)
  batch_size = int(cell.traffic["global_batch"])
  mesh = create_mesh(cell.chips, devices=devices) if cell.chips > 1 else None
  parts = family.build_parts(cell.config, cell.chips, batch_size)
  step, bad = None, 0
  for seed in seeds:
    t = time.perf_counter()
    batch = traffic.make_batch(cell.traffic, spec.inputs, spec.n_numerical,
                               seed, 0,
                               traffic.family_labels(family, cell.config))
    with jax.default_device(devices[0]):
      ref = reference.one_step(spec, logits, batch, seed)
      low = reference.one_step(spec, logits, batch, seed,
                               precision="bfloat16")
    control = {
        "loss_gap": abs(low.loss - ref.loss) / abs(ref.loss),
        "table_change_gap": check.worst_gap(
            low.table_delta, ref.table_delta, reference.table_name)[0],
        "dense_change_gap": check.worst_gap(
            low.dense_delta, ref.dense_delta, str)[0]}
    inside = all(control[k] <= limits[k] for k in control)
    prog = program.Program(parts, spec, seed, mesh)
    state = jax.block_until_ready(prog.fill())
    if step is None:
      step = prog.compile_step(state, batch)
    state, compared, _ = check.one_step(prog, state, step, batch, ref, limits)
    sound = {c.name: c.value for c in compared}
    where = {c.name: c.where for c in compared}
    bad += int(inside) + int(not all(c.ok for c in compared))
    del state, prog
    print(json.dumps({"seed": seed, "program": sound, "control": control,
                      "where": where,
                      "control_inside_every_limit": inside,
                      "seconds": round(time.perf_counter() - t, 1)}),
          flush=True)
  print(f"limits {json.dumps(limits)}; device {dev}; "
        f"{'FAILED' if bad else 'ok'} on {len(seeds)} seeds")
  return 1 if bad else 0


if __name__ == "__main__":
  sys.exit(main())
