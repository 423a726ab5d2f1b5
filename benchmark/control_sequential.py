"""The control's readings for a cell whose references `benchmark/control.py`
cannot hold at once: it keeps the float32 and the bfloat16 reference whole,
in float64, beside the program's read-backs, and at 7.2e8 dense values that
met the one-chip machine's 40 GiB of host memory (PERF.md, PR 33).

    python benchmark/control_sequential.py --workload <cell> --seeds 1,2 \
        [--stand_ins bfloat16,weight,not_packed]

Per seed: the float32 reference, kept as float32; then each stand-in put in
the program's place, one after the other, each gone before the next:

- ``bfloat16``: the reference with its step's arithmetic in bfloat16
  (`reference.one_step(..., precision="bfloat16")`), the nearest precision
  below the float32 the configurations state;
- any other name: a wrong forward the family names
  (``reference_faults(config) -> {name: (logits_fn, loss)}``), in float32.

Each is judged as a run's check judges the program: the same three numbers,
by `check.worst_gap`, as `check.Compared` under the configuration's
``check_limits``. It prints the check's ``compare`` lines and one line of
JSON a stand-in, which says ``"correct": false`` where a limit was passed.
It runs no program step: a run's check prints the program's readings. It
exits non-zero if the bfloat16 control is inside every limit on any seed; a
fault decides nothing, it shows what the limits see at the cell's size.
Limits are set from readings on the chip only; the device is on every line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def control(cell, seeds, names=("bfloat16",)) -> int:
  """The readings of ``cell`` on ``seeds``; -> the number of seeds on which
  the bfloat16 control was inside every limit."""
  import jax
  import numpy as np

  from benchmark import check, reference, traffic
  devices = jax.devices()
  dev = {"platform": devices[0].platform, "kind": devices[0].device_kind}
  family = cell.family()
  spec = family.model_spec(cell.config)
  limits = cell.config["check_limits"]
  sound = functools.partial(family.reference_logits, cell.config)
  stand_ins = {}
  for name in names:
    if name == "bfloat16":
      stand_ins[name] = (spec, sound, "bfloat16")
    else:
      logits_fn, loss = family.reference_faults(cell.config)[name]
      stand_ins[name] = (dataclasses.replace(spec, loss=loss), logits_fn,
                         "float32")

  def one_step(spec, logits_fn, batch, seed, precision):
    """-> (loss, table changes, dense changes), the changes as float32."""
    with jax.default_device(devices[0]):
      r = reference.one_step(spec, logits_fn, batch, seed,
                             precision=precision)
    as32 = lambda d: {k: v.astype(np.float32) for k, v in d.items()}
    return r.loss, as32(r.table_delta), as32(r.dense_delta)

  inside = 0
  for seed in seeds:
    batch = traffic.make_batch(cell.traffic, spec.inputs, spec.n_numerical,
                               seed, 0,
                               traffic.family_labels(family, cell.config))
    t = time.perf_counter()
    loss, tables, dense = one_step(spec, sound, batch, seed, "float32")
    print(f"seed {seed}: float32 reference in "
          f"{time.perf_counter() - t:.1f}s, loss {loss:.7g}", flush=True)
    for name, (spec_s, logits_s, precision) in stand_ins.items():
      t = time.perf_counter()
      loss_s, tables_s, dense_s = one_step(spec_s, logits_s, batch, seed,
                                           precision)
      compared = [check.Compared(
          "loss_gap", abs(loss_s - loss) / max(abs(loss), 1e-30),
          f"step 0 ({name} {loss_s:.7g}, reference {loss:.7g})",
          limits["loss_gap"])]
      for key, got, want, label in (
          ("table_change_gap", tables_s, tables, reference.table_name),
          ("dense_change_gap", dense_s, dense, str)):
        gap, where = check.worst_gap(got, want, label)
        compared.append(check.Compared(key, gap, where, limits[key]))
      del tables_s, dense_s
      for c in compared:
        print(c.line(), flush=True)
      correct = all(c.ok for c in compared)
      inside += int(correct and name == "bfloat16")
      print(json.dumps({
          "seed": seed, "stand_in": name, "correct": correct,
          **{c.name: c.value for c in compared},
          "outside": [c.name for c in compared if not c.ok],
          "seconds": round(time.perf_counter() - t, 1), "device": dev}),
            flush=True)
  print(f"limits {json.dumps(limits)}; device {dev}; the bfloat16 control "
        f"was inside every limit on {inside} seed(s)", flush=True)
  return inside


def main(argv=None) -> int:
  p = argparse.ArgumentParser(description=__doc__)
  p.add_argument("--workload", required=True)
  p.add_argument("--seeds", required=True)
  p.add_argument("--stand_ins", default="bfloat16")
  args = p.parse_args(argv)

  import jax

  from benchmark import specs
  from distributed_embeddings_tpu.compile_cache import enable_compile_cache
  enable_compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  inside = control(specs.load_cell(args.workload),
                   [int(s) for s in args.seeds.split(",")],
                   args.stand_ins.split(","))
  return 1 if inside else 0


if __name__ == "__main__":
  sys.exit(main())
