"""What the harness computes at toy size, as digests: every pool array of
every committed mix x configuration, every field of the reference's
``StepChange`` (float32 and the bfloat16 control) and the ``compare`` lines
of a toy run, for one fixed seed each.

``data/parent_fixtures.json`` holds them as commit 03d7441 (PR 27)
computed them, recorded before PR 28's first edit by this file's
arithmetic against that tree's API (``make_pool`` without a labels hook).
``test_bench_parent_fixtures.py`` holds the harness to them: a family that
says nothing new gets the same bits. Run as a script to print the current
tree's digests:

    JAX_PLATFORMS=cpu python tests/benchmark/fixtures.py > now.json
"""

import contextlib
import functools
import hashlib
import io
import json
import os
import platform
import sys

import numpy as np

import bench_toy

SEED = 2**31 + 9001      # pools and the reference's one step
RUN_SEED = 2**31 + 5     # the toy runs whose compare lines are kept
PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "parent_fixtures.json")


def machine() -> str:
  """What the reference's float arithmetic depends on beside the code: a
  digest recorded elsewhere is held by its norm, not its bits."""
  import jax
  model = ""
  try:
    with open("/proc/cpuinfo") as f:
      model = next((ln.split(":", 1)[1].strip() for ln in f
                    if ln.startswith("model name")), "")
  except OSError:
    pass
  return (f"{platform.machine()} {model} jax {jax.__version__} "
          f"numpy {np.__version__}")


def sha(a) -> str:
  a = np.ascontiguousarray(a)
  h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
  h.update(a.tobytes())
  return h.hexdigest()


def arr(a):
  return {"sha256": sha(a), "norm": float(np.sqrt(np.sum(np.square(
      np.asarray(a, np.float64)))))}


def mixes(root):
  return sorted(f[:-5] for f in os.listdir(
      os.path.join(root, "benchmark", "workloads")) if f.endswith(".json"))


def _family(root, key):
  from benchmark import specs, traffic
  cell = specs.load_cell(bench_toy.CELLS[key], root)
  family = cell.family()
  return cell, family, family.model_spec(cell.config), \
      traffic.family_labels(family, cell.config)


def pool_digest(root, mix, key):
  """sha256 of every array of the pool ``mix`` makes for configuration
  ``key``: ``<batch>.<field>``; a tree of labels adds its leaves' paths."""
  import jax
  from benchmark import traffic
  _, _, spec, make_labels = _family(root, key)
  with open(os.path.join(root, "benchmark", "workloads", mix + ".json")) as f:
    params = json.load(f)
  out = {}
  for i, b in enumerate(traffic.make_pool(params, spec.inputs,
                                          spec.n_numerical, SEED,
                                          make_labels)):
    out[f"{i}.numerical"], out[f"{i}.cats"] = sha(b.numerical), sha(b.cats)
    for path, leaf in jax.tree_util.tree_flatten_with_path(b.labels)[0]:
      out[f"{i}.labels{jax.tree_util.keystr(path)}"] = sha(leaf)
  return out


def step_change_digest(root, key, precision):
  from benchmark import reference, traffic
  cell, family, spec, make_labels = _family(root, key)
  batch = traffic.make_batch(cell.traffic, spec.inputs, spec.n_numerical,
                             SEED, 0, make_labels)
  sc = reference.one_step(
      spec, functools.partial(family.reference_logits, cell.config), batch,
      SEED, precision=precision)
  each = lambda d: {str(k): arr(v) for k, v in sorted(d.items())}
  return {"loss": float(sc.loss).hex(), "loss_value": float(sc.loss),
          "table_rows": each(sc.table_rows),
          "table_delta": each(sc.table_delta),
          "acc_delta": each(sc.acc_delta),
          "dense_delta": each(sc.dense_delta),
          "dense_before": each(sc.dense_before)}


def compare_lines(root, key):
  """The ``compare`` lines of one toy run of the cell (CPU, no chip)."""
  from benchmark import run, specs
  cell = specs.load_cell(bench_toy.CELLS[key], root)
  devices, dev = bench_toy.cpu_devices(cell.chips)
  buf = io.StringIO()
  with contextlib.redirect_stdout(buf):
    result = run.run_cell(cell, RUN_SEED, 0.3, False, devices, dev)
  return result.correct, [ln for ln in buf.getvalue().splitlines()
                          if ln.startswith("compare")]


def current(root):
  out = {"machine": machine(), "seed": SEED, "run_seed": RUN_SEED,
         "pools": {}, "step_changes": {}, "compare_lines": {}}
  for key in bench_toy.CELLS:
    for mix in mixes(root):
      out["pools"][f"{mix}/{key}"] = pool_digest(root, mix, key)
    for precision in ("float32", "bfloat16"):
      out["step_changes"][f"{key}/{precision}"] = step_change_digest(
          root, key, precision)
    out["compare_lines"][key] = compare_lines(root, key)[1]
  return out


if __name__ == "__main__":
  import tempfile
  with tempfile.TemporaryDirectory() as tmp:
    json.dump(current(bench_toy.make_root(tmp)), sys.stdout, indent=1,
              sort_keys=True)
