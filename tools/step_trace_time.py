"""What a language-model cell's step costs a process before the compile
cache can be asked: seconds of Python.

`setup_s` is judged by its median, and all but the first of a cell's runs
load the compiled step from the persistent cache. What a warm run still pays
is everything before the cache's key exists: building the plan and the model,
tracing the step and lowering it to the module whose hash the key is. That is
Python, it is paid by every process, and a kernel's body is part of it: a
Pallas kernel that writes N ``pl.when`` out in a Python loop is traced, N
closures and all, once a call site and transform (PERF.md, PR 44: 8 to 30 s
of `setup_s` in the four MoE cells).

This builds the cell's step as the benchmark does, for a DESCRIBED v5e (no
chip; `tools/step_recompute.py::compile_built`), stops where the compile would
begin, and prints, a process a reading (a trace is kept for the process, so
a second reading in one process would measure nothing):

  plan_and_model_s   `family.build_parts` and `program.Program`: what
                     `benchmark/run.py` prints as "plan and model"
  trace_lower_s      `Program.compile_step` up to the lowered module: the
                     part of "step compiled or loaded" no cache shortens
  kernel_calls       Mosaic kernel calls in the lowered module (a
                     `pallas_call` lowered is one; a jitted function is
                     lowered once a module however often it is called)
  kernel_body_traces how often Pallas ran a kernel body's Python, by the
                     body's name
  module_sha         sha256 of the lowered module's text: equal on two
                     processes, or the cache would miss

then one line with the least of each over ``--repeat`` processes: the host
wanders by a second or two, and the least is the number to compare. One
process more runs both stages under `cProfile` and gives ``python_calls``,
the function calls the two stages made: a count that hardly moves from
process to process (a part in a thousand) where the seconds move by a third,
so it tells +5% from noise; its own seconds are not read. Run it
over two checkouts (``--root``: the directory whose `benchmark` and
`distributed_embeddings_tpu` are imported; default this file's) and the
difference is what a change adds to every warm start. Seconds of THIS host's
Python: the driver's host read 2.7 times these at PR 43. Nothing here is a
device time.

  python tools/step_trace_time.py laguna_moe_train_1chip --repeat 3
  python tools/step_trace_time.py laguna_moe_train_1chip --root parent_checkout
"""

import argparse
import collections
import cProfile
import hashlib
import json
import os
import pstats
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TIMES = ("plan_and_model_s", "trace_lower_s")


class _Lowered(Exception):
  """Raised where the step's compile would begin; carries the module."""


def read_once(cell_name: str, root: str, count_calls: bool = False):
  """One reading, in this process; ``count_calls``: under `cProfile`, for
  the count of function calls and not for the seconds."""
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  sys.path.insert(0, HERE)
  import step_recompute       # puts this file's checkout on the path
  sys.path.insert(0, root)    # ... and the one to be read before it
  import jax
  from jax._src import stages
  from jax._src.pallas import pallas_call

  traces = collections.Counter()
  trace_body = pallas_call._trace_kernel_to_jaxpr

  def counted(fun, debug_info, *args, **kwargs):
    traces[debug_info.func_name] += 1
    return trace_body(fun, debug_info, *args, **kwargs)
  pallas_call._trace_kernel_to_jaxpr = counted

  profile = cProfile.Profile() if count_calls else None
  if profile:
    profile.enable()
  t0 = time.perf_counter()
  built = step_recompute.build_program(cell_name)
  t1 = time.perf_counter()

  def stop(lowered, *args, **kwargs):
    raise _Lowered(lowered.as_text())
  stages.Lowered.compile = stop
  try:
    step_recompute.compile_built(*built)
    raise SystemExit("the step compiled without `Lowered.compile`")
  except _Lowered as e:
    t2 = time.perf_counter()
    module = e.args[0]
  if profile:
    profile.disable()
  if "step_fn" not in module[:400]:
    raise SystemExit("the first module lowered is not the step's: "
                     + module[:200])
  if profile:
    return {"python_calls": sum(
        calls for _, calls, *_ in pstats.Stats(profile).stats.values())}
  import benchmark
  return {"cell": cell_name,
          "root": os.path.dirname(os.path.dirname(
              os.path.abspath(benchmark.__file__))),
          "plan_and_model_s": round(t1 - t0, 2),
          "trace_lower_s": round(t2 - t1, 2),
          "kernel_calls": module.count("tpu_custom_call"),
          "kernel_body_traces": dict(traces),
          "module_sha": hashlib.sha256(module.encode()).hexdigest()[:16]}


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("cell")
  ap.add_argument("--repeat", type=int, default=3,
                  help="processes, each one reading; 0: read in this one")
  ap.add_argument("--root", default=os.path.dirname(HERE),
                  help="the checkout to read (default: this file's)")
  ap.add_argument("--calls", action="store_true",
                  help="with --repeat 0: count function calls under cProfile")
  args = ap.parse_args(argv)
  root = os.path.abspath(args.root)
  if args.repeat == 0:
    print(json.dumps(read_once(args.cell, root, args.calls)), flush=True)
    return
  env = dict(os.environ, JAX_PLATFORMS="cpu", ALLOW_MULTIPLE_LIBTPU_LOAD="1")

  def process(*more):
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), args.cell, "--repeat",
         "0", "--root", root, *more], env=env, check=True,
        stdout=subprocess.PIPE, text=True).stdout.strip().splitlines()[-1]
    print(out, flush=True)
    return json.loads(out)

  readings = [process() for _ in range(args.repeat)]
  least = dict(readings[0], readings=len(readings),
               module_shas=len({r["module_sha"] for r in readings}),
               **process("--calls"))
  for key in TIMES:
    least[key] = min(r[key] for r in readings)
  del least["module_sha"]
  print(json.dumps({"least": least}), flush=True)
  return least


if __name__ == "__main__":
  main()
