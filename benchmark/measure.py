"""Run several benchmark runs one after another, each in its own process,
and keep what each printed: the builder's tool for sets of runs on the chip.

    python benchmark/measure.py LABEL WORKLOAD:SEED:SECONDS:TRACE [...]

This parent imports no JAX, so each child has the chip to itself. Every
run's full output goes to ``chiprun_out/LABEL_<i>.txt``; the parent prints
one summary line per run and the run's result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv) -> int:
  label, runs = argv[0], argv[1:]
  out_dir = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    command = json.load(f)["command"]  # exactly what the driver starts
  worst = 0
  for i, run in enumerate(runs):
    workload, seed, seconds, trace = run.split(":")
    cmd = command + ["--workload", workload, "--seed", seed,
                     "--seconds", seconds, "--trace", trace]
    t = time.time()
    done = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    wall = time.time() - t
    path = os.path.join(out_dir, f"{label}_{i}.txt")
    with open(path, "w") as f:
      f.write(done.stdout)
    lines = done.stdout.strip().splitlines()
    print(f"== {run} rc={done.returncode} wall={wall:.1f}s -> {path}")
    for ln in lines:
      if ln.startswith(("compare", "window", "set-up", "trace", "state",
                        "step compiled", "reference", "check", "imports",
                        "device", "plan")):
        print("   " + ln[:400])
    if lines:
      print(lines[-1][:3000] if done.returncode == 0 else
            "\n".join(lines[-15:])[-3000:])
    worst = max(worst, abs(done.returncode))
    sys.stdout.flush()
  return 1 if worst else 0


if __name__ == "__main__":
  sys.exit(main(sys.argv[1:]))
