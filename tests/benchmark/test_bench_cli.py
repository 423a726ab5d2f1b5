"""`benchmark/run.py` as the driver starts it: no chip, no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_toy


def _run(cwd, *args, env_extra=None):
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env.update(JAX_PLATFORMS="cpu", BENCH_RUN="anything", **(env_extra or {}))
  return subprocess.run(
      [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
      cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _prints_no_result(done):
  lines = done.stdout.strip().splitlines()
  if not lines:
    return True
  try:
    return "metrics" not in json.loads(lines[-1])
  except (json.JSONDecodeError, TypeError):
    return True


@pytest.mark.parametrize("cell", ["dlrm_train_1chip", "zoo_tiny_train_1chip",
                                  "dlrm_train_4chip"])
def test_refuses_a_cpu_backend(cell):
  done = _run(bench_toy.ROOT, "--workload", cell, "--seed", str(2**31 + 11),
              "--seconds", "1", "--trace", "0")
  assert done.returncode != 0
  assert "needs a TPU" in done.stderr
  assert _prints_no_result(done)


def test_refuses_an_unknown_cell_and_a_negative_seed():
  done = _run(bench_toy.ROOT, "--workload", "no_such_cell", "--seed", "1",
              "--seconds", "1", "--trace", "0")
  assert done.returncode != 0 and "no workload" in done.stderr
  done = _run(bench_toy.ROOT, "--workload", "dlrm_train_1chip", "--seed",
              "-4", "--seconds", "1", "--trace", "0")
  assert done.returncode != 0 and _prints_no_result(done)


def test_fails_without_the_program(tmp_path):
  """In a directory that holds only BENCHMARK.json and the files under
  `paths` there is no system to measure: non-zero, no result."""
  with open(os.path.join(bench_toy.ROOT, "BENCHMARK.json")) as f:
    paths = json.load(f)["paths"]
  shutil.copy(os.path.join(bench_toy.ROOT, "BENCHMARK.json"), tmp_path)
  for p in paths:
    shutil.copytree(os.path.join(bench_toy.ROOT, p), tmp_path / p,
                    ignore=shutil.ignore_patterns("__pycache__"))
  done = _run(str(tmp_path), "--workload", "dlrm_train_1chip", "--seed", "3",
              "--seconds", "1", "--trace", "0")
  assert done.returncode != 0
  assert _prints_no_result(done)
