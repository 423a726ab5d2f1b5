"""Toy-size copies of the benchmark's data files for the CPU tests: the
same families, harness and check at a size a test run can hold."""

import hashlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

CELLS = {"dlrm": "dlrm_train_1chip", "zoo": "zoo_tiny_train_1chip",
         "dlrm4": "dlrm_train_4chip"}


def _edit(path, fn):
  with open(path) as f:
    data = json.load(f)
  fn(data)
  with open(path, "w") as f:
    json.dump(data, f)


def make_root(dst: str) -> str:
  """A copy of BENCHMARK.json and ``benchmark/`` under ``dst`` with every
  configuration and traffic mix cut to toy size. Nothing else differs."""
  shutil.copytree(os.path.join(ROOT, "benchmark"),
                  os.path.join(dst, "benchmark"),
                  ignore=shutil.ignore_patterns("__pycache__"))
  shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
  cfg = os.path.join(dst, "benchmark", "configs")
  _edit(os.path.join(cfg, "dlrm-criteo1tb.json"),
        lambda c: c.update(vocab_scale=0.0002))
  _edit(os.path.join(cfg, "dlrm-criteo1tb-4chip.json"),
        lambda c: c.update(vocab_scale=0.0004))

  def shrink_zoo(c):
    c["embedding_groups"] = [[n, nnz, max(10, r // 1000), w, s]
                             for n, nnz, r, w, s in c["embedding_groups"]]
    c["dense_row_threshold"] = 64
  _edit(os.path.join(cfg, "zoo-tiny-v3.json"), shrink_zoo)
  for mix in ("criteo_powerlaw", "zoo_powerlaw"):
    _edit(os.path.join(dst, "benchmark", "workloads", f"{mix}.json"),
          lambda c: c.update(global_batch=256, pool_batches=3))
  return dst


def digests(root: str) -> dict:
  """sha256 of every file under ``root``'s ``benchmark/``: a test that adds
  files to a copy shows with it that it rewrote none."""
  out = {}
  for base, _, files in os.walk(os.path.join(root, "benchmark")):
    for name in files:
      path = os.path.join(base, name)
      with open(path, "rb") as f:
        out[path] = hashlib.sha256(f.read()).hexdigest()
  return out


def break_compile_step(monkeypatch, breaker):
  """Break the timed path underneath the harness, where it compiles its
  step: ``breaker(prog, step) -> call(state, *batch)`` stands in for the
  compiled step (its HLO text stays readable)."""
  from benchmark import program
  compile_step = program.Program.compile_step

  class Broken:
    def __init__(self, step, call):
      self.as_text, self._call = step.as_text, call

    def __call__(self, state, *batch):
      return self._call(state, *batch)

  def broken_compile(self, state, batch):
    step = compile_step(self, state, batch)
    return Broken(step, breaker(self, step))
  monkeypatch.setattr(program.Program, "compile_step", broken_compile)


def cpu_devices(n: int):
  import jax
  devices = jax.devices()[:n]
  return devices, {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)}
