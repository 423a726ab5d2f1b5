"""The traced train steps of the configurations the benchmark had before the
sequence inputs and the summed rules, as text: ``jax.make_jaxpr`` of the
DLRM, zoo and toy-sequence steps at toy size, built the benchmark's way
(``family.build_parts`` -> ``Program.compile_step``'s own call of
``make_sparse_train_step``). ``tests/data/jaxpr_baseline/`` holds what the
parent of PR 29 traced, recorded before that PR's first edit to the program:

    JAX_PLATFORMS=cpu python tests/jaxpr_baseline.py --record

A hotness-1 input, a summed input and ``adam_rule()`` without ``summed`` have
to keep tracing to these (`tests/test_sequence_inputs.py`).
"""

from __future__ import annotations

import gzip
import hashlib
import json
import os
import re
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(HERE, "benchmark")):
  if p not in sys.path:
    sys.path.insert(0, p)

DATA = os.path.join(HERE, "data", "jaxpr_baseline")
STEPS = ("dlrm", "zoo", "toyseq")
_ADDRESS = re.compile(r"0x[0-9a-f]+")


def _toy_root(dst: str) -> str:
  """The benchmark's toy copy with the toy sequence family added."""
  import bench_toy
  root = bench_toy.make_root(dst)
  toy = os.path.join(HERE, "benchmark", "data", "toyseq")
  for name, sub in (("toyseq.py", "families"), ("toyseq.json", "configs"),
                    ("toyseq_tokens.json", "workloads")):
    shutil.copy(os.path.join(toy, name),
                os.path.join(root, "benchmark", sub, name))
  return root


def step_text(which: str, root: str) -> str:
  """The jaxpr of one toy step, object addresses struck out."""
  import jax
  import jax.numpy as jnp

  from benchmark import specs, traffic
  from distributed_embeddings_tpu.training import (
      init_sparse_state_direct,
      make_sparse_train_step,
  )

  def load(path):
    with open(os.path.join(root, "benchmark", path)) as f:
      return json.load(f)

  config, mix = {
      "dlrm": ("configs/dlrm-criteo1tb.json", "workloads/criteo_powerlaw.json"),
      "zoo": ("configs/zoo-tiny-v3.json", "workloads/zoo_powerlaw.json"),
      "toyseq": ("configs/toyseq.json", "workloads/toyseq_tokens.json"),
  }[which]
  config, mix = load(config), load(mix)
  family = specs.load_module(
      os.path.join(root, "benchmark", "families", f"{config['family']}.py"),
      f"baseline_family_{which}")
  spec = family.model_spec(config)
  batch = traffic.make_batch(mix, spec.inputs, spec.n_numerical, 1, 0,
                             traffic.family_labels(family, config))
  parts = family.build_parts(config, 1, int(mix["global_batch"]))
  state = jax.eval_shape(lambda: init_sparse_state_direct(
      parts.plan, parts.rule,
      jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                             parts.dense_template),
      parts.optimizer, jax.random.PRNGKey(0)))
  zeros = lambda t: jax.tree_util.tree_map(
      lambda x: jnp.zeros(x.shape, x.dtype), t)
  example = (zeros(batch.numerical), parts.split_cats(zeros(batch.cats)),
             zeros(batch.labels))
  inner = make_sparse_train_step(
      parts.model, parts.plan, parts.loss_fn, parts.optimizer, parts.rule,
      None, state, example, donate=False)

  def step_fn(carry, numerical, cats, labels):
    return inner(carry, numerical, parts.split_cats(cats), labels)

  text = str(jax.make_jaxpr(step_fn)(state, *example[:1], zeros(batch.cats),
                                     example[2]))
  return _ADDRESS.sub("0x", text)


def all_texts() -> dict:
  tmp = tempfile.mkdtemp(prefix="jaxpr_baseline_")
  try:
    root = _toy_root(tmp)
    return {which: step_text(which, root) for which in STEPS}
  finally:
    shutil.rmtree(tmp, ignore_errors=True)


def recorded(which: str) -> str:
  with gzip.open(os.path.join(DATA, f"{which}.txt.gz"), "rt") as f:
    return f.read()


def digest(text: str) -> str:
  return hashlib.sha256(text.encode()).hexdigest()


if __name__ == "__main__":
  if sys.argv[1:] != ["--record"]:
    raise SystemExit(__doc__)
  os.makedirs(DATA, exist_ok=True)
  for which, text in all_texts().items():
    with gzip.GzipFile(os.path.join(DATA, f"{which}.txt.gz"), "wb",
                       mtime=0) as f:
      f.write(text.encode())
    print(which, len(text.splitlines()), "lines", digest(text))
