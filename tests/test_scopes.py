"""Every instruction of the sparse train step lies under a scope of
``telemetry/scopes.py``: coverage by count, on the compiled HLO of the toy
DLRM and the toy zoo step, at world 1 and on a mesh of four virtual devices.
"""

import collections
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models import DLRM, SyntheticModel, bce_loss
from distributed_embeddings_tpu.models.dlrm import dlrm_embedding_plan
from distributed_embeddings_tpu.models.synthetic import (
    EmbeddingGroup,
    SyntheticModelConfig,
    expand_tables,
)
from distributed_embeddings_tpu.ops.packed_table import adagrad_rule, sgd_rule
from distributed_embeddings_tpu.parallel import create_mesh
from distributed_embeddings_tpu.telemetry import scopes
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

BATCH = 64
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")
NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element")


def _dlrm(world):
  rows = [40, 6000, 7, 9000, 12, 5000]  # small ones one-hot, big ones sparse
  model = DLRM(vocab_sizes=rows, embedding_dim=16, bottom_mlp=(32, 16),
               top_mlp=(32, 1), world_size=world, strategy="memory_balanced",
               batch_hint=BATCH, compute_dtype=jnp.float32)
  plan = dlrm_embedding_plan(rows, 16, world, "memory_balanced",
                             batch_hint=BATCH)
  cats = [jnp.zeros((BATCH,), jnp.int32) for _ in rows]
  acts = [jnp.zeros((2, 16), jnp.float32) for _ in rows]
  return model, plan, sgd_rule(0.1), optax.sgd(0.1), cats, acts, 13


def _zoo(world):
  cfg = SyntheticModelConfig(
      name="toy", embedding_groups=(
          EmbeddingGroup(2, (1, 4), 2000, 8, True),   # shared, multi-hot
          EmbeddingGroup(3, (1,), 3000, 16, False),
          EmbeddingGroup(2, (1,), 20, 8, False)),     # one-hot dense class
      mlp_sizes=(32, 16), num_numerical_features=4, interact_stride=None)
  tables, tmap, hotness = expand_tables(cfg)
  model = SyntheticModel(config=cfg, world_size=world,
                         strategy="memory_balanced", dense_row_threshold=64,
                         batch_hint=BATCH)
  plan = DistEmbeddingStrategy(tables, world, "memory_balanced",
                               input_table_map=tmap, dense_row_threshold=64,
                               input_hotness=hotness, batch_hint=BATCH)
  cats = [jnp.zeros((BATCH,) if h == 1 else (BATCH, h), jnp.int32)
          for h in hotness]
  acts = [jnp.zeros((2, tables[t].output_dim), jnp.float32) for t in tmap]
  return (model, plan, adagrad_rule(0.01), optax.adagrad(0.01), cats, acts, 4)


def _compiled_step(family, world) -> str:
  model, plan, rule, opt, cats, acts, n_num = family(world)
  numerical = jnp.zeros((BATCH, n_num), jnp.float32)
  dense = model.init(jax.random.PRNGKey(0), numerical[:2],
                     [c[:2] for c in cats], emb_acts=acts)["params"]
  mesh = create_mesh(world, devices=jax.devices()[:world]) \
      if world > 1 else None
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1), mesh=mesh)
  labels = jnp.zeros((BATCH,), jnp.float32)
  step = make_sparse_train_step(model, plan, bce_loss, opt, rule, mesh, state,
                                (numerical, cats, labels), donate=False)
  return step.lower(state, numerical, cats, labels).compile().as_text()


def _layer(op_name: str):
  """(top-level scope, backward) of an ``op_name``, (None, False) without."""
  for part in op_name.split(";")[0].split("/"):
    inner = _WRAPPED.match(part)
    if inner and inner.group(1) in scopes.TOP_LEVEL:
      return inner.group(1), "transpose(" in part
  return None, False


# What may lie outside every top-level scope, as (opcode, op_name) patterns.
# The compiler's own instructions: layout copies, fusion wrappers, rewritten
# dots and hoisted constant broadcasts come with no op_name at all, or with
# one that stops at the functions (``jit(..)/jit(local_step)[/shard_map]``)
# or names a compiler temporary (``broadcast.12``) or a parameter.
COMPILER_MADE = re.compile(
    r"^$|^jit\(\w+\)(/jit\(\w+\))*(/shard_map)?(/[a-z\-]+\.\d+)?$"
    r"|^[a-z\-]+\.\d+$|^state\[")
# The program's own, written out: the step counter's add.
UNSCOPED_BY_DESIGN = re.compile(r"^jit\(\w+\)(/jit\(\w+\))*(/shard_map)?/add$")


@pytest.mark.parametrize("family,world", [
    (_dlrm, 1), (_zoo, 1), (_dlrm, 4), (_zoo, 4)],
    ids=["dlrm-world1", "zoo-world1", "dlrm-world4", "zoo-world4"])
def test_every_instruction_of_the_step_has_a_top_level_scope(family, world):
  text = _compiled_step(family, world)
  seen = collections.Counter()
  stray, total, made = [], 0, 0
  for line in text.splitlines():
    m = _INSTRUCTION.match(line)
    if not m or m.group(1) in NO_WORK:
      continue
    total += 1
    name = _OP_NAME.search(line)
    name = name.group(1) if name else ""
    scope, backward = _layer(name)
    if scope is not None:
      seen[(scope, backward)] += 1
    elif COMPILER_MADE.match(name):
      made += 1
    elif UNSCOPED_BY_DESIGN.match(name):
      if m.group(1) not in ("add", "fusion"):
        stray.append(line.strip()[:200])
    else:
      stray.append(line.strip()[:200])
  assert not stray, "\n".join(stray[:20])
  assert made < 0.3 * total, (made, total)
  for scope in scopes.TOP_LEVEL:
    assert seen[(scope, False)], f"no forward op under {scope}"
  # the differentiated tail has both directions; routing, the fused gather,
  # the dense update and the sparse apply lie outside autodiff
  for scope in (scopes.COMBINE, scopes.MODEL, scopes.LOSS):
    assert seen[(scope, True)], f"no backward op under {scope}"
  for scope in (scopes.ROUTE, scopes.GATHER, scopes.DENSE_UPDATE,
                scopes.APPLY):
    assert not seen[(scope, True)], f"{scope} has a backward op"
  for child in (scopes.ONEHOT,) + ((scopes.EXCHANGE,) if world > 1 else ()) \
      + ((scopes.INTERACT,) if family is _dlrm else ()):
    assert f"{child}/" in text or f"{child})" in text, child


def test_the_vocabulary_is_one_flat_set_of_names():
  names = scopes.TOP_LEVEL + scopes.CHILDREN + scopes.LM_CHILDREN
  assert len(set(names)) == len(names) == 21
  for n in names:
    assert n.startswith("de_") and "/" not in n and "(" not in n
  declared = {v for k, v in vars(scopes).items()
              if k.isupper() and isinstance(v, str)}
  assert declared == set(names)


def test_main_py_profile_dir_traces_five_annotated_steps(tmp_path):
  """``examples/dlrm/main.py --profile_dir``: the operator's use of the
  scopes (docs/ARCHITECTURE.md, "Reading a profile")."""
  import glob
  import os
  import subprocess
  import sys
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  done = subprocess.run(
      [sys.executable, os.path.join(root, "examples", "dlrm", "main.py"),
       "--dataset", "dummy", "--sparse", "--batch_size", "256",
       "--vocab_scale", "0.0001", "--world_size", "1", "--steps", "9",
       "--profile_dir", str(tmp_path)],
      env=env, capture_output=True, text=True, timeout=600)
  assert done.returncode == 0, done.stderr[-2000:]
  assert "profile of steps 4..8" in done.stdout
  files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
  assert len(files) == 1
  data = jax.profiler.ProfileData.from_file(files[0])
  steps = [e.name for plane in data.planes for line in plane.lines
           for e in line.events if e.name == "train"]
  assert len(steps) == 5
