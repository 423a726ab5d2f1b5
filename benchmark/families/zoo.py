"""The reference's synthetic model zoo: sum-combined embeddings over one-hot
and multi-hot inputs, concatenated with the numerical features, MLP to one
logit; Adagrad.

Program side: the recipe of `tools/bench_synthetic.py` (plan ->
``SyntheticModel`` -> ``adagrad_rule`` -> ``make_sparse_train_step``).
Reference side: the reference's `synthetic_models.py` model: one table per
group entry, an input per entry of the group's ``nnz`` (shared tables are
read by several inputs), outputs concatenated in input order, then the
numerical features, then ``mlp_sizes + [1]`` with ReLU on all but the last.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark import reference, traffic


def _expand(config):
  """-> per table (rows, width), per input (table, hotness)."""
  tables, inputs = [], []
  for n_tables, nnz, rows, width, shared in config["embedding_groups"]:
    if len(nnz) > 1 and not shared:
      raise ValueError("several hotnesses need a shared table")
    for _ in range(n_tables):
      tables.append((int(rows), int(width)))
      inputs += [(len(tables) - 1, int(h)) for h in nnz]
  return tables, inputs


def model_spec(config: Dict[str, Any]) -> reference.ModelSpec:
  tables, inputs = _expand(config)
  if config.get("interact_stride") is not None:
    raise NotImplementedError("no reference yet for the strided pooling")
  fan_in = sum(tables[t][1] for t, _ in inputs) \
      + int(config["num_numerical_features"])
  leaves = {}
  for i, w in enumerate(list(config["mlp_sizes"]) + [1]):
    # Glorot-uniform kernels and zero biases (assumed; see the config file)
    leaves[f"mlp/dense_{i}/kernel"] = (
        (fan_in, w), float(np.sqrt(6.0 / (fan_in + w))))
    leaves[f"mlp/dense_{i}/bias"] = ((w,), 0.0)
    fan_in = w
  return reference.ModelSpec(
      tables=tuple(reference.TableSpec(r, w, float(config["table_init_scale"]))
                   for r, w in tables),
      inputs=tuple(traffic.CatInput(t, tables[t][0], h) for t, h in inputs),
      n_numerical=int(config["num_numerical_features"]),
      dense_leaves=leaves, optimizer=dict(config["optimizer"]),
      summed_tables=frozenset(
          t for t, (r, _) in enumerate(tables)
          if r <= int(config["dense_row_threshold"])))


def reference_logits(config, dense, embs, numerical):
  import jax.numpy as jnp
  x = jnp.concatenate(list(embs) + [numerical], axis=1)
  n = len(config["mlp_sizes"]) + 1
  for i in range(n):
    x = x @ dense[f"mlp/dense_{i}/kernel"] + dense[f"mlp/dense_{i}/bias"]
    if i < n - 1:
      x = jnp.maximum(x, 0)
  return x[:, 0]


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by `tools/bench_synthetic.py`'s recipe."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark.program import Parts
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models import SyntheticModel, bce_loss
  from distributed_embeddings_tpu.models.synthetic import (
      EmbeddingGroup,
      SyntheticModelConfig,
      expand_tables,
  )
  from distributed_embeddings_tpu.ops.packed_table import adagrad_rule

  opt = config["optimizer"]
  if opt["name"] != "adagrad":
    raise ValueError("the zoo family trains with Adagrad")
  cfg = SyntheticModelConfig(
      name=config["model_name"],
      embedding_groups=tuple(EmbeddingGroup(n, tuple(nnz), r, w, s)
                             for n, nnz, r, w, s in
                             config["embedding_groups"]),
      mlp_sizes=tuple(config["mlp_sizes"]),
      num_numerical_features=int(config["num_numerical_features"]),
      interact_stride=config.get("interact_stride"))
  tables, tmap, hotness = expand_tables(cfg)
  thr = int(config["dense_row_threshold"])
  strategy = config["plan_strategy"]
  model = SyntheticModel(config=cfg, world_size=world, strategy=strategy,
                         dense_row_threshold=thr, batch_hint=global_batch)
  plan = DistEmbeddingStrategy(tables, world, strategy, input_table_map=tmap,
                               dense_row_threshold=thr,
                               input_hotness=hotness, batch_hint=global_batch)
  lr = float(opt["learning_rate"])
  kw = dict(initial_accumulator_value=float(opt["initial_accumulator_value"]),
            eps=float(opt["eps"]))
  spans, at = [], 0
  for h in hotness:
    spans.append((at, at + h))
    at += h

  def split_cats(m):
    return [m[:, a] if b - a == 1 else m[:, a:b] for a, b in spans]

  n_num = cfg.num_numerical_features
  template = jax.eval_shape(
      lambda: model.init(
          jax.random.PRNGKey(0), jnp.zeros((2, n_num), jnp.float32),
          split_cats(jnp.zeros((2, at), jnp.int32)),
          emb_acts=[jnp.zeros((2, tables[t].output_dim), jnp.float32)
                    for t in tmap])["params"])
  return Parts(model=model, plan=plan, rule=adagrad_rule(lr, **kw),
               optimizer=optax.adagrad(lr, **kw), loss_fn=bce_loss,
               dense_template=template, split_cats=split_cats)
