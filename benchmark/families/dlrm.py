"""DLRM (dot interaction): the program objects for a DLRM configuration, and
the model's plain equations for the reference.

Program side: the recipe of `examples/dlrm/main.py --sparse` (plan ->
``DLRM`` -> ``sgd_rule``), built in-process. Reference side: the published
model (Naumov et al. 2019; the reference's `examples/dlrm/main.py`): bottom
MLP with ReLU after every layer, pairwise dot products of the bottom output
and the 26 embeddings, strict lower triangle in row-major order, concatenated
with the bottom output, top MLP with ReLU on all but the last layer.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark import reference, traffic


def vocab(config: Dict[str, Any]):
  return [max(int(config["min_rows"]), int(v * config["vocab_scale"]))
          for v in config["vocab_sizes"]]


def _mlp_leaves(prefix, widths, fan_in):
  leaves = {}
  for i, w in enumerate(widths):
    # Glorot-uniform kernels and zero biases (assumed; see the config file)
    leaves[f"{prefix}/dense_{i}/kernel"] = (
        (fan_in, w), float(np.sqrt(6.0 / (fan_in + w))))
    leaves[f"{prefix}/dense_{i}/bias"] = ((w,), 0.0)
    fan_in = w
  return leaves


def model_spec(config: Dict[str, Any]) -> reference.ModelSpec:
  rows = vocab(config)
  width = int(config["embedding_width"])
  n_feat = len(rows) + 1
  top_in = n_feat * (n_feat - 1) // 2 + width
  leaves = _mlp_leaves("bottom_mlp", config["bottom_mlp"],
                       int(config["num_numerical_features"]))
  leaves.update(_mlp_leaves("top_mlp", config["top_mlp"], top_in))
  return reference.ModelSpec(
      # uniform(+-1/sqrt(rows)) per table: the reference's DLRMInitializer
      tables=tuple(reference.TableSpec(r, width, float(1.0 / np.sqrt(r)))
                   for r in rows),
      inputs=tuple(traffic.CatInput(t, r, 1) for t, r in enumerate(rows)),
      n_numerical=int(config["num_numerical_features"]),
      dense_leaves=leaves, optimizer=dict(config["optimizer"]))


def reference_logits(config, dense, embs, numerical):
  import jax.numpy as jnp

  def mlp(prefix, x, n, relu_last):
    for i in range(n):
      x = x @ dense[f"{prefix}/dense_{i}/kernel"] \
          + dense[f"{prefix}/dense_{i}/bias"]
      if i < n - 1 or relu_last:
        x = jnp.maximum(x, 0)
    return x

  bottom = mlp("bottom_mlp", numerical, len(config["bottom_mlp"]), True)
  feats = jnp.stack([bottom] + list(embs), axis=1)  # [B, F, D]
  inter = jnp.einsum("bpd,bqd->bpq", feats, feats)
  r, c = np.tril_indices(feats.shape[1], k=-1)
  z = jnp.concatenate([inter[:, r, c], bottom], axis=1)
  return mlp("top_mlp", z, len(config["top_mlp"]), False)[:, 0]


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by `examples/dlrm/main.py`'s sparse recipe."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark.program import Parts
  from distributed_embeddings_tpu.models import DLRM, bce_loss
  from distributed_embeddings_tpu.models.dlrm import dlrm_embedding_plan
  from distributed_embeddings_tpu.ops.packed_table import sgd_rule

  if config["optimizer"]["name"] != "sgd":
    raise ValueError("the dlrm family trains with SGD")
  rows, width = vocab(config), int(config["embedding_width"])
  lr = float(config["optimizer"]["learning_rate"])
  strategy = config["plan_strategy"]
  model = DLRM(vocab_sizes=rows, embedding_dim=width,
               bottom_mlp=tuple(config["bottom_mlp"]),
               top_mlp=tuple(config["top_mlp"]), world_size=world,
               strategy=strategy, batch_hint=global_batch,
               compute_dtype=jnp.float32)
  plan = dlrm_embedding_plan(rows, width, world, strategy,
                             batch_hint=global_batch)
  n_num = int(config["num_numerical_features"])
  template = jax.eval_shape(
      lambda: model.init(
          jax.random.PRNGKey(0), jnp.zeros((2, n_num), jnp.float32),
          [jnp.zeros((2,), jnp.int32) for _ in rows],
          emb_acts=[jnp.zeros((2, width), jnp.float32) for _ in rows]
      )["params"])
  return Parts(
      model=model, plan=plan, rule=sgd_rule(lr), optimizer=optax.sgd(lr),
      loss_fn=bce_loss, dense_template=template,
      split_cats=lambda m: [m[:, i] for i in range(len(rows))])
