"""A toy sequence family: the harness's test of its own family interface and
nothing else. It is not a model of anything, is no cell's family, and no
real family should start from it.

What it asks of the harness that DLRM and the zoo do not: an input kept as
a sequence (one table read at hotness L, the rows not summed); labels that
are a tree (targets ``[B, L]``, a mask ``[B, L]``, a weight ``[B]``) drawn
after the ids; outputs ``[B, L, V]`` under a loss of its own (weighted
cross-entropy at the masked positions only); a leaf that starts at 1 (the
norm's gain) and a leaf of rank 3 (a stack of expert matrices, one chosen
per position by a top-1 router); Adam on the dense leaves and, through
``adam_rule``, per occurrence on the table's rows; no numerical features.

Program side: the lookup engine returns rows uncombined only for hotness-1
inputs (``parallel/lookup_engine.py::_combine``), so the one input of
hotness L is L hotness-1 inputs that share the table (the zoo's way), and
the flax model stacks their rows again. ``make_sparse_train_step`` hands
the labels to ``loss_fn`` as they come, so the tree travels as it is.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark import reference, traffic

NORM_EPS = 1e-6


def make_labels(rng, mix, config, cats):
  """Drawn after the ids: a target token per position, the positions the
  loss counts (the mix's ``mask_rate``), a weight per sequence."""
  b, length = cats.shape
  return {
      "targets": rng.integers(0, int(config["vocab"]), size=(b, length)
                              ).astype(np.int32),
      "mask": (rng.random((b, length)) < float(mix["mask_rate"])
               ).astype(np.float32),
      "weight": rng.uniform(0.5, 1.5, size=(b,)).astype(np.float32)}


def loss(jnp, outputs, labels):
  """Weighted cross-entropy at the masked positions, over all positions of
  the batch (a constant, so a shard's mean is the batch's)."""
  top = jnp.max(outputs, axis=-1, keepdims=True)
  lse = jnp.log(jnp.sum(jnp.exp(outputs - top), axis=-1)) + top[..., 0]
  picked = jnp.take_along_axis(outputs, labels["targets"][..., None],
                               axis=-1)[..., 0]
  return jnp.mean(labels["weight"][:, None] * labels["mask"]
                  * (lse - picked))


def model_spec(config: Dict[str, Any]) -> reference.ModelSpec:
  v, w, f = int(config["vocab"]), int(config["width"]), int(config["ffn"])
  e = int(config["experts"])
  glorot = lambda a, b: float(np.sqrt(6.0 / (a + b)))
  return reference.ModelSpec(
      tables=(reference.TableSpec(v, w, float(config["table_init_scale"])),),
      inputs=(traffic.CatInput(0, v, int(config["seq_len"]), sequence=True),),
      n_numerical=0,
      dense_leaves={
          "gain": ((w,), 0.0, 1.0),            # starts at 1
          "router": ((w, e), glorot(w, e)),
          "up": ((e, w, f), glorot(w, f)),     # rank 3: a stack of experts
          "down": ((e, f, w), glorot(f, w)),
          "head": ((w, v), glorot(w, v))},     # untied from the table
      optimizer=dict(config["optimizer"]), loss=loss)


def _equations(jnp, dense, x):
  """``x [B, L, W]`` -> ``[B, L, V]``; shared by nobody: the flax model
  below writes the same equations again."""
  h = x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + NORM_EPS)) * dense["gain"]
  scores = h @ dense["router"]                                  # [B, L, E]
  top = jnp.max(scores, axis=-1, keepdims=True)
  prob = jnp.exp(scores - top)
  prob = prob / jnp.sum(prob, axis=-1, keepdims=True)
  pick = (scores >= top).astype(h.dtype)                        # top-1
  pick = pick / jnp.sum(pick, axis=-1, keepdims=True)
  mid = jnp.maximum(jnp.einsum("blw,ewf->blef", h, dense["up"]), 0)
  out = jnp.einsum("blef,efw->blew", mid, dense["down"])
  y = jnp.sum(out * (pick * prob)[..., None], axis=2)
  return (x + y) @ dense["head"]


def reference_logits(config, dense, embs, numerical):
  import jax.numpy as jnp
  del config, numerical
  (x,) = embs
  return _equations(jnp, dense, x)


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  import flax.linen as nn
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark.program import Parts
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  opt = config["optimizer"]
  if opt["name"] != "adam":
    raise ValueError("the toy sequence family trains with Adam")
  v, w, f = int(config["vocab"]), int(config["width"]), int(config["ffn"])
  e, length = int(config["experts"]), int(config["seq_len"])

  class ToySeq(nn.Module):

    @nn.compact
    def __call__(self, numerical, cats, emb_acts=None):
      del numerical, cats
      x = jnp.stack(list(emb_acts), axis=1)
      init = nn.initializers.zeros
      gain = self.param("gain", init, (w,))
      router = self.param("router", init, (w, e))
      up = self.param("up", init, (e, w, f))
      down = self.param("down", init, (e, f, w))
      head = self.param("head", init, (w, v))
      h = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + NORM_EPS) * gain
      scores = jnp.einsum("blw,we->ble", h, router)
      prob = jax.nn.softmax(scores, axis=-1)
      pick = jax.nn.one_hot(jnp.argmax(scores, -1), e, dtype=h.dtype)
      mid = nn.relu(jnp.einsum("blw,ewf->blef", h, up))
      out = jnp.einsum("blef,efw->blew", mid, down)
      y = jnp.sum(out * (pick * prob)[..., None], axis=2)
      return jnp.einsum("blw,wv->blv", x + y, head)

  def loss_fn(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels["targets"][..., None], -1)[..., 0]
    return jnp.mean(labels["weight"][:, None] * labels["mask"] * nll)

  plan = DistEmbeddingStrategy(
      [TableConfig(input_dim=v, output_dim=w, combiner="sum")], world,
      config["plan_strategy"], input_table_map=[0] * length,
      dense_row_threshold=int(config["dense_row_threshold"]),
      input_hotness=[1] * length, batch_hint=global_batch)
  lr = float(opt["learning_rate"])
  kw = dict(b1=float(opt["b1"]), b2=float(opt["b2"]), eps=float(opt["eps"]))
  model = ToySeq()
  template = jax.eval_shape(
      lambda: model.init(
          jax.random.PRNGKey(0), jnp.zeros((2, 0), jnp.float32), None,
          emb_acts=[jnp.zeros((2, w), jnp.float32)] * length)["params"])
  return Parts(model=model, plan=plan, rule=adam_rule(lr, **kw),
               optimizer=optax.adam(lr, **kw), loss_fn=loss_fn,
               dense_template=template,
               split_cats=lambda m: [m[:, i] for i in range(length)])
