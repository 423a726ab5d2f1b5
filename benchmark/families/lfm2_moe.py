"""LFM2-MoE on the training path: one chip's share of a decoder in which the
mixer's kind and the feed-forward's kind vary independently by layer
(double-gated short convolutions beside grouped-query attention at a head of
64; a dense MLP on the leading layers, then sigmoid-routed experts chosen
under a selection bias), over packed documents
(``configs/lfm2-24b-a2b-ep8share.json``).

What the harness fixes, and the way round each, is `families/laguna.py`'s:
*where documents start* is the batch's numerical features (``seq_len``
uniforms a sample; position 0 starts a document and position ``i > 0`` one
where ``u_i < 1 / mean_document_length``); the forwards return ``{"logits",
"weight"}`` (``weight`` 1 where the next token continues the document);
``make_labels`` draws nothing, the targets are the ids shifted by one; the
loss is ``sum(weight CE) / sum(weight)``; the token table is one sequence
input under summed Adam; ``build_parts`` lowers ``program.READ_CHUNK``;
``model_spec`` installs `benchmark/in_blocks.py` (4.7e8 dense values).

*A leaf no gradient reaches.* ``expert_bias`` enters the choice of experts
and nothing differentiable, so the reference's gradient of it is exactly 0,
Adam's first step from zero moments is 0, and `check.worst_gap` judges the
program's change of it on the median leaf's scale: any movement shows.

Reference side: :func:`reference_logits` is the benchmark's own copy of the
equations (the configuration file's ``equations`` and ``assumed``). It
imports nothing of the program. The convolution by shifted copies of
``B * u`` under a mask computed from positions (a tap is read where the
earlier position is not before the document's first); attention by full
scores, a block of queries at a time against EVERY key under the mask from
positions (causal, same document), keys and values repeated to the query
heads; the experts by a loop, each held expert over every token in turn; the
router's product at ``highest``, the choice on ``s + b`` scattered into a
mask, the weights from ``s``. A layer, a block of queries and an expert are
each under ``jax.checkpoint`` so that its ``jax.grad`` fits on the chip
beside the weights and their gradients. :func:`reference_faults` names three
wrong forwards that `benchmark/control_sequential.py` puts in the
reference's place.

Program side: the recipe of the program's own model (``models/lfm2_moe.py``):
plan -> ``Lfm2Moe`` -> ``adam_rule(summed=True)`` ->
``make_sparse_train_step``.
"""

from __future__ import annotations

import functools
import importlib.util
from typing import Any, Dict

import numpy as np

from benchmark import in_blocks, reference, specs, traffic

CONV, FULL = "conv", "full_attention"
DENSE, EXPERTS = "dense", "experts"
QUERY_BLOCK = 128   # queries the reference attends at a time


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
  here = tuple(int(i) for i in config["layers_here"])
  dense_layers = int(config["num_dense_layers"])
  return dict(
      d=int(config["hidden_size"]), f=int(config["intermediate_size"]),
      hq=int(config["num_attention_heads"]),
      hkv=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
      fe=int(config["moe_intermediate_size"]),
      experts=int(config["num_experts"]),
      top_k=int(config["num_experts_per_tok"]),
      renormalise=bool(config["norm_topk_prob"]),
      routed_scale=float(config["routed_scaling_factor"]),
      biased=bool(config["use_expert_bias"]),
      taps=int(config["conv_L_cache"]), eps=float(config["norm_eps"]),
      theta=float(config["rope_parameters"]["rope_theta"]),
      first=int(config["experts_held"][0]),
      held=int(config["experts_held"][1]), here=here,
      dense_layers=dense_layers, layer_types=tuple(config["layer_types"]),
      # (mixer, feed-forward) of every layer that runs here
      kinds=tuple((config["layer_types"][i],
                   DENSE if i < dense_layers else EXPERTS) for i in here),
      vocab=int(config["vocab_here"]), length=int(config["seq_len"]),
      mean_doc=int(config["mean_document_length"]))


def layer_leaves(s: Dict[str, Any], mixer: str, ffn: str,
                 scales: Dict[str, float]) -> Dict[str, Any]:
  """name -> (shape, scale[, offset]) of one layer's dense leaves: the
  mixer's from its kind, the feed-forward's from its."""
  d, scale = s["d"], scales["matrix"]
  gain = lambda *shape: (shape, 0.0, 1.0)
  leaves = {"operator_norm": gain(d), "ffn_norm": gain(d)}
  if mixer == CONV:
    leaves.update({"w_in": ((d, 3 * d), scale),
                   "conv": ((s["taps"], d), scales["conv"]),
                   "w_out": ((d, d), scale)})
  else:
    cq, ckv = s["hq"] * s["hd"], s["hkv"] * s["hd"]
    leaves.update({"wq": ((d, cq), scale), "wk": ((d, ckv), scale),
                   "wv": ((d, ckv), scale), "wo": ((cq, d), scale),
                   "q_norm": gain(s["hd"]), "k_norm": gain(s["hd"])})
  if ffn == DENSE:
    f = s["f"]
    return {**leaves, "w_gate": ((d, f), scale), "w_up": ((d, f), scale),
            "w_down": ((f, d), scale)}
  fe, held = s["fe"], s["held"]
  leaves.update({"router": ((d, s["experts"]), scale),
                 "w_gate": ((held, d, fe), scale),
                 "w_up": ((held, d, fe), scale),
                 "w_down": ((held, fe, d), scale)})
  if s["biased"]:
    leaves["expert_bias"] = ((s["experts"],), scales["bias"])
  return leaves


def make_labels(rng, mix, config, cats):
  """Nothing is drawn: a position's target is the next token (the last
  position's counts for nothing)."""
  del rng, mix, config
  return {"targets": np.concatenate(
      [cats[:, 1:], np.zeros_like(cats[:, :1])], axis=1)}


def loss(jnp, outputs, labels):
  """Mean over the positions that are not a document's last of
  ``CE(logits_t, token_{t+1})``."""
  logits, weight = outputs["logits"], outputs["weight"]
  top = jnp.max(logits, axis=-1, keepdims=True)
  lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
  picked = jnp.take_along_axis(logits, labels["targets"][..., None],
                               axis=-1)[..., 0]
  return jnp.sum(weight * (lse - picked)) / jnp.maximum(jnp.sum(weight), 1.0)


def model_spec(config: Dict[str, Any]) -> reference.ModelSpec:
  if importlib.util.find_spec(
      "distributed_embeddings_tpu.models.lfm2_moe") is None:
    # before the reference's minutes: a program without the model cannot
    # run the cell, and says so at once
    raise specs.SpecError(
        "family lfm2_moe: this checkout's program has no "
        "distributed_embeddings_tpu/models/lfm2_moe.py")
  in_blocks.install()
  s = sizes(config)
  if config["optimizer"]["name"] != "adam":
    raise specs.SpecError("the lfm2_moe family trains with Adam")
  if set(s["layer_types"]) - {CONV, FULL}:
    raise specs.SpecError(f"layer_types {s['layer_types']}: {CONV} or {FULL}")
  scales = {"matrix": float(config["init_scale"]),
            "conv": float(config["assumed_sizes"]["conv_init_bound"]),
            "bias": float(config["assumed_sizes"]["expert_bias_spread"])}
  leaves = {"embedding_norm": ((s["d"],), 0.0, 1.0),
            "head": ((s["d"], s["vocab"]), scales["matrix"])}
  for i, kinds in enumerate(s["kinds"]):
    for name, leaf in layer_leaves(s, *kinds, scales).items():
      leaves[f"layer_{i}_{name}"] = leaf
  return reference.ModelSpec(
      tables=(reference.TableSpec(s["vocab"], s["d"], scales["matrix"]),),
      inputs=(traffic.CatInput(0, s["vocab"], s["length"], sequence=True),),
      n_numerical=s["length"], dense_leaves=leaves,
      optimizer=dict(config["optimizer"]), summed_tables=frozenset({0}),
      loss=loss)


def reference_logits(config, dense, embs, numerical, *, reset=True,
                     choose_biased=True, weigh_biased=False, counters=False):
  """The plain equations, in the dtype the arguments come in (float32; the
  bfloat16 control hands everything over rounded; the router's product is
  float32 at ``highest`` either way). ``reset=False``,
  ``choose_biased=False`` and ``weigh_biased=True`` are
  :func:`reference_faults`' wrong forwards; ``counters`` adds, an expert
  layer, the assignments on the held experts and the choices the bias
  moved, int32 ``[expert layers]`` each."""
  import jax
  import jax.numpy as jnp

  s = sizes(config)
  (rows,) = embs                                          # [B, L, d]
  dt = rows.dtype
  b, length, d = rows.shape
  hq, hkv, hd = s["hq"], s["hkv"], s["hd"]
  eps = jnp.asarray(s["eps"], dt)

  def rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain

  pos = jnp.arange(length)
  starts = (numerical < 1.0 / s["mean_doc"]) | (pos == 0)[None, :]
  # the first position of each position's document
  first = jax.lax.cummax(jnp.where(starts, pos[None, :], 0), axis=1)

  inv = 1.0 / s["theta"] ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
  ang = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  cos, sin = (jnp.asarray(t, dt)[None, :, None, :]
              for t in (np.cos(ang), np.sin(ang)))

  def rotate(y):
    y1, y2 = y[..., :hd // 2], y[..., hd // 2:]
    return y * cos + jnp.concatenate([-y2, y1], axis=-1) * sin

  def short_conv(p, h):
    gate_in, gate_out, u = jnp.split(h @ p["w_in"], 3, axis=-1)
    z = gate_in * u
    c = z * p["conv"][s["taps"] - 1]
    for back in range(1, s["taps"]):
      earlier = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :length]
      # the position `back` before lies in this position's document
      seen = (pos[None, :] - back >= first) if reset \
          else jnp.broadcast_to(pos[None, :] >= back, first.shape)
      c = c + jnp.where(seen[..., None], earlier, 0) \
          * p["conv"][s["taps"] - 1 - back]
    return (gate_out * c) @ p["w_out"]

  q_block = min(QUERY_BLOCK, length)

  @jax.checkpoint
  def attend(q_blk, at, k, v):
    """``q_blk [B, q, H, hd]`` from position ``at`` on, against every key:
    causal and inside the query's document."""
    q_pos = at + jnp.arange(q_block)
    q_first = jax.lax.dynamic_slice_in_dim(first, at, q_block, axis=1)
    allowed = (pos[None, None, :] <= q_pos[None, :, None]) \
        & (pos[None, None, :] >= q_first[:, :, None])          # [B, q, L]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) \
        * jnp.asarray(hd ** -0.5, dt)
    scores = jnp.where(allowed[:, None], scores.astype(jnp.float32),
                       -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, v)

  def attention(p, h):
    q = rotate(rms((h @ p["wq"]).reshape(b, length, hq, hd), p["q_norm"]))
    k = rotate(rms((h @ p["wk"]).reshape(b, length, hkv, hd), p["k_norm"]))
    v = (h @ p["wv"]).reshape(b, length, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)  # query head n reads key-value
    v = jnp.repeat(v, hq // hkv, axis=2)  # head n // (hq / hkv)
    pad = -length % q_block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda xs: attend(xs[0], xs[1], k, v),
        (jnp.moveaxis(q.reshape(b, -1, q_block, hq, hd), 1, 0),
         jnp.arange(0, length + pad, q_block)))
    a = jnp.moveaxis(out, 0, 1).reshape(b, length + pad, hq * hd)
    return a[:, :length] @ p["wo"]

  @jax.checkpoint
  def expert(h, w, w_gate, w_up, w_down):
    return w[..., None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

  def experts(p, h):
    with jax.default_matmul_precision("highest"):
      logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    score = jax.nn.sigmoid(logits)
    biased = score + p["expert_bias"].astype(jnp.float32) if s["biased"] \
        else score
    _, top_e = jax.lax.top_k(biased if choose_biased else score, s["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(top_e, s["experts"], dtype=jnp.float32),
                     axis=-2)                                 # [B, L, E] 0/1
    weight = (biased if weigh_biased else score) * chosen
    if s["renormalise"]:
      weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = (s["routed_scale"] * weight).astype(dt)

    def one(y, xs):   # an expert this chip holds, over every token
      e, w_gate, w_up, w_down = xs
      return y + expert(h, jnp.take(weight, s["first"] + e, axis=-1),
                        w_gate, w_up, w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(s["held"]), p["w_gate"], p["w_up"],
                         p["w_down"]))
    _, plain_e = jax.lax.top_k(score, s["top_k"])
    plain = jnp.sum(jax.nn.one_hot(plain_e, s["experts"], dtype=jnp.float32),
                    axis=-2)
    held = chosen[..., s["first"]:s["first"] + s["held"]]
    return y, (jnp.sum(held).astype(jnp.int32),
               jnp.sum(chosen * (1 - plain)).astype(jnp.int32))

  def layer(mixer, ffn, p, x):
    h = rms(x, p["operator_norm"])
    x = x + (short_conv(p, h) if mixer == CONV else attention(p, h))
    h = rms(x, p["ffn_norm"])
    if ffn == DENSE:
      return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) \
          @ p["w_down"], None
    y, counts = experts(p, h)
    return x + y, counts

  x, counted = rows, []
  for i, kinds in enumerate(s["kinds"]):
    prefix = f"layer_{i}_"
    p = {n[len(prefix):]: w for n, w in dense.items() if n.startswith(prefix)}
    x, counts = jax.checkpoint(functools.partial(layer, *kinds))(p, x)
    if counts is not None:
      counted.append(counts)
  weight = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  out = {"logits": rms(x, dense["embedding_norm"]) @ dense["head"],
         "weight": weight.astype(dt)}
  if counters:
    out["assignments"] = jnp.stack([a for a, _ in counted])
    out["moved"] = jnp.stack([m for _, m in counted])
  return out


def reference_faults(config: Dict[str, Any]):
  """Wrong forwards for ``benchmark/control_sequential.py --stand_ins``, put in
  the reference's place at the cell's own size: name -> (``logits_fn``,
  ``loss``). ``no_bias``: the experts chosen on the unbiased scores;
  ``biased_weights``: the chosen experts weighted by ``s + b``;
  ``no_reset``: the convolution reads across a document's first token."""
  sound = functools.partial(reference_logits, config)
  return {"no_bias": (functools.partial(sound, choose_biased=False), loss),
          "biased_weights": (functools.partial(sound, weigh_biased=True),
                             loss),
          "no_reset": (functools.partial(sound, reset=False), loss)}


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by the recipe of `models/lfm2_moe.py`."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark import program
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
  from distributed_embeddings_tpu.models.olmo_hybrid import next_token_loss
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  s, opt = sizes(config), config["optimizer"]
  # as `families/laguna.py`: the check's read-back gathers `READ_CHUNK`
  # physical rows at a time whatever their width; at this table's 6,144
  # lanes (2,048 and Adam's two moments) a chunk of at most 256 MiB
  row_bytes = 3 * s["d"] * 4
  program.READ_CHUNK = min(program.READ_CHUNK,
                           1 << ((1 << 28) // row_bytes).bit_length() - 1)
  cfg = Lfm2MoeConfig(
      hidden_size=s["d"], intermediate_size=s["f"],
      num_attention_heads=s["hq"], num_key_value_heads=s["hkv"],
      head_dim=s["hd"], moe_intermediate_size=s["fe"],
      num_experts=s["experts"], num_experts_per_tok=s["top_k"],
      norm_topk_prob=s["renormalise"], routed_scaling_factor=s["routed_scale"],
      use_expert_bias=s["biased"], conv_L_cache=s["taps"], norm_eps=s["eps"],
      rope_theta=s["theta"], num_dense_layers=s["dense_layers"],
      layer_types=s["layer_types"], layers_here=s["here"],
      vocab_size=s["vocab"], experts_held=(s["first"], s["held"]),
      seq_len=s["length"], mean_document_length=s["mean_doc"],
      # the configuration names its attention path ("splash": the TPU's
      # kernel, so a run that finds no TPU fails instead of timing
      # something else); a toy copy for the CPU names "xla" itself
      attention=str(config["attention"]))
  model = Lfm2Moe(cfg)
  plan = DistEmbeddingStrategy(
      [TableConfig(input_dim=s["vocab"], output_dim=s["d"], combiner=None)],
      world, config["plan_strategy"], input_table_map=[0],
      dense_row_threshold=int(config["dense_row_threshold"]),
      input_hotness=[s["length"]], batch_hint=global_batch)
  lr = float(opt["learning_rate"])
  kw = dict(b1=float(opt["b1"]), b2=float(opt["b2"]), eps=float(opt["eps"]))
  template = jax.eval_shape(
      lambda: model.init(
          jax.random.PRNGKey(0), jnp.zeros((2, s["length"]), jnp.float32),
          None, emb_acts=[jnp.zeros((2, s["length"], s["d"]), jnp.float32)]
      )["params"])
  return program.Parts(
      model=model, plan=plan, rule=adam_rule(lr, summed=True, **kw),
      optimizer=optax.adam(lr, **kw), loss_fn=next_token_loss,
      dense_template=template, split_cats=lambda m: [m])
