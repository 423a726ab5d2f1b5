"""SDAR-MoE on the training path: one chip's share of a block-diffusion
mixture-of-experts language model (``configs/sdar-30b-a3b-ep8share.json``).

What the harness fixes, and the way round each. It draws ids per input and
hands neither forward the labels, so the noise is the batch's *numerical
features*: ``seq_len + seq_len / block_length`` uniforms in [0, 1) a sample
(one per position, then one per block), from which program and reference
derive the masked positions and ``t`` alike; the forwards return
``{"logits", "weight"}`` (``weight`` is ``1 / t`` at masked positions, 0
elsewhere); ``make_labels`` draws nothing: the targets are the clean tokens.
The token table is one sequence input; Adam on it is summed
(``ModelSpec.summed_tables``; the program's ``adam_rule(summed=True)``).

Reference side: :func:`reference_logits` is the benchmark's own copy of the
published equations (Qwen3-MoE's decoder as ``sdar_moe`` uses it; block
diffusion as SDAR trains it; the departures are the configuration file's
``assumed``). It imports nothing of the program. So that its ``jax.grad``
fits on the chip beside the weights and their gradients it is computed a
layer at a time under ``jax.checkpoint``, attention a block of queries at a
time against every key under the mask (the mask computed from positions, no
tile skipped), each held expert over every token in turn.

Program side: the recipe of the program's own model
(``models/sdar_moe.py``): plan -> ``SDARMoE`` -> ``adam_rule(summed=True)``
-> ``make_sparse_train_step``.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

import numpy as np

from benchmark import reference, specs, traffic

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "moe_norm", "router", "w_gate", "w_up", "w_down")
QUERY_BLOCK = 256  # queries the reference attends at a time


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
  return dict(
      d=int(config["hidden_size"]), hq=int(config["num_attention_heads"]),
      hkv=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
      f=int(config["moe_intermediate_size"]),
      experts=int(config["num_experts"]),
      top_k=int(config["num_experts_per_tok"]),
      eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
      layers=int(config["num_hidden_layers_here"]),
      first=int(config["experts_held"][0]),
      held=int(config["experts_held"][1]), vocab=int(config["vocab_here"]),
      length=int(config["seq_len"]), block=int(config["block_length"]),
      t_min=float(config["t_min"]))


def make_labels(rng, mix, config, cats):
  """Nothing is drawn: the loss's targets are the clean tokens."""
  del rng, mix, config
  return {"targets": cats}


def loss(jnp, outputs, labels):
  """``sum over masked positions of (1 / t) CE(logits, x0) / (B L)``."""
  logits = outputs["logits"]
  top = jnp.max(logits, axis=-1, keepdims=True)
  lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
  picked = jnp.take_along_axis(logits, labels["targets"][..., None],
                               axis=-1)[..., 0]
  return jnp.mean(outputs["weight"] * (lse - picked))


def model_spec(config: Dict[str, Any]) -> reference.ModelSpec:
  if importlib.util.find_spec(
      "distributed_embeddings_tpu.models.sdar_moe") is None:
    # before the reference's minutes: a program without the model cannot
    # run the cell, and says so at once
    raise specs.SpecError(
        "family sdar_moe: this checkout's program has no "
        "distributed_embeddings_tpu/models/sdar_moe.py")
  s = sizes(config)
  if config["optimizer"]["name"] != "adam":
    raise specs.SpecError("the sdar_moe family trains with Adam")
  scale = float(config["init_scale"])
  d, hq, hkv, hd, f = s["d"], s["hq"], s["hkv"], s["hd"], s["f"]
  leaves = {"mask_embedding": ((d,), scale), "final_norm": ((d,), 0.0, 1.0),
            "head": ((d, s["vocab"]), scale)}
  shapes = {
      "attn_norm": (d,), "wq": (d, hq * hd), "wk": (d, hkv * hd),
      "wv": (d, hkv * hd), "wo": (hq * hd, d), "q_norm": (hd,),
      "k_norm": (hd,), "moe_norm": (d,), "router": (d, s["experts"]),
      "w_gate": (s["held"], d, f), "w_up": (s["held"], d, f),
      "w_down": (s["held"], f, d)}
  for i in range(s["layers"]):
    for name in LAYER_LEAVES:
      gain = name.endswith("_norm")
      leaves[f"layer_{i}_{name}"] = (shapes[name], 0.0, 1.0) if gain \
          else (shapes[name], scale)
  return reference.ModelSpec(
      tables=(reference.TableSpec(s["vocab"], d, scale),),
      inputs=(traffic.CatInput(0, s["vocab"], s["length"], sequence=True),),
      n_numerical=s["length"] + s["length"] // s["block"],
      dense_leaves=leaves, optimizer=dict(config["optimizer"]),
      summed_tables=frozenset({0}), loss=loss)


def reference_logits(config, dense, embs, numerical):
  """The plain equations, in the dtype the arguments come in (float32; the
  bfloat16 control hands everything over rounded)."""
  import jax
  import jax.numpy as jnp

  s = sizes(config)
  (rows,) = embs                                          # [B, L, d]
  dt = rows.dtype
  length, block, hq, hkv, hd = (s["length"], s["block"], s["hq"], s["hkv"],
                                s["hd"])

  def rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + jnp.asarray(s["eps"], dt)) * gain

  # the noise: one uniform per position, then one per block
  t = s["t_min"] + (1.0 - s["t_min"]) * numerical[:, length:]
  t = jnp.repeat(t, block, axis=1)
  masked = numerical[:, :length] < t
  weight = jnp.where(masked, 1.0 / t, 0.0)
  xt = jnp.where(masked[..., None], dense["mask_embedding"], rows)
  x = jnp.concatenate([xt, rows], axis=1)                 # [B, 2 L, d]
  b, n_pos, _ = x.shape

  # RoPE: both halves are numbered 0 .. L-1
  inv = 1.0 / s["theta"] ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
  pos = np.tile(np.arange(length, dtype=np.float32), 2)
  ang = jnp.asarray(pos[:, None] * inv[None, :])
  cos = jnp.concatenate([jnp.cos(ang)] * 2, -1).astype(dt)[None, :, None, :]
  sin = jnp.concatenate([jnp.sin(ang)] * 2, -1).astype(dt)[None, :, None, :]

  def rotate(y):
    y1, y2 = y[..., :hd // 2], y[..., hd // 2:]
    return y * cos + jnp.concatenate([-y2, y1], axis=-1) * sin

  key_pos = jnp.arange(n_pos)
  key_noisy, key_block = key_pos < length, (key_pos % length) // block
  q_block = min(QUERY_BLOCK, n_pos)

  @jax.checkpoint
  def attend(q_blk, first, k, v):
    """``q_blk [B, q, H, hd]`` from position ``first`` on, against every
    key, under the mask."""
    q_pos = first + jnp.arange(q_block)
    q_noisy, qb = (q_pos < length)[:, None], ((q_pos % length) // block)[:, None]
    allowed = jnp.where(
        q_noisy,
        jnp.where(key_noisy[None], key_block[None] == qb, key_block[None] < qb),
        ~key_noisy[None] & (key_block[None] <= qb))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) \
        * jnp.asarray(hd ** -0.5, dt)
    scores = jnp.where(allowed[None, None], scores.astype(jnp.float32),
                       -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, v)

  @jax.checkpoint
  def expert(h, chosen, w_gate, w_up, w_down):
    y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
    return chosen[..., None] * y

  @jax.checkpoint
  def layer(p, x):
    h = rms(x, p["attn_norm"])
    q = rotate(rms((h @ p["wq"]).reshape(b, n_pos, hq, hd), p["q_norm"]))
    k = rotate(rms((h @ p["wk"]).reshape(b, n_pos, hkv, hd), p["k_norm"]))
    v = (h @ p["wv"]).reshape(b, n_pos, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)  # query head n reads key head
    v = jnp.repeat(v, hq // hkv, axis=2)  # n // (hq / hkv)
    out = jax.lax.map(
        lambda xs: attend(xs[0], xs[1], k, v),
        (jnp.moveaxis(q.reshape(b, n_pos // q_block, q_block, hq, hd), 1, 0),
         jnp.arange(0, n_pos, q_block)))
    attn = jnp.moveaxis(out, 0, 1).reshape(b, n_pos, hq * hd)
    x = x + attn @ p["wo"]
    h = rms(x, p["moe_norm"])
    with jax.default_matmul_precision("highest"):
      logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    prob = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(prob, s["top_k"])
    top_p = (top_p / jnp.sum(top_p, axis=-1, keepdims=True)).astype(dt)
    y = jnp.zeros_like(x)
    for e in range(s["held"]):   # the experts this chip holds, one by one
      chosen = jnp.sum(jnp.where(top_e == s["first"] + e, top_p, 0), axis=-1)
      y = y + expert(h, chosen, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return x + y

  for i in range(s["layers"]):
    x = layer({n: dense[f"layer_{i}_{n}"] for n in LAYER_LEAVES}, x)
  h = rms(x[:, :length], dense["final_norm"])
  return {"logits": h @ dense["head"], "weight": weight}


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by the recipe of `models/sdar_moe.py`."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark.program import Parts
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models.sdar_moe import (
      SDARMoE,
      SDARMoEConfig,
      block_diffusion_loss,
  )
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  s, opt = sizes(config), config["optimizer"]
  cfg = SDARMoEConfig(
      hidden_size=s["d"], num_attention_heads=s["hq"],
      num_key_value_heads=s["hkv"], head_dim=s["hd"],
      moe_intermediate_size=s["f"], num_experts=s["experts"],
      num_experts_per_tok=s["top_k"], rms_norm_eps=s["eps"],
      rope_theta=s["theta"], num_hidden_layers=s["layers"],
      vocab_size=s["vocab"], experts_held=(s["first"], s["held"]),
      block_length=s["block"], t_min=s["t_min"], seq_len=s["length"],
      # the configuration names its attention path ("splash": the TPU's
      # kernel, so a run that finds no TPU fails instead of timing
      # something else); a toy copy for the CPU names "xla" itself
      attention=str(config["attention"]))
  model = SDARMoE(cfg)
  plan = DistEmbeddingStrategy(
      [TableConfig(input_dim=s["vocab"], output_dim=s["d"], combiner=None)],
      world, config["plan_strategy"], input_table_map=[0],
      dense_row_threshold=int(config["dense_row_threshold"]),
      input_hotness=[s["length"]], batch_hint=global_batch)
  lr = float(opt["learning_rate"])
  kw = dict(b1=float(opt["b1"]), b2=float(opt["b2"]), eps=float(opt["eps"]))
  template = jax.eval_shape(
      lambda: model.init(
          jax.random.PRNGKey(0), jnp.zeros((2, cfg.n_numerical), jnp.float32),
          None, emb_acts=[jnp.zeros((2, s["length"], s["d"]), jnp.float32)]
      )["params"])
  return Parts(model=model, plan=plan, rule=adam_rule(lr, summed=True, **kw),
               optimizer=optax.adam(lr, **kw), loss_fn=block_diffusion_loss,
               dense_template=template, split_cats=lambda m: [m])
