"""GLM-4.7-Flash on the training path: one chip's share of a decoder of latent
attention (low-rank queries, keys and values with a norm in the middle, heads
of 256 whose keys end in ONE rotary part shared by all) over sigmoid-routed
experts chosen under a selection bias beside a shared expert, with a
multi-token-prediction module that shares the token table and the head with
the trunk, over packed documents (``configs/glm-4.7-flash-ep8share.json``).

What the harness fixes, and the way round each, is `families/laguna.py`'s:
*where documents start* is the batch's numerical features (``seq_len``
uniforms a sample; position 0 starts a document and position ``i > 0`` one
where ``u_i < 1 / mean_document_length``); the token table is one sequence
input under summed Adam; ``build_parts`` lowers ``program.READ_CHUNK``;
``model_spec`` installs `benchmark/in_blocks.py` (6.7e8 dense values).

*Two predictions, two shifts.* The forwards return ``{"logits", "weight",
"mtp_logits", "mtp_weight"}``; ``make_labels`` draws nothing and gives the
tree ``{"targets", "targets_2"}``, the ids shifted by one and by two;
:func:`loss` is ``CE(logits, targets) + mtp_loss_weight * CE(mtp_logits,
targets_2)``, each a mean over its own weights. The token rows enter the
model twice (as they are, and shifted by one into the module) and the head
is an operand of two products, so one ``value_and_grad`` sums two uses into
a table row's gradient and into the head's.

*A leaf no gradient reaches.* ``expert_bias`` enters the choice of experts
and nothing differentiable: `families/lfm2_moe.py`'s note holds here.

Reference side: :func:`reference_logits` is the benchmark's own copy of the
equations (the configuration file's ``equations`` and ``assumed``). It
imports nothing of the program. Attention by full scores, a block of queries
at a time against EVERY key under the mask from positions (causal, same
document), the shared rotary key broadcast to the heads; the experts by a
loop, each held expert over every token in turn, the shared expert beside
them; the router's product at ``highest``, the choice on ``s + b`` scattered
into a mask, the weights from ``s``. A layer, the module, a block of queries
and an expert are each under ``jax.checkpoint`` so that its ``jax.grad``
fits on the chip beside the weights and their gradients.
:func:`reference_faults` names five wrong steps that
`benchmark/control_sequential.py` puts in the reference's place.

Program side: the recipe of the program's own model
(``models/glm_moe_lite.py``): plan -> ``GlmMoeLite`` ->
``adam_rule(summed=True)`` -> ``make_sparse_train_step`` under
``mtp_training_loss``.
"""

from __future__ import annotations

import functools
import importlib.util
from typing import Any, Dict

import numpy as np

from benchmark import in_blocks, reference, specs, traffic

QUERY_BLOCK = 128   # queries the reference attends at a time


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
  here = tuple(int(i) for i in config["layers_here"])
  first_dense = int(config["first_k_dense_replace"])
  return dict(
      d=int(config["hidden_size"]), f=int(config["intermediate_size"]),
      fe=int(config["moe_intermediate_size"]),
      heads=int(config["num_attention_heads"]),
      q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
      nope=int(config["qk_nope_head_dim"]),
      rope=int(config["qk_rope_head_dim"]), v=int(config["v_head_dim"]),
      experts=int(config["n_routed_experts"]),
      shared=int(config["n_shared_experts"]),
      top_k=int(config["num_experts_per_tok"]),
      renormalise=bool(config["norm_topk_prob"]),
      routed_scale=float(config["routed_scaling_factor"]),
      eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
      first=int(config["experts_held"][0]),
      held=int(config["experts_held"][1]), here=here,
      first_dense=first_dense, layers=int(config["num_hidden_layers"]),
      modules=int(config["num_nextn_predict_layers"]),
      # the feed-forward of every trunk layer that runs here: dense or not
      dense=tuple(i < first_dense for i in here),
      mtp_weight=float(config["assumed_sizes"]["mtp_loss_weight"]),
      vocab=int(config["vocab_here"]), length=int(config["seq_len"]),
      mean_doc=int(config["mean_document_length"]))


def layer_leaves(s: Dict[str, Any], dense: bool,
                 scales: Dict[str, float]) -> Dict[str, Any]:
  """name -> (shape, scale[, offset]) of one layer's dense leaves: latent
  attention, then the dense MLP or the experts with the shared one."""
  d, scale, h = s["d"], scales["matrix"], s["heads"]
  gain = lambda *shape: (shape, 0.0, 1.0)
  leaves = {
      "input_norm": gain(d), "w_dq": ((d, s["q_rank"]), scale),
      "q_a_norm": gain(s["q_rank"]),
      "w_uq": ((s["q_rank"], h * (s["nope"] + s["rope"])), scale),
      "w_dkv": ((d, s["kv_rank"] + s["rope"]), scale),
      "kv_a_norm": gain(s["kv_rank"]),
      "w_ukv": ((s["kv_rank"], h * (s["nope"] + s["v"])), scale),
      "w_o": ((h * s["v"], d), scale), "post_attention_norm": gain(d)}
  if dense:
    f = s["f"]
    return {**leaves, "w_gate": ((d, f), scale), "w_up": ((d, f), scale),
            "w_down": ((f, d), scale)}
  fe, held, fs = s["fe"], s["held"], s["shared"] * s["fe"]
  return {**leaves, "router": ((d, s["experts"]), scale),
          "expert_bias": ((s["experts"],), scales["bias"]),
          "w_gate": ((held, d, fe), scale), "w_up": ((held, d, fe), scale),
          "w_down": ((held, fe, d), scale),
          "shared_gate": ((d, fs), scale), "shared_up": ((d, fs), scale),
          "shared_down": ((fs, d), scale)}


def make_labels(rng, mix, config, cats):
  """Nothing is drawn: a position's targets are the next token and the one
  after it (the last positions' count for nothing)."""
  del rng, mix, config
  shifted = lambda n: np.concatenate(
      [cats[:, n:], np.zeros_like(cats[:, :n])], axis=1)
  return {"targets": shifted(1), "targets_2": shifted(2)}


def cross_entropy(jnp, logits, weight, targets):
  """Mean over the positions of weight 1 of ``CE(logits_t, targets_t)``."""
  top = jnp.max(logits, axis=-1, keepdims=True)
  lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
  picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * (lse - picked)) / jnp.maximum(jnp.sum(weight), 1.0)


def loss(jnp, outputs, labels, mtp_weight, second="targets_2"):
  """The next-token loss and ``mtp_weight`` times the prediction module's,
  whose targets are the tokens two ahead."""
  first = cross_entropy(jnp, outputs["logits"], outputs["weight"],
                        labels["targets"])
  if not mtp_weight:
    return first
  return first + mtp_weight * cross_entropy(
      jnp, outputs["mtp_logits"], outputs["mtp_weight"], labels[second])


def model_spec(config: Dict[str, Any]) -> reference.ModelSpec:
  if importlib.util.find_spec(
      "distributed_embeddings_tpu.models.glm_moe_lite") is None:
    # before the reference's minutes: a program without the model cannot
    # run the cell, and says so at once
    raise specs.SpecError(
        "family glm_moe_lite: this checkout's program has no "
        "distributed_embeddings_tpu/models/glm_moe_lite.py")
  in_blocks.install()
  s = sizes(config)
  if config["optimizer"]["name"] != "adam":
    raise specs.SpecError("the glm_moe_lite family trains with Adam")
  if s["modules"] != 1 or int(config["n_group"]) != 1:
    raise specs.SpecError("the glm_moe_lite family has one prediction "
                          "module and one group of experts")
  scales = {"matrix": float(config["init_scale"]),
            "bias": float(config["assumed_sizes"]["expert_bias_spread"])}
  gain = (s["d"],), 0.0, 1.0
  leaves = {"norm": gain, "head": ((s["d"], s["vocab"]), scales["matrix"]),
            "mtp_enorm": gain, "mtp_hnorm": gain, "mtp_norm": gain,
            "mtp_w_eh": ((2 * s["d"], s["d"]), scales["matrix"])}
  for name, leaf in layer_leaves(s, False, scales).items():
    leaves[f"mtp_layer_{name}"] = leaf
  for i, dense in enumerate(s["dense"]):
    for name, leaf in layer_leaves(s, dense, scales).items():
      leaves[f"layer_{i}_{name}"] = leaf
  return reference.ModelSpec(
      tables=(reference.TableSpec(s["vocab"], s["d"], scales["matrix"]),),
      inputs=(traffic.CatInput(0, s["vocab"], s["length"], sequence=True),),
      n_numerical=s["length"], dense_leaves=leaves,
      optimizer=dict(config["optimizer"]), summed_tables=frozenset({0}),
      loss=functools.partial(loss, mtp_weight=s["mtp_weight"]))


def reference_logits(config, dense, embs, numerical, *, rope_shared_key=True,
                     norm_kv_latent=True, scale_routed=True, counters=False):
  """The plain equations, in the dtype the arguments come in (float32; the
  bfloat16 control hands everything over rounded; the router's product is
  float32 at ``highest`` either way). ``rope_shared_key=False``,
  ``norm_kv_latent=False`` and ``scale_routed=False`` are
  :func:`reference_faults`' wrong forwards; ``counters`` adds, an expert
  layer (the module's last), the assignments on the held experts and the
  choices the bias moved, int32 ``[expert layers]`` each."""
  import jax
  import jax.numpy as jnp

  s = sizes(config)
  (rows,) = embs                                          # [B, L, d]
  dt = rows.dtype
  b, length, d = rows.shape
  heads, nope, dr, dv = s["heads"], s["nope"], s["rope"], s["v"]
  eps = jnp.asarray(s["eps"], dt)

  def rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain

  pos = jnp.arange(length)
  starts = (numerical < 1.0 / s["mean_doc"]) | (pos == 0)[None, :]
  # the first position of each position's document
  first = jax.lax.cummax(jnp.where(starts, pos[None, :], 0), axis=1)

  inv = 1.0 / s["theta"] ** (np.arange(0, dr, 2, dtype=np.float32) / dr)
  ang = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  cos, sin = (jnp.asarray(t, dt)[None, :, None, :]
              for t in (np.cos(ang), np.sin(ang)))

  def rotate(y):   # [B, L, heads, rope]
    y1, y2 = y[..., :dr // 2], y[..., dr // 2:]
    return y * cos + jnp.concatenate([-y2, y1], axis=-1) * sin

  q_block = min(QUERY_BLOCK, length)

  @jax.checkpoint
  def attend(q_blk, at, k, v):
    """``q_blk [B, q, H, nope + rope]`` from position ``at`` on, against
    every key: causal and inside the query's document."""
    q_pos = at + jnp.arange(q_block)
    q_first = jax.lax.dynamic_slice_in_dim(first, at, q_block, axis=1)
    allowed = (pos[None, None, :] <= q_pos[None, :, None]) \
        & (pos[None, None, :] >= q_first[:, :, None])          # [B, q, L]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) \
        * jnp.asarray((nope + dr) ** -0.5, dt)
    scores = jnp.where(allowed[:, None], scores.astype(jnp.float32),
                       -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, v)

  def attention(p, h):
    c_q = rms(h @ p["w_dq"], p["q_a_norm"])
    q = (c_q @ p["w_uq"]).reshape(b, length, heads, nope + dr)
    down = h @ p["w_dkv"]
    c_kv, k_r = down[..., :s["kv_rank"]], down[..., s["kv_rank"]:]
    if norm_kv_latent:
      c_kv = rms(c_kv, p["kv_a_norm"])
    kv = (c_kv @ p["w_ukv"]).reshape(b, length, heads, nope + dv)
    k_r = k_r[:, :, None, :]                      # one head, every head's
    if rope_shared_key:
      k_r = rotate(k_r)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, length, heads, dr))],
        axis=-1)
    v = kv[..., nope:]
    pad = -length % q_block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda xs: attend(xs[0], xs[1], k, v),
        (jnp.moveaxis(q.reshape(b, -1, q_block, heads, nope + dr), 1, 0),
         jnp.arange(0, length + pad, q_block)))
    a = jnp.moveaxis(out, 0, 1).reshape(b, length + pad, heads * dv)
    return a[:, :length] @ p["w_o"]

  def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

  @jax.checkpoint
  def expert(h, w, w_gate, w_up, w_down):
    return w[..., None] * swiglu(h, w_gate, w_up, w_down)

  def experts(p, h):
    with jax.default_matmul_precision("highest"):
      logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    score = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(score + p["expert_bias"].astype(jnp.float32),
                             s["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(top_e, s["experts"], dtype=jnp.float32),
                     axis=-2)                                 # [B, L, E] 0/1
    weight = score * chosen
    if s["renormalise"]:
      weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if scale_routed:
      weight = s["routed_scale"] * weight
    weight = weight.astype(dt)

    def one(y, xs):   # an expert this chip holds, over every token
      e, w_gate, w_up, w_down = xs
      return y + expert(h, jnp.take(weight, s["first"] + e, axis=-1),
                        w_gate, w_up, w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(s["held"]), p["w_gate"], p["w_up"],
                         p["w_down"]))
    y = y + swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    _, plain_e = jax.lax.top_k(score, s["top_k"])
    plain = jnp.sum(jax.nn.one_hot(plain_e, s["experts"], dtype=jnp.float32),
                    axis=-2)
    held = chosen[..., s["first"]:s["first"] + s["held"]]
    return y, (jnp.sum(held).astype(jnp.int32),
               jnp.sum(chosen * (1 - plain)).astype(jnp.int32))

  def layer(dense_mlp, p, x):
    x = x + attention(p, rms(x, p["input_norm"]))
    h = rms(x, p["post_attention_norm"])
    if dense_mlp:
      return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None
    y, counts = experts(p, h)
    return x + y, counts

  def leaves_of(tree, prefix):
    return {n[len(prefix):]: w for n, w in tree.items()
            if n.startswith(prefix)}

  def module(p, x, rows):
    """``z_i = [rms(e_{i+1}) ; rms(x_i)] W_eh``, then the module's layer."""
    following = jnp.concatenate([rows[:, 1:], jnp.zeros_like(rows[:, :1])],
                                axis=1)
    z = jnp.concatenate([rms(following, p["enorm"]), rms(x, p["hnorm"])],
                        axis=-1) @ p["w_eh"]
    return layer(False, leaves_of(p, "layer_"), z)

  x, counted = rows, []
  for i, dense_mlp in enumerate(s["dense"]):
    x, counts = jax.checkpoint(functools.partial(layer, dense_mlp))(
        leaves_of(dense, f"layer_{i}_"), x)
    if counts is not None:
      counted.append(counts)
  mtp = leaves_of(dense, "mtp_")
  z, counts = jax.checkpoint(module)(mtp, x, rows)
  counted.append(counts)
  goes_on = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  twice = goes_on & jnp.concatenate(
      [goes_on[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  out = {"logits": rms(x, dense["norm"]) @ dense["head"],
         "weight": goes_on.astype(dt),
         "mtp_logits": rms(z, mtp["norm"]) @ dense["head"],
         "mtp_weight": twice.astype(dt)}
  if counters:
    out["assignments"] = jnp.stack([a for a, _ in counted])
    out["moved"] = jnp.stack([m for _, m in counted])
  return out


def reference_faults(config: Dict[str, Any]):
  """Wrong steps for ``benchmark/control_sequential.py --stand_ins``, put in
  the reference's place at the cell's own size: name -> (``logits_fn``,
  ``loss``). ``no_key_rope``: RoPE left off the shared key ``k_r``;
  ``no_kv_norm``: ``kv_a_layernorm`` left out; ``no_scale``: the routed
  scaling factor dropped; ``mtp_shift_one``: the module held to the tokens
  one ahead and not two; ``no_mtp_loss``: the module's term left out (no
  gradient reaches a leaf of the module: each reads exactly 1)."""
  sound = functools.partial(reference_logits, config)
  weight = sizes(config)["mtp_weight"]
  whole = functools.partial(loss, mtp_weight=weight)
  return {
      "no_key_rope": (functools.partial(sound, rope_shared_key=False), whole),
      "no_kv_norm": (functools.partial(sound, norm_kv_latent=False), whole),
      "no_scale": (functools.partial(sound, scale_routed=False), whole),
      "mtp_shift_one": (sound, functools.partial(whole, second="targets")),
      "no_mtp_loss": (sound, functools.partial(loss, mtp_weight=0.0))}


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by the recipe of `models/glm_moe_lite.py`."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark import program
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models.glm_moe_lite import (
      GlmMoeLite,
      GlmMoeLiteConfig,
      mtp_training_loss,
  )
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  s, opt = sizes(config), config["optimizer"]
  # as `families/laguna.py`: the check's read-back gathers `READ_CHUNK`
  # physical rows at a time whatever their width; at this table's 6,144
  # lanes (2,048 and Adam's two moments) a chunk of at most 256 MiB
  row_bytes = 3 * s["d"] * 4
  program.READ_CHUNK = min(program.READ_CHUNK,
                           1 << ((1 << 28) // row_bytes).bit_length() - 1)
  cfg = GlmMoeLiteConfig(
      hidden_size=s["d"], intermediate_size=s["f"],
      moe_intermediate_size=s["fe"], num_attention_heads=s["heads"],
      q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
      qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
      v_head_dim=s["v"], n_routed_experts=s["experts"],
      n_shared_experts=s["shared"], num_experts_per_tok=s["top_k"],
      norm_topk_prob=s["renormalise"], routed_scaling_factor=s["routed_scale"],
      first_k_dense_replace=s["first_dense"], num_hidden_layers=s["layers"],
      num_nextn_predict_layers=s["modules"], rms_norm_eps=s["eps"],
      rope_theta=s["theta"], layers_here=s["here"], vocab_size=s["vocab"],
      experts_held=(s["first"], s["held"]), seq_len=s["length"],
      mean_document_length=s["mean_doc"],
      # the configuration names its attention path ("splash": the TPU's
      # kernel, so a run that finds no TPU fails instead of timing
      # something else); a toy copy for the CPU names "xla" itself
      attention=str(config["attention"]))
  model = GlmMoeLite(cfg)
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
      optimizer=optax.adam(lr, **kw),
      loss_fn=functools.partial(mtp_training_loss, weight=s["mtp_weight"]),
      dense_template=template, split_cats=lambda m: [m])
