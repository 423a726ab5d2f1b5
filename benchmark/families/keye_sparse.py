"""Keye-VL-2.0's language model on the training path: one chip's share of a
Qwen3-MoE decoder whose attention chooses its keys by a learned indexer
(DeepSeek-Sparse-Attention as the published ``sa_config`` sizes it), over
packed documents (``configs/keye-vl2-30b-a3b-ep8share.json``).

What the harness fixes, and the way round each, is `families/laguna.py`'s:
*where documents start* is the batch's numerical features (``seq_len``
uniforms a sample; position 0 starts a document and position ``i > 0`` one
where ``u_i < 1 / mean_document_length``); ``make_labels`` draws nothing, the
targets are the ids shifted by one; the token table is one sequence input
under summed Adam; ``build_parts`` lowers ``program.READ_CHUNK``;
``model_spec`` installs `benchmark/in_blocks.py` (4.7e8 dense values).

*A loss with two owners.* The forwards return ``{"logits", "weight",
"index_kl"}``: ``index_kl`` is the layers' indexer losses summed, each the
mean over positions of the KL from the main attention's probabilities (the
heads' mean, detached) to the indexer's softmax, both over the selected keys.
:func:`loss` is ``sum(weight CE) / sum(weight) + index_kl``. The indexer reads
its layer's input detached, so one ``value_and_grad`` gives the indexer's
five leaves a layer the KL's gradient alone and every other leaf the
language-model loss's alone.

Reference side: :func:`reference_logits` is the benchmark's own copy of the
equations (the configuration file's ``equations`` and ``assumed``). It
imports nothing of the program. A block of queries at a time against EVERY
key: the indexer's scores (float32, the product at ``highest``), the visible
pairs from positions (causal, same document), the selection by
``lax.top_k`` scattered into a mask, attention under it with keys and values
repeated to the query heads, the KL of that block; the experts by a loop
over the held ones. A layer, a block of queries and an expert are each under
``jax.checkpoint`` so that its ``jax.grad`` fits on the chip.
:func:`reference_faults` names four wrong forwards that
`benchmark/control_sequential.py` puts in the reference's place.
:func:`document_counts` counts, from a batch's documents alone, the pairs a
layer's selection keeps: what `tools/sparse_index_load.py` holds the
program's counters and the reference's mask against.

Program side: the recipe of the program's own model
(``models/keye_sparse.py``): plan -> ``KeyeSparse`` ->
``adam_rule(summed=True)`` -> ``make_sparse_train_step``.
"""

from __future__ import annotations

import functools
import importlib.util
from typing import Any, Dict

import numpy as np

from benchmark import in_blocks, reference, specs, traffic

QUERY_BLOCK = 128   # queries the reference attends at a time


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
  sa, assumed = config["sa_config"], config["assumed_sizes"]
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
      hi=int(sa["indexer_num_heads"]), di=int(sa["indexer_head_dim"]),
      hki=int(sa["indexer_num_kv_heads"]), select=int(sa["topk"]),
      q_chunk=int(sa["q_chunk_size"]),
      index_rotary=int(assumed["indexer_rotary_dim"]),
      kl_weight=float(assumed["index_loss_weight"]),
      length=int(config["seq_len"]),
      mean_doc=int(config["mean_document_length"]))


def layer_leaves(s: Dict[str, Any], scale: float) -> Dict[str, Any]:
  """name -> (shape, scale[, offset]) of a layer's dense leaves."""
  d, hq, hkv, hd, f, held = (s["d"], s["hq"], s["hkv"], s["hd"], s["f"],
                             s["held"])
  gain = lambda *shape: (shape, 0.0, 1.0)
  return {
      "attn_norm": gain(d), "wq": ((d, hq * hd), scale),
      "wk": ((d, hkv * hd), scale), "wv": ((d, hkv * hd), scale),
      "wo": ((hq * hd, d), scale), "q_norm": gain(hd), "k_norm": gain(hd),
      "index_wq": ((d, s["hi"] * s["di"]), scale),
      "index_wk": ((d, s["di"]), scale), "index_ww": ((d, s["hi"]), scale),
      "index_norm_gain": gain(s["di"]),
      "index_norm_bias": ((s["di"],), 0.0, 0.0),
      "moe_norm": gain(d), "router": ((d, s["experts"]), scale),
      "w_gate": ((held, d, f), scale), "w_up": ((held, d, f), scale),
      "w_down": ((held, f, d), scale)}


def make_labels(rng, mix, config, cats):
  """Nothing is drawn: a position's target is the next token (the last
  position's counts for nothing)."""
  del rng, mix, config
  return {"targets": np.concatenate(
      [cats[:, 1:], np.zeros_like(cats[:, :1])], axis=1)}


def next_token(jnp, outputs, labels):
  """Mean over the positions that are not a document's last of
  ``CE(logits_t, token_{t+1})``."""
  logits, weight = outputs["logits"], outputs["weight"]
  top = jnp.max(logits, axis=-1, keepdims=True)
  lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
  picked = jnp.take_along_axis(logits, labels["targets"][..., None],
                               axis=-1)[..., 0]
  return jnp.sum(weight * (lse - picked)) / jnp.maximum(jnp.sum(weight), 1.0)


def loss(jnp, outputs, labels):
  """The language-model loss and the indexers' KL, weight 1 on each."""
  return next_token(jnp, outputs, labels) + outputs["index_kl"]


def model_spec(config: Dict[str, Any]) -> reference.ModelSpec:
  if importlib.util.find_spec(
      "distributed_embeddings_tpu.models.keye_sparse") is None:
    # before the reference's minutes: a program without the model cannot
    # run the cell, and says so at once
    raise specs.SpecError(
        "family keye_sparse: this checkout's program has no "
        "distributed_embeddings_tpu/models/keye_sparse.py")
  in_blocks.install()
  s = sizes(config)
  if config["optimizer"]["name"] != "adam":
    raise specs.SpecError("the keye_sparse family trains with Adam")
  if s["hki"] != 1 or s["kl_weight"] != 1.0:
    raise specs.SpecError("the keye_sparse family has one shared index key "
                          "head and weight 1 on the indexer's loss")
  scale = float(config["init_scale"])
  leaves = {"final_norm": ((s["d"],), 0.0, 1.0),
            "head": ((s["d"], s["vocab"]), scale)}
  for i in range(s["layers"]):
    for name, leaf in layer_leaves(s, scale).items():
      leaves[f"layer_{i}_{name}"] = leaf
  return reference.ModelSpec(
      tables=(reference.TableSpec(s["vocab"], s["d"], scale),),
      inputs=(traffic.CatInput(0, s["vocab"], s["length"], sequence=True),),
      n_numerical=s["length"], dense_leaves=leaves,
      optimizer=dict(config["optimizer"]), summed_tables=frozenset({0}),
      loss=loss)


def document_counts(config: Dict[str, Any], numerical) -> Dict[str, int]:
  """From a batch's numerical features alone, a layer's ``visible_pairs``
  (causal, same document), ``selected_pairs`` (a query keeps ``topk`` of
  its visible keys, all where it has no more) and ``active_queries`` (those
  with more than ``topk``), summed over the batch."""
  s = sizes(config)
  starts = np.asarray(numerical) < 1.0 / s["mean_doc"]
  starts[:, 0] = True
  at = np.arange(starts.shape[1])
  first = np.maximum.accumulate(np.where(starts, at[None, :], 0), axis=1)
  seen = at[None, :] - first + 1
  return {"visible_pairs": int(seen.sum()),
          "selected_pairs": int(np.minimum(seen, s["select"]).sum()),
          "active_queries": int((seen > s["select"]).sum())}


def reference_logits(config, dense, embs, numerical, *, select=True,
                     topk=None, detach_input=True, with_kl=True,
                     counters=False):
  """The plain equations, in the dtype the arguments come in (float32; the
  bfloat16 control hands everything over rounded; the indexer's score is
  float32 at ``highest`` either way, as the router's logits are).
  ``select=False``, ``topk``, ``detach_input=False`` and ``with_kl=False``
  are :func:`reference_faults`' wrong forwards; ``counters`` adds the pairs
  each layer's mask keeps, int32 ``[layers]``."""
  import jax
  import jax.numpy as jnp

  s = sizes(config)
  (rows,) = embs                                          # [B, L, d]
  dt = rows.dtype
  b, length, _ = rows.shape
  hq, hkv, hd, hi, di = s["hq"], s["hkv"], s["hd"], s["hi"], s["di"]
  keep = min(s["select"] if topk is None else int(topk), length)
  eps = jnp.asarray(s["eps"], dt)

  def rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain

  def layer_norm(x, gain, bias):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) \
        * gain + bias

  def table(width):   # plain RoPE over `width` leading dimensions of a head
    inv = 1.0 / s["theta"] ** (np.arange(0, width, 2, dtype=np.float32)
                               / width)
    ang = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return tuple(jnp.asarray(t, dt)[None, :, None, :]
                 for t in (np.cos(ang), np.sin(ang)))

  def rotate(y, cos_sin):
    cos, sin = cos_sin
    n = cos.shape[-1]
    y1, y2, kept = y[..., :n // 2], y[..., n // 2:n], y[..., n:]
    turned = y[..., :n] * cos + jnp.concatenate([-y2, y1], axis=-1) * sin
    return jnp.concatenate([turned, kept], axis=-1)

  whole_head, index_part = table(hd), table(s["index_rotary"])
  pos = jnp.arange(length)
  starts = (numerical < 1.0 / s["mean_doc"]) | (pos == 0)[None, :]
  # the first position of each position's document
  first = jax.lax.cummax(jnp.where(starts, pos[None, :], 0), axis=1)
  q_block = min(QUERY_BLOCK, length)

  @jax.checkpoint
  def attend(q_blk, qi_blk, w_blk, at, k, v, ki):
    """``q_blk [B, q, H, hd]``, the indexer's ``qi_blk [B, q, Hi, di]`` and
    ``w_blk [B, q, Hi]`` from position ``at`` on, against every key -> (the
    attention's output, the block's KLs summed, the pairs selected)."""
    q_pos = at + jnp.arange(q_block)
    q_first = jax.lax.dynamic_slice_in_dim(first, at, q_block, axis=1)
    visible = (pos[None, None, :] <= q_pos[None, :, None]) \
        & (pos[None, None, :] >= q_first[:, :, None])           # [B, q, L]
    with jax.default_matmul_precision("highest"):
      products = jnp.einsum("bqhd,bkd->bqhk", qi_blk.astype(jnp.float32),
                            ki.astype(jnp.float32))
    index = jnp.einsum("bqh,bqhk->bqk", w_blk.astype(jnp.float32),
                       jax.nn.relu(products))
    chosen = visible
    if select and keep < length:
      _, best = jax.lax.top_k(jnp.where(visible, index, -jnp.inf), keep)
      bi, qi_ = np.ogrid[:b, :q_block]
      chosen = jnp.zeros(visible.shape, bool).at[
          bi[..., None], qi_[..., None], best].set(True) & visible
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) \
        * jnp.asarray(hd ** -0.5, dt)
    scores = jnp.where(chosen[:, None], scores.astype(jnp.float32), -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", prob.astype(dt), v)
    target = jax.lax.stop_gradient(jnp.mean(prob, axis=1))      # [B, q, L]
    log_index = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf),
                                   axis=-1)
    live = chosen & (target > 0)
    kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                   - jnp.where(live, log_index, 0.0)), 0.0)
    return out, jnp.sum(kl), jnp.sum(chosen, dtype=jnp.int32)

  def attention(p, h):
    q = rotate(rms((h @ p["wq"]).reshape(b, length, hq, hd), p["q_norm"]),
               whole_head)
    k = rotate(rms((h @ p["wk"]).reshape(b, length, hkv, hd), p["k_norm"]),
               whole_head)
    v = (h @ p["wv"]).reshape(b, length, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)  # query head n reads key-value
    v = jnp.repeat(v, hq // hkv, axis=2)  # head n // (hq / hkv)
    hd_in = jax.lax.stop_gradient(h) if detach_input else h
    qi = rotate((hd_in @ p["index_wq"]).reshape(b, length, hi, di),
                index_part)
    ki = layer_norm(hd_in @ p["index_wk"], p["index_norm_gain"],
                    p["index_norm_bias"])
    ki = rotate(ki[:, :, None, :], index_part)[:, :, 0, :]
    w = (hd_in @ p["index_ww"]) * jnp.asarray(hi ** -0.5 * di ** -0.5, dt)
    pad = -length % q_block
    blocks = lambda y: jnp.moveaxis(
        jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2)).reshape(
            (b, -1, q_block) + y.shape[2:]), 1, 0)
    out, kl, kept = jax.lax.map(
        lambda xs: attend(xs[0], xs[1], xs[2], xs[3], k, v, ki),
        (blocks(q), blocks(qi), blocks(w),
         jnp.arange(0, length + pad, q_block)))
    a = jnp.moveaxis(out, 0, 1).reshape(b, length + pad, hq * hd)
    return a[:, :length] @ p["wo"], jnp.sum(kl) / (b * length), jnp.sum(kept)

  @jax.checkpoint
  def expert(h, w, w_gate, w_up, w_down):
    return w[..., None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

  def moe(p, h):
    with jax.default_matmul_precision("highest"):
      logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), s["top_k"])
    top_w = (top_p / jnp.sum(top_p, axis=-1, keepdims=True)).astype(dt)

    def one(y, xs):   # an expert this chip holds, over every token
      e, w_gate, w_up, w_down = xs
      w = jnp.sum(jnp.where(top_e == s["first"] + e, top_w, 0), axis=-1)
      return y + expert(h, w, w_gate, w_up, w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(s["held"]), p["w_gate"], p["w_up"],
                         p["w_down"]))
    return y

  @jax.checkpoint
  def layer(p, x):
    o, kl, kept = attention(p, rms(x, p["attn_norm"]))
    x = x + o
    return x + moe(p, rms(x, p["moe_norm"])), kl, kept

  x, index_kl, kept = rows, jnp.zeros((), jnp.float32), []
  for i in range(s["layers"]):
    prefix = f"layer_{i}_"
    p = {n[len(prefix):]: w for n, w in dense.items() if n.startswith(prefix)}
    x, kl, n = layer(p, x)
    index_kl = index_kl + kl
    kept.append(n)
  weight = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  out = {"logits": rms(x, dense["final_norm"]) @ dense["head"],
         "weight": weight.astype(dt),
         "index_kl": index_kl if with_kl else jnp.zeros((), jnp.float32)}
  if counters:
    out["selected_pairs"] = jnp.stack(kept)
  return out


def reference_faults(config: Dict[str, Any]):
  """Wrong forwards for ``benchmark/control_sequential.py --stand_ins``, put in
  the reference's place at the cell's own size: name -> (``logits_fn``,
  ``loss``). ``no_selection``: dense causal attention inside a document in
  the selection's place (the KL then over every visible key); ``topk_half``:
  half the published ``topk``; ``no_kl``: the indexers' loss left out, so the
  indexers' leaves never move; ``input_attached``: the ``stop_gradient`` on
  the indexer's input left out, so the KL reaches every leaf below it."""
  sound = functools.partial(reference_logits, config)
  half = int(config["sa_config"]["topk"]) // 2
  return {"no_selection": (functools.partial(sound, select=False), loss),
          "topk_half": (functools.partial(sound, topk=half), loss),
          "no_kl": (functools.partial(sound, with_kl=False), loss),
          "input_attached": (functools.partial(sound, detach_input=False),
                             loss)}


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by the recipe of `models/keye_sparse.py`."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark import program
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models.keye_sparse import (
      KeyeSparse,
      KeyeSparseConfig,
      sparse_training_loss,
  )
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  s, opt = sizes(config), config["optimizer"]
  # as `families/laguna.py`: the check's read-back gathers `READ_CHUNK`
  # physical rows at a time whatever their width; at this table's 6,144
  # lanes (2,048 and Adam's two moments) a chunk of at most 256 MiB
  row_bytes = 3 * s["d"] * 4
  program.READ_CHUNK = min(program.READ_CHUNK,
                           1 << ((1 << 28) // row_bytes).bit_length() - 1)
  cfg = KeyeSparseConfig(
      hidden_size=s["d"], num_attention_heads=s["hq"],
      num_key_value_heads=s["hkv"], head_dim=s["hd"],
      moe_intermediate_size=s["f"], num_experts=s["experts"],
      num_experts_per_tok=s["top_k"], rms_norm_eps=s["eps"],
      rope_theta=s["theta"], num_hidden_layers=s["layers"],
      vocab_size=s["vocab"], experts_held=(s["first"], s["held"]),
      indexer_num_heads=s["hi"], indexer_head_dim=s["di"],
      indexer_num_kv_heads=s["hki"], topk=s["select"],
      q_chunk_size=s["q_chunk"], indexer_rotary_dim=s["index_rotary"],
      seq_len=s["length"], mean_document_length=s["mean_doc"])
  model = KeyeSparse(cfg)
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
      optimizer=optax.adam(lr, **kw), loss_fn=sparse_training_loss,
      dense_template=template, split_cats=lambda m: [m])
