"""Laguna on the training path: one chip's share of a decoder whose layers
differ in shape (window and full attention at 64 and 48 query heads, two
rotary tables, a gate on the attention output, a leading dense MLP, then a
sigmoid-routed mixture of experts beside a shared expert), over packed
documents (``configs/laguna-xs2-ep8share.json``).

What the harness fixes, and the way round each, is `families/olmo_hybrid.py`'s:
*where documents start* is the batch's numerical features (``seq_len``
uniforms a sample; position 0 starts a document and position ``i > 0`` one
where ``u_i < 1 / mean_document_length``); the forwards return ``{"logits",
"weight"}`` (``weight`` 1 where the next token continues the document);
``make_labels`` draws nothing, the targets are the ids shifted by one; the
loss is ``sum(weight CE) / sum(weight)``; the token table is one sequence
input under summed Adam; ``build_parts`` lowers ``program.READ_CHUNK`` so
that a read-back chunk of this table's 6,144-lane rows is at most 256 MiB;
``model_spec`` has `benchmark/in_blocks.py` hand the harness's whole-leaf host
functions a block of rows at a time (7.4e8 dense values).

Reference side: :func:`reference_logits` is the benchmark's own copy of the
equations (the configuration file's ``equations`` and ``assumed``). It
imports nothing of the program. Attention by full scores: a block of queries
at a time against EVERY key under the mask computed from positions (causal,
same document, and on a sliding layer ``i - j < sliding_window``; no tile is
skipped), keys and values repeated to the layer's query heads; the rotary
tables worked out here from ``rope_parameters``; the experts by a loop,
each held expert over every token in turn; the router's product at
``highest``. So that its ``jax.grad`` fits on the chip beside the weights and
their gradients, a layer, a block of queries and an expert are each under
``jax.checkpoint``. It prints, once a batch, the documents of the batch it is
given and the pairs each kind of layer's mask leaves (a line that starts with
``reference``). :func:`reference_faults` names three wrong forwards that
`benchmark/control_sequential.py` puts in the reference's place, to read what
the check's limits see of them at the cell's size.

Program side: the recipe of the program's own model (``models/laguna.py``):
plan -> ``Laguna`` -> ``adam_rule(summed=True)`` ->
``make_sparse_train_step``.
"""

from __future__ import annotations

import functools
import importlib.util
import math
from typing import Any, Dict

import numpy as np

from benchmark import in_blocks, reference, roofline_laguna, specs, traffic

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
QUERY_BLOCK = 128   # queries the reference attends at a time
_SAID = set()


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
  layers = int(config["num_hidden_layers_here"])
  return dict(
      d=int(config["hidden_size"]), f=int(config["intermediate_size"]),
      hkv=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
      fe=int(config["moe_intermediate_size"]),
      fs=int(config["shared_expert_intermediate_size"]),
      experts=int(config["num_experts"]),
      top_k=int(config["num_experts_per_tok"]),
      routed_scale=float(config["moe_routed_scaling_factor"]),
      first=int(config["experts_held"][0]),
      held=int(config["experts_held"][1]),
      window=int(config["sliding_window"]),
      eps=float(config["rms_norm_eps"]), layers=layers,
      kinds=tuple(config["layer_types"][:layers]),
      mlps=tuple(config["mlp_layer_types"][:layers]),
      heads=tuple(int(h) for h in
                  config["num_attention_heads_per_layer"][:layers]),
      rope=config["rope_parameters"], vocab=int(config["vocab_here"]),
      length=int(config["seq_len"]),
      mean_doc=int(config["mean_document_length"]))


def layer_leaves(s: Dict[str, Any], layer: int, scale: float
                 ) -> Dict[str, Any]:
  """name -> (shape, scale[, offset]) of layer ``layer``'s dense leaves:
  the attention's from its head count, the MLP's from its kind."""
  d, cq, ckv = s["d"], s["heads"][layer] * s["hd"], s["hkv"] * s["hd"]
  gain = lambda *shape: (shape, 0.0, 1.0)
  leaves = {"attn_norm": gain(d), "wq": ((d, cq), scale),
            "wk": ((d, ckv), scale), "wv": ((d, ckv), scale),
            "wg": ((d, cq), scale), "wo": ((cq, d), scale),
            "mlp_norm": gain(d)}
  if s["mlps"][layer] == DENSE:
    f = s["f"]
    return {**leaves, "w_gate": ((d, f), scale), "w_up": ((d, f), scale),
            "w_down": ((f, d), scale)}
  fe, fs, held = s["fe"], s["fs"], s["held"]
  return {**leaves, "router": ((d, s["experts"]), scale),
          "w_gate": ((held, d, fe), scale), "w_up": ((held, d, fe), scale),
          "w_down": ((held, fe, d), scale),
          "shared_gate": ((d, fs), scale), "shared_up": ((d, fs), scale),
          "shared_down": ((fs, d), scale)}


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
      "distributed_embeddings_tpu.models.laguna") is None:
    # before the reference's minutes: a program without the model cannot
    # run the cell, and says so at once
    raise specs.SpecError(
        "family laguna: this checkout's program has no "
        "distributed_embeddings_tpu/models/laguna.py")
  in_blocks.install()
  s = sizes(config)
  if config["optimizer"]["name"] != "adam":
    raise specs.SpecError("the laguna family trains with Adam")
  if set(s["kinds"]) - {SLIDING, FULL} or set(s["mlps"]) - {DENSE, SPARSE}:
    raise specs.SpecError(f"layer_types {s['kinds']}: {SLIDING} or {FULL}; "
                          f"mlp_layer_types {s['mlps']}: {DENSE} or {SPARSE}")
  scale = float(config["init_scale"])
  leaves = {"final_norm": ((s["d"],), 0.0, 1.0),
            "head": ((s["d"], s["vocab"]), scale)}
  for i in range(s["layers"]):
    for name, leaf in layer_leaves(s, i, scale).items():
      leaves[f"layer_{i}_{name}"] = leaf
  return reference.ModelSpec(
      tables=(reference.TableSpec(s["vocab"], s["d"], scale),),
      inputs=(traffic.CatInput(0, s["vocab"], s["length"], sequence=True),),
      n_numerical=s["length"], dense_leaves=leaves,
      optimizer=dict(config["optimizer"]), summed_tables=frozenset({0}),
      loss=loss)


def rotary(s: Dict[str, Any], kind: str):
  """-> (cos, sin) ``[L, rotated width]`` float32 of the layers of ``kind``
  from the published ``rope_parameters[kind]``: ``default`` is plain RoPE
  over ``partial_rotary_factor * head_dim`` dimensions; ``yarn`` divides the
  frequency of a dimension pair by ``factor`` where the pair makes under
  ``beta_slow`` turns in the original context, keeps it where it makes over
  ``beta_fast``, blends linearly between, and multiplies cos and sin by
  ``attention_factor``. Frequencies, positions and angles in float32, as the
  family's own code computes them."""
  p = s["rope"][kind]
  dim = int(s["hd"] * float(p.get("partial_rotary_factor", 1.0)))
  base = float(p["rope_theta"])
  inv = (1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
         ).astype(np.float32)
  scale = 1.0
  if p["rope_type"] == "yarn":
    original = float(p["original_max_position_embeddings"])
    pair_of = lambda turns: dim * math.log(
        original / (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(pair_of(float(p["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(p["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 0.001), 0, 1).astype(np.float32)
    inv = inv / np.float32(p["factor"]) * ramp + inv * (1 - ramp)
    scale = float(p["attention_factor"])
  ang = np.arange(s["length"], dtype=np.float32)[:, None] * inv[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  return (np.cos(ang) * np.float32(scale)).astype(np.float32), \
      (np.sin(ang) * np.float32(scale)).astype(np.float32)


def _say_documents(s, starts):
  """Once a batch: its documents, and the pairs each mask leaves."""
  starts = np.asarray(starts)
  key = starts.tobytes()
  if key in _SAID:
    return
  _SAID.add(key)
  per_seq = starts.sum(axis=1)
  lengths = np.concatenate([np.diff(np.append(np.flatnonzero(row), len(row)))
                            for row in starts])
  causal = int(np.sum(lengths * (lengths + 1) // 2))
  near = np.minimum(lengths, s["window"])
  local = int(np.sum(near * (near + 1) // 2 + (lengths - near) * s["window"]))
  expected = [starts.shape[0] * roofline_laguna.expected_pairs(
      s["length"], s["mean_doc"], w) for w in (None, s["window"])]
  print(f"reference batch: {starts.shape[0]} sequence(s) of {starts.shape[1]} "
        f"tokens, {int(per_seq.sum())} documents, lengths {lengths.min()}.."
        f"{lengths.max()}; pairs a full-attention layer keeps {causal} "
        f"(the mix's expectation {expected[0]:.0f}), a window of "
        f"{s['window']} {local} ({expected[1]:.0f}), in "
        f"{sum(k == FULL for k in s['kinds'])} and "
        f"{sum(k == SLIDING for k in s['kinds'])} of {len(s['kinds'])} "
        "layers", flush=True)


def document_starts(jnp, s, numerical):
  """``[B, L]`` bool: position 0, and where the feature is under
  ``1 / mean_document_length``."""
  return (numerical < 1.0 / s["mean_doc"]) \
      | (jnp.arange(numerical.shape[1]) == 0)[None, :]


def continues(jnp, starts):
  """The loss's weight: True where the next token is of the same document
  (False at a document's last token and at the sequence's)."""
  return jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)


def reference_logits(config, dense, embs, numerical, *, windowed=True,
                     shared=True):
  """The plain equations, in the dtype the arguments come in (float32; the
  bfloat16 control hands everything over rounded). ``windowed=False`` and
  ``shared=False`` are :func:`reference_faults`' wrong forwards."""
  import jax
  import jax.numpy as jnp

  s = sizes(config)
  (rows,) = embs                                          # [B, L, d]
  dt = rows.dtype
  b, length, _ = rows.shape
  hkv, hd = s["hkv"], s["hd"]
  eps = jnp.asarray(s["eps"], dt)

  def rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain

  pos = jnp.arange(length)
  starts = document_starts(jnp, s, numerical)
  jax.debug.callback(lambda x: _say_documents(s, x), starts)
  # the first position of each position's document
  first = jax.lax.cummax(jnp.where(starts, pos[None, :], 0), axis=1)
  tables = {kind: tuple(jnp.asarray(t, dt)[None, :, None, :]
                        for t in rotary(s, kind)) for kind in set(s["kinds"])}

  def rotate(y, kind):
    cos, sin = tables[kind]
    n = cos.shape[-1]
    y1, y2, kept = y[..., :n // 2], y[..., n // 2:n], y[..., n:]
    turned = y[..., :n] * cos + jnp.concatenate([-y2, y1], axis=-1) * sin
    return jnp.concatenate([turned, kept], axis=-1)

  q_block = min(QUERY_BLOCK, length)

  @functools.partial(jax.checkpoint, static_argnums=(0,))
  def attend(kind, q_blk, at, k, v):
    """``q_blk [B, q, H, hd]`` from position ``at`` on, against every key:
    causal, inside the query's document and, on a sliding layer, at most
    ``sliding_window - 1`` back."""
    q_pos = at + jnp.arange(q_block)
    q_first = jax.lax.dynamic_slice_in_dim(first, at, q_block, axis=1)
    allowed = (pos[None, None, :] <= q_pos[None, :, None]) \
        & (pos[None, None, :] >= q_first[:, :, None])          # [B, q, L]
    if kind == SLIDING and windowed:
      allowed = allowed & (q_pos[None, :, None] - pos[None, None, :]
                           < s["window"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) \
        * jnp.asarray(hd ** -0.5, dt)
    scores = jnp.where(allowed[:, None], scores.astype(jnp.float32),
                       -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, v)

  def attention(kind, heads, p, h):
    q = rotate((h @ p["wq"]).reshape(b, length, heads, hd), kind)
    k = rotate((h @ p["wk"]).reshape(b, length, hkv, hd), kind)
    v = (h @ p["wv"]).reshape(b, length, hkv, hd)
    k = jnp.repeat(k, heads // hkv, axis=2)  # query head n reads key-value
    v = jnp.repeat(v, heads // hkv, axis=2)  # head n // (heads / hkv)
    pad = -length % q_block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda xs: attend(kind, xs[0], xs[1], k, v),
        (jnp.moveaxis(q.reshape(b, -1, q_block, heads, hd), 1, 0),
         jnp.arange(0, length + pad, q_block)))
    a = jnp.moveaxis(out, 0, 1).reshape(b, length + pad, heads * hd)
    return (jax.nn.sigmoid(h @ p["wg"]) * a[:, :length]) @ p["wo"]

  @jax.checkpoint
  def expert(h, w, w_gate, w_up, w_down):
    y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
    return y if w is None else w[..., None] * y

  def sparse_mlp(p, h):
    with jax.default_matmul_precision("highest"):
      logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    score = jax.nn.sigmoid(logits)
    top_s, top_e = jax.lax.top_k(score, s["top_k"])
    top_w = (s["routed_scale"] * top_s
             / jnp.sum(top_s, axis=-1, keepdims=True)).astype(dt)
    y = expert(h, None, p["shared_gate"], p["shared_up"], p["shared_down"]) \
        if shared else jnp.zeros_like(h)

    def one(y, xs):   # an expert this chip holds, over every token
      e, w_gate, w_up, w_down = xs
      w = jnp.sum(jnp.where(top_e == s["first"] + e, top_w, 0), axis=-1)
      return y + expert(h, w, w_gate, w_up, w_down), None
    # one by one, as a loop the compiler keeps rolled (32 bodies a layer,
    # forward and backward, were two thirds of the reference's compile)
    y, _ = jax.lax.scan(one, y, (jnp.arange(s["held"]), p["w_gate"],
                                 p["w_up"], p["w_down"]))
    return y

  def layer(i, p, x):
    x = x + attention(s["kinds"][i], s["heads"][i], p,
                      rms(x, p["attn_norm"]))
    h = rms(x, p["mlp_norm"])
    if s["mlps"][i] == DENSE:
      return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) \
          @ p["w_down"]
    return x + sparse_mlp(p, h)

  x = rows
  for i in range(s["layers"]):
    prefix = f"layer_{i}_"
    p = {n[len(prefix):]: w for n, w in dense.items() if n.startswith(prefix)}
    x = jax.checkpoint(functools.partial(layer, i))(p, x)
  return {"logits": rms(x, dense["final_norm"]) @ dense["head"],
          "weight": continues(jnp, starts).astype(dt)}


def reference_faults(config: Dict[str, Any]):
  """Wrong forwards for ``benchmark/control_sequential.py --stand_ins``, put in
  the reference's place at the cell's own size: name -> (``logits_fn``,
  ``loss``). ``weight``: the loss's weight dropped (at this mix 1 position
  in 4,096 ends a document); ``no_window``: the sliding layers attend to
  their whole document; ``no_shared``: the shared expert left out."""
  sound = functools.partial(reference_logits, config)

  def every_position(jnp, outputs, labels):
    """A document's last token is asked for the next document's first."""
    return loss(jnp, dict(outputs, weight=jnp.ones_like(outputs["weight"])),
                labels)

  return {"weight": (sound, every_position),
          "no_window": (functools.partial(sound, windowed=False), loss),
          "no_shared": (functools.partial(sound, shared=False), loss)}


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by the recipe of `models/laguna.py`."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark import program
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models.laguna import (
      Laguna,
      LagunaConfig,
      freeze_rope_parameters,
  )
  from distributed_embeddings_tpu.models.olmo_hybrid import next_token_loss
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  s, opt = sizes(config), config["optimizer"]
  # as `families/olmo_hybrid.py`: the check's read-back gathers `READ_CHUNK`
  # physical rows at a time whatever their width; at this table's 6,144
  # lanes (2,048 and Adam's two moments) a chunk of at most 256 MiB
  row_bytes = 3 * s["d"] * 4
  program.READ_CHUNK = min(program.READ_CHUNK,
                           1 << ((1 << 28) // row_bytes).bit_length() - 1)
  cfg = LagunaConfig(
      hidden_size=s["d"], intermediate_size=s["f"],
      num_key_value_heads=s["hkv"], head_dim=s["hd"],
      moe_intermediate_size=s["fe"], shared_expert_intermediate_size=s["fs"],
      num_experts=s["experts"], num_experts_per_tok=s["top_k"],
      moe_routed_scaling_factor=s["routed_scale"],
      sliding_window=s["window"], rms_norm_eps=s["eps"],
      num_hidden_layers=s["layers"], layer_types=s["kinds"],
      mlp_layer_types=s["mlps"], num_attention_heads_per_layer=s["heads"],
      rope_parameters=freeze_rope_parameters(s["rope"]),
      vocab_size=s["vocab"], experts_held=(s["first"], s["held"]),
      seq_len=s["length"], mean_document_length=s["mean_doc"],
      # the configuration names its attention path ("splash": the TPU's
      # kernel, so a run that finds no TPU fails instead of timing
      # something else); a toy copy for the CPU names "xla" itself
      attention=str(config["attention"]))
  model = Laguna(cfg)
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
