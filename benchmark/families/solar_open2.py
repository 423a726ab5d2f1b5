"""Solar-Open2-250B on the training path: one chip's share of a decoder whose
mixers are three Kimi-Delta-Attention layers (a delta rule whose decay is PER
KEY CHANNEL) to one gated grouped-query attention layer without positions,
every layer over 320 sigmoid-routed experts chosen under a selection bias
beside a shared one, over packed documents
(``configs/solar-open2-250b-ep40tp8share.json``).

What the harness fixes, and the way round each, is `families/olmo_hybrid.py`'s
and `families/glm_moe_lite.py`'s: *where documents start* is the batch's
numerical features (``seq_len`` uniforms a sample; position 0 starts a
document and position ``i > 0`` one where ``u_i < 1 / mean_document_length``);
the forwards return ``{"logits", "weight"}`` and ``make_labels`` draws
nothing (the targets are the ids shifted by one); the token table is one
sequence input under summed Adam; the harness's leaves start at ``offset +
uniform(+-scale)``: ``A_log`` and ``dt_bias`` take an offset each, so that a
seeded channel's decay is neither 0 nor 1 (the configuration's ``assumed``);
``build_parts`` lowers ``program.READ_CHUNK`` (rows of 4,096 floats and
Adam's two moments are 12,288 lanes, the widest yet: a chunk of at most 256
MiB is 4,096 rows); ``model_spec`` installs `benchmark/in_blocks.py` (7.4e8
dense values).

*A leaf no gradient reaches.* ``expert_bias`` enters the choice of experts
and nothing differentiable: `families/lfm2_moe.py`'s note holds here.

Reference side: :func:`reference_logits` is the benchmark's own copy of the
equations (the configuration file's ``equations`` and ``assumed``). It
imports nothing of the program. The delta rule runs ONE TOKEN AT A TIME, the
definition itself (``lax.scan`` over ``t``, ``S' = Diag(exp(g_t)) S``,
products as multiply-and-sum in the arguments' dtype: no chunks, no
sub-blocks, no matmul precision to choose). So that its ``jax.grad`` fits on
the chip beside the weights and their gradients it is computed a layer at a
time under ``jax.checkpoint``, the scan in blocks of tokens under
``jax.checkpoint`` (the state is kept once a block, not once a token),
attention a block of queries at a time against every key under the
causal-and-document mask with the key-value head repeated to its query
heads, the experts by a loop, each held expert over every token.
:func:`reference_faults` names four wrong forwards that
`benchmark/control_sequential.py` puts in the reference's place.

Program side: the recipe of the program's own model
(``models/solar_open2.py``): plan -> ``SolarOpen2`` ->
``adam_rule(summed=True)`` -> ``make_sparse_train_step``.
"""

from __future__ import annotations

import functools
import importlib.util
from typing import Any, Dict

import numpy as np

from benchmark import in_blocks, reference, specs, traffic

KDA, GQA = "kda", "gqa"
QUERY_BLOCK = 256   # queries the reference attends at a time
TOKEN_BLOCK = 64    # tokens of the recurrence between two kept states
# A_log = 1 +- 1 (A in e^0 .. e^2, inside fla's uniform (0, 16)); dt_bias =
# -4.6 +- 2.3 (softplus of it in 0.001 .. 0.1, fla's range for dt); the
# convolution's taps +-0.5 (PyTorch's Conv1d default at 4 taps)
A_LOG, DT_BIAS, CONV_SCALE = (1.0, 1.0), (2.3, -4.6), 0.5
_SAID = set()


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
  here = tuple(int(i) for i in config["layers_here"])
  gqa = set(int(i) for i in config["gqa_layers"])
  linear = config["linear_attn_config"]
  return dict(
      d=int(config["hidden_size"]), fe=int(config["moe_intermediate_size"]),
      heads=int(config["num_attention_heads"]),
      kv_heads=int(config["num_key_value_heads"]),
      hd=int(config["head_dim"]), lin_heads=int(linear["num_heads"]),
      lin_hd=int(linear["head_dim"]),
      taps=int(linear["short_conv_kernel_size"]),
      neg_eig=bool(config["kda_allow_neg_eigval"]),
      full_proj=bool(config["kda_use_full_proj"]),
      use_rope=bool(config["use_rope"]), gate=bool(config["use_gqa_gate"]),
      theta=float(config["rope_theta"]),
      experts=int(config["n_routed_experts"]),
      shared=int(config["n_shared_experts"]),
      top_k=int(config["num_experts_per_tok"]),
      renormalise=bool(config["norm_topk_prob"]),
      routed_scale=float(config["routed_scaling_factor"]),
      first_dense=int(config["first_k_dense_replace"]),
      layers=int(config["num_hidden_layers"]),
      eps=float(config["rms_norm_eps"]), here=here, gqa_layers=tuple(sorted(gqa)),
      kinds=tuple(GQA if i in gqa else KDA for i in here),
      h_first=int(config["heads_held"][0]), h=int(config["heads_held"][1]),
      e_first=int(config["experts_held"][0]),
      held=int(config["experts_held"][1]),
      vocab=int(config["vocab_here"]), length=int(config["seq_len"]),
      mean_doc=int(config["mean_document_length"]),
      chunk=int(config["chunk"]))


def layer_leaves(s: Dict[str, Any], kind: str,
                 scales: Dict[str, float]) -> Dict[str, Any]:
  """name -> (shape, scale[, offset]) of one layer's dense leaves: the
  mixer's for the heads held, then the experts held with the shared one."""
  d, scale, h = s["d"], scales["matrix"], s["h"]
  gain = lambda *shape: (shape, 0.0, 1.0)
  fe, held, fs = s["fe"], s["held"], s["shared"] * s["fe"]
  experts = {
      "post_attention_norm": gain(d), "router": ((d, s["experts"]), scale),
      "expert_bias": ((s["experts"],), scales["bias"]),
      "w_gate": ((held, d, fe), scale), "w_up": ((held, d, fe), scale),
      "w_down": ((held, fe, d), scale),
      "shared_gate": ((d, fs), scale), "shared_up": ((d, fs), scale),
      "shared_down": ((fs, d), scale)}
  if kind == GQA:
    group = s["heads"] // s["kv_heads"]
    cq, ckv = h * s["hd"], h // group * s["hd"]
    return {"input_norm": gain(d), "wq": ((d, cq), scale),
            "wk": ((d, ckv), scale), "wv": ((d, ckv), scale),
            "wg": ((d, cq), scale), "wo": ((cq, d), scale), **experts}
  hd, taps = s["lin_hd"], s["taps"]
  c = h * hd
  return {"input_norm": gain(d), "wq": ((d, c), scale),
          "wk": ((d, c), scale), "wv": ((d, c), scale),
          "conv_q": ((taps, c), CONV_SCALE), "conv_k": ((taps, c), CONV_SCALE),
          "conv_v": ((taps, c), CONV_SCALE),
          "w_fa": ((d, hd), scale), "w_fb": ((hd, c), scale),
          "a_log": ((h,), *A_LOG), "dt_bias": ((c,), *DT_BIAS),
          "wb": ((d, h), scale),
          "w_ga": ((d, hd), scale), "w_gb": ((hd, c), scale),
          "b_g": ((c,), scale), "o_norm": gain(hd),
          "wo": ((c, d), scale), **experts}


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
      "distributed_embeddings_tpu.models.solar_open2") is None:
    # before the reference's minutes: a program without the model cannot
    # run the cell, and says so at once
    raise specs.SpecError(
        "family solar_open2: this checkout's program has no "
        "distributed_embeddings_tpu/models/solar_open2.py")
  in_blocks.install()
  s = sizes(config)
  if config["optimizer"]["name"] != "adam":
    raise specs.SpecError("the solar_open2 family trains with Adam")
  if s["full_proj"] or s["use_rope"] or not s["gate"] or s["first_dense"]:
    raise specs.SpecError(
        "the solar_open2 family is written for kda_use_full_proj false, "
        "use_rope false, use_gqa_gate true and first_k_dense_replace 0")
  if s["heads"] != s["lin_heads"] or s["h"] % (s["heads"] // s["kv_heads"]):
    raise specs.SpecError(
        f"heads_held {config['heads_held']}: one range of both mixers' "
        "heads, in whole groups of query heads a key-value head")
  scales = {"matrix": float(config["init_scale"]),
            "bias": float(config["assumed_sizes"]["expert_bias_spread"])}
  leaves = {"norm": ((s["d"],), 0.0, 1.0),
            "head": ((s["d"], s["vocab"]), scales["matrix"])}
  for i, kind in enumerate(s["kinds"]):
    for name, leaf in layer_leaves(s, kind, scales).items():
      leaves[f"layer_{i}_{name}"] = leaf
  return reference.ModelSpec(
      tables=(reference.TableSpec(s["vocab"], s["d"], scales["matrix"]),),
      inputs=(traffic.CatInput(0, s["vocab"], s["length"], sequence=True),),
      n_numerical=s["length"], dense_leaves=leaves,
      optimizer=dict(config["optimizer"]), summed_tables=frozenset({0}),
      loss=loss)


def _say_documents(s, starts):
  """Once a batch: its documents, and the rule's chunks a layer."""
  starts = np.asarray(starts)
  key = starts.tobytes()
  if key in _SAID:
    return
  _SAID.add(key)
  per_seq = starts.sum(axis=1)
  lengths = np.concatenate([np.diff(np.append(np.flatnonzero(row), len(row)))
                            for row in starts])
  print(f"reference batch: {starts.shape[0]} sequence(s) of {starts.shape[1]} "
        f"tokens, {int(per_seq.sum())} documents ({int(per_seq.sum()) - len(per_seq)} "
        f"resets after position 0), lengths {lengths.min()}..{lengths.max()} "
        f"median {int(np.median(lengths))}; the program's rule runs "
        f"{-(-s['length'] // s['chunk'])} chunks of {s['chunk']} tokens a layer "
        f"in {sum(k == KDA for k in s['kinds'])} of {len(s['kinds'])} "
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


def reference_logits(config, dense, embs, numerical, *, rule_dtype=None,
                     gate=True, scalar_decay=False, rope=False,
                     counters=False):
  """The plain equations, in the dtype the arguments come in (float32; the
  bfloat16 control hands everything over rounded; the router's product is
  float32 at ``highest`` either way). ``rule_dtype`` (the recurrence in a
  lower precision), ``gate=False`` (the attention layer's gate dropped),
  ``scalar_decay`` (one decay a head, the channels' mean) and ``rope`` (a
  rotary pass on the attention layer) are :func:`reference_faults`' wrong
  forwards; ``counters`` adds, a layer, the assignments on the held
  experts, int32 ``[layers]``."""
  import jax
  import jax.numpy as jnp

  s = sizes(config)
  (rows,) = embs                                          # [B, L, d]
  dt = rows.dtype
  b, length, _ = rows.shape
  h, hd, lhd, taps = s["h"], s["hd"], s["lin_hd"], s["taps"]
  group = s["heads"] // s["kv_heads"]
  eps = jnp.asarray(s["eps"], dt)

  def rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain

  def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + jnp.asarray(1e-6, dt))

  pos = jnp.arange(length)
  starts = document_starts(jnp, s, numerical)
  jax.debug.callback(lambda x: _say_documents(s, x), starts)
  # the first position of each position's document
  first = jax.lax.cummax(jnp.where(starts, pos[None, :], 0), axis=1)

  def conv(x, w):
    """``y_t = sum_j w_j x_{t-(taps-1)+j}``, taps before the document's
    first token read 0; then SiLU."""
    y = jnp.zeros_like(x)
    for j in range(taps):
      back = taps - 1 - j
      tap = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :length]
      inside = (pos[None, :] - back >= first)[..., None]
      y = y + jnp.where(inside, tap, jnp.zeros((), dt)) * w[j]
    return jax.nn.silu(y)

  def token(state, x):
    """One token of the rule: decay a key channel (or reset), delta write,
    read."""
    q_t, k_t, v_t, a_t, b_t, new = x     # [B, H, dk|dv], [B, H, dk], [B, H], [B]
    state = jnp.where(new[:, None, None, None], jnp.zeros((), state.dtype),
                      a_t[..., None] * state)
    err = v_t - jnp.sum(state * k_t[..., None], axis=-2)
    state = state + (b_t[..., None] * k_t)[..., None] * err[..., None, :]
    return state, jnp.sum(state * q_t[..., None], axis=-2)

  @jax.checkpoint
  def tokens(state, xs):
    return jax.lax.scan(token, state, xs)

  def kda_rule(q, k, v, alpha, beta):
    """``[B, L, H, .]`` -> ``o [B, L, H, dv]``, a token at a time; only a
    block's first state is kept for the backward pass."""
    pad = -length % TOKEN_BLOCK
    def blocks(x):  # [B, L, ...] -> [L / T, T, B, ...]; padding after the end
      x = jnp.pad(jnp.moveaxis(x, 1, 0), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
      return x.reshape((-1, TOKEN_BLOCK) + x.shape[1:])
    _, o = jax.lax.scan(
        tokens, jnp.zeros((b, h, lhd, lhd), q.dtype),
        tuple(blocks(x) for x in (q, k, v, alpha, beta, starts)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:length], 0, 1)

  def kda_mixer(p, u):
    heads = lambda x: x.reshape(b, length, h, lhd)
    q = heads(conv(u @ p["wq"], p["conv_q"]))
    k = heads(conv(u @ p["wk"], p["conv_k"]))
    v = heads(conv(u @ p["wv"], p["conv_v"]))
    g = -jnp.exp(p["a_log"])[:, None] * heads(jax.nn.softplus(
        (u @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]))
    if scalar_decay:   # WRONG: one decay a head
      g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(u @ p["wb"])
    if s["neg_eig"]:
      beta = beta * jnp.asarray(2.0, dt)
    operands = (l2(q) * jnp.asarray(lhd ** -0.5, dt), l2(k), v, jnp.exp(g),
                beta)
    if rule_dtype is not None:   # WRONG: the recurrence in a lower precision
      operands = tuple(x.astype(rule_dtype) for x in operands)
    o = kda_rule(*operands).astype(dt)
    gate_ = jax.nn.sigmoid(heads((u @ p["w_ga"]) @ p["w_gb"] + p["b_g"]))
    o = rms(o, p["o_norm"]) * gate_
    return o.reshape(b, length, h * lhd) @ p["wo"]

  inv = 1.0 / s["theta"] ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
  ang = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  cos, sin = (jnp.asarray(t, dt)[None, :, None, :]
              for t in (np.cos(ang), np.sin(ang)))

  def rotate(y):   # [B, L, heads, hd]
    y1, y2 = y[..., :hd // 2], y[..., hd // 2:]
    return y * cos + jnp.concatenate([-y2, y1], axis=-1) * sin

  q_block = min(QUERY_BLOCK, length)

  @jax.checkpoint
  def attend(q_blk, at, k, v):
    """``q_blk [B, q, H, hd]`` from position ``at`` on, against every key,
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

  def gqa_mixer(p, u):
    q = (u @ p["wq"]).reshape(b, length, h, hd)
    k = (u @ p["wk"]).reshape(b, length, h // group, hd)
    v = (u @ p["wv"]).reshape(b, length, h // group, hd)
    if rope:   # WRONG: use_rope is false
      q, k = rotate(q), rotate(k)
    # query head i reads key-value head i // group
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    pad = -length % q_block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda xs: attend(xs[0], xs[1], k, v),
        (jnp.moveaxis(q.reshape(b, -1, q_block, h, hd), 1, 0),
         jnp.arange(0, length + pad, q_block)))
    o = jnp.moveaxis(out, 0, 1).reshape(b, length + pad, h * hd)[:, :length]
    if gate:   # dropped: WRONG, use_gqa_gate is true
      o = jax.nn.sigmoid(u @ p["wg"]) * o
    return o @ p["wo"]

  def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down

  @jax.checkpoint
  def expert(x, w, w_gate, w_up, w_down):
    return w[..., None] * swiglu(x, w_gate, w_up, w_down)

  def experts(p, x):
    with jax.default_matmul_precision("highest"):
      logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    score = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(score + p["expert_bias"].astype(jnp.float32),
                             s["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(top_e, s["experts"], dtype=jnp.float32),
                     axis=-2)                                 # [B, L, E] 0/1
    weight = score * chosen
    if s["renormalise"]:
      weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = (s["routed_scale"] * weight).astype(dt)

    def one(y, xs):   # an expert this chip holds, over every token
      e, w_gate, w_up, w_down = xs
      return y + expert(x, jnp.take(weight, s["e_first"] + e, axis=-1),
                        w_gate, w_up, w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(s["held"]), p["w_gate"], p["w_up"],
                         p["w_down"]))
    y = y + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    held = chosen[..., s["e_first"]:s["e_first"] + s["held"]]
    return y, jnp.sum(held).astype(jnp.int32)

  def layer(kind, p, x):
    mixer = kda_mixer if kind == KDA else gqa_mixer
    x = x + mixer(p, rms(x, p["input_norm"]))
    y, count = experts(p, rms(x, p["post_attention_norm"]))
    return x + y, count

  x, counted = rows, []
  for i, kind in enumerate(s["kinds"]):
    prefix = f"layer_{i}_"
    p = {n[len(prefix):]: w for n, w in dense.items() if n.startswith(prefix)}
    x, count = jax.checkpoint(functools.partial(layer, kind))(p, x)
    counted.append(count)
  out = {"logits": rms(x, dense["norm"]) @ dense["head"],
         "weight": continues(jnp, starts).astype(dt)}
  if counters:
    out["assignments"] = jnp.stack(counted)
  return out


def reference_faults(config: Dict[str, Any]):
  """Wrong forwards for ``benchmark/control_sequential.py --stand_ins``, put in
  the reference's place at the cell's own size: name -> (``logits_fn``,
  ``loss``). ``bf16_rule``: the one-token recurrence (state, decays,
  products) in bfloat16, everything round it float32; ``no_gate``: the
  attention layer's ``sigmoid(u W_g)`` dropped; ``scalar_decay``: one decay a
  head (the mean of its 128 channels' log-decays) in place of one a channel;
  ``rope``: a rotary pass (theta ``rope_theta``) on the attention layer's
  ``q`` and ``k``."""
  import jax.numpy as jnp

  sound = functools.partial(reference_logits, config)
  return {
      "bf16_rule": (functools.partial(sound, rule_dtype=jnp.bfloat16), loss),
      "no_gate": (functools.partial(sound, gate=False), loss),
      "scalar_decay": (functools.partial(sound, scalar_decay=True), loss),
      "rope": (functools.partial(sound, rope=True), loss)}


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by the recipe of `models/solar_open2.py`."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark import program
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models.solar_open2 import (
      SolarOpen2,
      SolarOpen2Config,
      next_token_loss,
  )
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  s, opt = sizes(config), config["optimizer"]
  # as `families/olmo_hybrid.py`: the check's read-back gathers `READ_CHUNK`
  # physical rows at a time whatever their width; at this table's 12,288
  # lanes (4,096 and Adam's two moments) a chunk of at most 256 MiB
  row_bytes = 3 * s["d"] * 4
  program.READ_CHUNK = min(program.READ_CHUNK,
                           1 << ((1 << 28) // row_bytes).bit_length() - 1)
  cfg = SolarOpen2Config(
      hidden_size=s["d"], moe_intermediate_size=s["fe"],
      num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
      head_dim=s["hd"], linear_num_heads=s["lin_heads"],
      linear_head_dim=s["lin_hd"], short_conv_kernel_size=s["taps"],
      kda_use_full_proj=s["full_proj"], kda_allow_neg_eigval=s["neg_eig"],
      use_rope=s["use_rope"], use_gqa_gate=s["gate"],
      gqa_layers=s["gqa_layers"], n_routed_experts=s["experts"],
      n_shared_experts=s["shared"], num_experts_per_tok=s["top_k"],
      norm_topk_prob=s["renormalise"], routed_scaling_factor=s["routed_scale"],
      first_k_dense_replace=s["first_dense"], num_hidden_layers=s["layers"],
      rms_norm_eps=s["eps"], layers_here=s["here"], vocab_size=s["vocab"],
      heads_held=(s["h_first"], s["h"]), experts_held=(s["e_first"], s["held"]),
      seq_len=s["length"], mean_document_length=s["mean_doc"],
      chunk=s["chunk"],
      # the configuration names its attention path ("splash": the TPU's
      # kernel, so a run that finds no TPU fails instead of timing
      # something else); a toy copy for the CPU names "xla" itself
      attention=str(config["attention"]))
  model = SolarOpen2(cfg)
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
