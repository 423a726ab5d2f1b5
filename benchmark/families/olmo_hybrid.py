"""Olmo-Hybrid on the training path: one chip's share of a decoder whose
mixers are three gated-delta-rule layers to one full-attention layer, over
packed documents (``configs/olmo-hybrid-7b-tp2share.json``).

What the harness fixes, and the way round each. It draws ids per input and
hands neither forward the labels, so *where documents start* is the batch's
numerical features: ``seq_len`` uniforms in [0, 1) a sample, one a position;
position 0 starts a document and position ``i > 0`` starts one where
``u_i < 1 / mean_document_length``, from which program and reference derive
the segments alike. The forwards return ``{"logits", "weight"}`` (``weight``
is 1 where the next token continues the document, 0 at a document's last
token and at the sequence's); ``make_labels`` draws nothing: the targets are
the ids shifted by one. The loss is ``sum(weight CE) / sum(weight)``. The
token table is one sequence input; Adam on it is summed
(``ModelSpec.summed_tables``; the program's ``adam_rule(summed=True)``). The
harness's leaves start at ``offset + uniform(+-scale)``: ``A_log`` and
``dt_bias`` take an offset each, so that a seeded head's decay is neither 0
nor 1 (the configuration's ``assumed``). The check's read-back gathers
``program.READ_CHUNK`` physical rows at a time whatever their width, 3 GB a
gather at this table's 11,520 lanes: ``build_parts`` lowers that module
constant for the process so that a chunk is at most 256 MiB (the same rows,
the same comparison; the next ``benchmark`` issue sizes it from the row).
The harness hashes, updates and rounds a dense leaf whole on the host, which
at this cell's 7.2e8 dense values took 250 of a run's 410-450 s, past the 360
the driver gives a run: ``model_spec`` has `benchmark/in_blocks.py` hand the
same three functions a leaf a block of rows at a time (the same bits).

Reference side: :func:`reference_logits` is the benchmark's own copy of the
equations (``olmo_hybrid``: Olmo 3's post-norm block; ``fla``'s gated delta
net as the linear-attention mixer; the departures are the configuration
file's ``assumed``). It imports nothing of the program. The gated delta rule
runs ONE TOKEN AT A TIME, the definition itself (``lax.scan`` over ``t``,
products as multiply-and-sum in the arguments' dtype: no chunks, no matmul
precision to choose). So that its ``jax.grad`` fits on the chip beside the
weights and their gradients it is computed a layer at a time under
``jax.checkpoint``, the scan in blocks of tokens under ``jax.checkpoint``
(the state is kept once a block, not once a token), attention a block of
queries at a time against every key under the causal-and-document mask.
It prints, once a batch, the documents of the batch it is given and the
chunk count of the program's rule (a line that starts with ``reference``, so
`measure.py` shows it). :func:`reference_faults` names two wrong forwards
that `benchmark/control_sequential.py` puts in the reference's place, to
read what the check's limits see of them at the cell's size.

Program side: the recipe of the program's own model
(``models/olmo_hybrid.py``): plan -> ``OlmoHybrid`` ->
``adam_rule(summed=True)`` -> ``make_sparse_train_step``.
"""

from __future__ import annotations

import functools
import importlib.util
from typing import Any, Dict

import numpy as np

from benchmark import in_blocks, reference, specs, traffic

LINEAR, FULL = "linear_attention", "full_attention"
QUERY_BLOCK = 256   # queries the reference attends at a time
TOKEN_BLOCK = 64    # tokens of the recurrence between two kept states
# A_log = 1 +- 1 (A in e^0 .. e^2, inside fla's uniform (0, 16)); dt_bias =
# -4.6 +- 2.3 (softplus of it in 0.001 .. 0.1, fla's range for dt); the
# convolution's taps +-0.5 (PyTorch's Conv1d default at 4 taps)
A_LOG, DT_BIAS, CONV_SCALE = (1.0, 1.0), (2.3, -4.6), 0.5
_SAID = set()


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
  layers = int(config["num_hidden_layers_here"])
  return dict(
      d=int(config["hidden_size"]), f=int(config["intermediate_size"]),
      heads=int(config["num_attention_heads"]),
      first=int(config["heads_held"][0]), held=int(config["heads_held"][1]),
      hd=int(config["head_dim"]), dk=int(config["linear_key_head_dim"]),
      dv=int(config["linear_value_head_dim"]),
      taps=int(config["linear_conv_kernel_dim"]),
      neg_eig=bool(config["linear_allow_neg_eigval"]),
      eps=float(config["rms_norm_eps"]),
      kinds=tuple(config["layer_types"][:layers]),
      vocab=int(config["vocab_here"]), length=int(config["seq_len"]),
      mean_doc=int(config["mean_document_length"]),
      chunk=int(config["chunk"]))


def layer_leaves(s: Dict[str, Any], kind: str, scale: float) -> Dict[str, Any]:
  """name -> (shape, scale[, offset]) of one layer's dense leaves."""
  d, f, h = s["d"], s["f"], s["held"]
  gain = lambda *shape: (shape, 0.0, 1.0)
  mlp = {"mixer_norm": gain(d), "w_gate": ((d, f), scale),
         "w_up": ((d, f), scale), "w_down": ((f, d), scale),
         "mlp_norm": gain(d)}
  if kind == FULL:
    c = h * s["hd"]
    return {"wq": ((d, c), scale), "wk": ((d, c), scale),
            "wv": ((d, c), scale), "wo": ((c, d), scale),
            "q_norm": gain(c), "k_norm": gain(c), **mlp}
  ck, cv = h * s["dk"], h * s["dv"]
  return {"wq": ((d, ck), scale), "wk": ((d, ck), scale),
          "wv": ((d, cv), scale), "wg": ((d, cv), scale),
          "wb": ((d, h), scale), "wa": ((d, h), scale),
          "conv_q": ((s["taps"], ck), CONV_SCALE),
          "conv_k": ((s["taps"], ck), CONV_SCALE),
          "conv_v": ((s["taps"], cv), CONV_SCALE),
          "a_log": ((h,), *A_LOG), "dt_bias": ((h,), *DT_BIAS),
          "o_norm": gain(s["dv"]), "wo": ((cv, d), scale), **mlp}


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
      "distributed_embeddings_tpu.models.olmo_hybrid") is None:
    # before the reference's minutes: a program without the model cannot
    # run the cell, and says so at once
    raise specs.SpecError(
        "family olmo_hybrid: this checkout's program has no "
        "distributed_embeddings_tpu/models/olmo_hybrid.py")
  in_blocks.install()
  s = sizes(config)
  if config["optimizer"]["name"] != "adam":
    raise specs.SpecError("the olmo_hybrid family trains with Adam")
  if set(s["kinds"]) - {LINEAR, FULL}:
    raise specs.SpecError(f"layer_types {s['kinds']}: {LINEAR} or {FULL}")
  scale = float(config["init_scale"])
  leaves = {"final_norm": ((s["d"],), 0.0, 1.0),
            "head": ((s["d"], s["vocab"]), scale)}
  for i, kind in enumerate(s["kinds"]):
    for name, leaf in layer_leaves(s, kind, scale).items():
      leaves[f"layer_{i}_{name}"] = leaf
  return reference.ModelSpec(
      tables=(reference.TableSpec(s["vocab"], s["d"], scale),),
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
        f"in {sum(k == LINEAR for k in s['kinds'])} of {len(s['kinds'])} "
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


def reference_logits(config, dense, embs, numerical):
  """The plain equations, in the dtype the arguments come in (float32; the
  bfloat16 control hands everything over rounded)."""
  import jax
  import jax.numpy as jnp

  s = sizes(config)
  (rows,) = embs                                          # [B, L, d]
  dt = rows.dtype
  b, length, _ = rows.shape
  h, hd, dk, dv, taps = s["held"], s["hd"], s["dk"], s["dv"], s["taps"]
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
    """One token of the rule: decay (or reset), delta write, read."""
    q_t, k_t, v_t, a_t, b_t, new = x       # [B, H, dk|dv], [B, H], [B]
    state = jnp.where(new[:, None, None, None], jnp.zeros((), dt),
                      a_t[..., None, None] * state)
    err = v_t - jnp.sum(state * k_t[..., None], axis=-2)
    state = state + (b_t[..., None] * k_t)[..., None] * err[..., None, :]
    return state, jnp.sum(state * q_t[..., None], axis=-2)

  @jax.checkpoint
  def tokens(state, xs):
    return jax.lax.scan(token, state, xs)

  def delta_rule(q, k, v, alpha, beta):
    """``[B, L, H, .]`` -> ``o [B, L, H, dv]``, a token at a time; only a
    block's first state is kept for the backward pass."""
    pad = -length % TOKEN_BLOCK
    def blocks(x):  # [B, L, ...] -> [L / T, T, B, ...]; padding after the end
      x = jnp.pad(jnp.moveaxis(x, 1, 0), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
      return x.reshape((-1, TOKEN_BLOCK) + x.shape[1:])
    _, o = jax.lax.scan(
        tokens, jnp.zeros((b, h, dk, dv), dt),
        tuple(blocks(x) for x in (q, k, v, alpha, beta, starts)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:length], 0, 1)

  def linear_mixer(p, u):
    q = conv(u @ p["wq"], p["conv_q"]).reshape(b, length, h, dk)
    k = conv(u @ p["wk"], p["conv_k"]).reshape(b, length, h, dk)
    v = conv(u @ p["wv"], p["conv_v"]).reshape(b, length, h, dv)
    z = (u @ p["wg"]).reshape(b, length, h, dv)
    beta = jax.nn.sigmoid(u @ p["wb"])
    if s["neg_eig"]:
      beta = beta * jnp.asarray(2.0, dt)
    alpha = jnp.exp(-jnp.exp(p["a_log"])
                    * jax.nn.softplus(u @ p["wa"] + p["dt_bias"]))
    o = delta_rule(l2(q) * jnp.asarray(dk ** -0.5, dt), l2(k), v, alpha, beta)
    o = rms(o, p["o_norm"]) * jax.nn.silu(z)
    return o.reshape(b, length, h * dv) @ p["wo"]

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

  def full_mixer(p, u):
    # the q/k norm spans the channels held here (the configuration's
    # ``assumed``: a tensor-parallel chip normalises what it holds)
    q = rms(u @ p["wq"], p["q_norm"]).reshape(b, length, h, hd)
    k = rms(u @ p["wk"], p["k_norm"]).reshape(b, length, h, hd)
    v = (u @ p["wv"]).reshape(b, length, h, hd)
    pad = -length % q_block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda xs: attend(xs[0], xs[1], k, v),
        (jnp.moveaxis(q.reshape(b, -1, q_block, h, hd), 1, 0),
         jnp.arange(0, length + pad, q_block)))
    attn = jnp.moveaxis(out, 0, 1).reshape(b, length + pad, h * hd)
    return attn[:, :length] @ p["wo"]

  def layer(kind, p, x):
    mixer = linear_mixer if kind == LINEAR else full_mixer
    x = x + rms(mixer(p, x), p["mixer_norm"])
    y = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return x + rms(y, p["mlp_norm"])

  x = rows
  for i, kind in enumerate(s["kinds"]):
    prefix = f"layer_{i}_"
    p = {n[len(prefix):]: w for n, w in dense.items() if n.startswith(prefix)}
    x = jax.checkpoint(functools.partial(layer, kind))(p, x)
  return {"logits": rms(x, dense["final_norm"]) @ dense["head"],
          "weight": continues(jnp, starts).astype(dt)}


def reference_faults(config: Dict[str, Any]):
  """Wrong forwards for ``benchmark/control_sequential.py --stand_ins``, put in
  the reference's place at the cell's own size: name -> (``logits_fn``,
  ``loss``). The same two faults that `tests/benchmark` breaks on the
  program's side at toy size, where a sixth of the positions start a
  document; the cell's 1 in 2,048 is another question, which only a reading
  at that size answers."""
  import jax.numpy as jnp

  s = sizes(config)
  sound = functools.partial(reference_logits, config)
  one_document = functools.partial(
      reference_logits, dict(config, mean_document_length=10 ** 9))

  def not_packed(dense, embs, numerical):
    """No reset of the rule or of the convolution's window, attention
    across documents; the loss keeps its weight."""
    out = one_document(dense, embs, numerical)
    starts = document_starts(jnp, s, numerical)
    return dict(out, weight=continues(jnp, starts).astype(out["weight"].dtype))

  def every_position(jnp, outputs, labels):
    """The weight dropped: a document's last token is asked for the next
    document's first."""
    return loss(jnp, dict(outputs, weight=jnp.ones_like(outputs["weight"])),
                labels)

  return {"weight": (sound, every_position), "not_packed": (not_packed, loss)}


def build_parts(config: Dict[str, Any], world: int, global_batch: int):
  """The program's objects, by the recipe of `models/olmo_hybrid.py`."""
  import jax
  import jax.numpy as jnp
  import optax

  from benchmark import program
  from distributed_embeddings_tpu.layers.embedding import TableConfig
  from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
  from distributed_embeddings_tpu.models.olmo_hybrid import (
      OlmoHybrid,
      OlmoHybridConfig,
      next_token_loss,
  )
  from distributed_embeddings_tpu.ops.packed_table import adam_rule

  s, opt = sizes(config), config["optimizer"]
  # The check's read-back (`Program.table_changes`) gathers `READ_CHUNK`
  # physical rows at a time however few the batch touched: 65,536 rows of
  # this table's 11,520 lanes (3,840 and Adam's two moments) are 3 GB, held
  # twice over, beside 9.2 GB of state: the chip refused it (PERF.md, PR
  # 33). Same rows, same comparison, in chunks of at most 256 MiB; a table
  # of narrow rows keeps the harness's chunk
  row_bytes = 3 * s["d"] * 4
  program.READ_CHUNK = min(program.READ_CHUNK,
                           1 << ((1 << 28) // row_bytes).bit_length() - 1)
  cfg = OlmoHybridConfig(
      hidden_size=s["d"], intermediate_size=s["f"],
      num_attention_heads=s["heads"], head_dim=s["hd"],
      linear_key_head_dim=s["dk"], linear_value_head_dim=s["dv"],
      linear_conv_kernel_dim=s["taps"], linear_allow_neg_eigval=s["neg_eig"],
      rms_norm_eps=s["eps"], layer_types=s["kinds"], vocab_size=s["vocab"],
      heads_held=(s["first"], s["held"]), seq_len=s["length"],
      mean_document_length=s["mean_doc"], chunk=s["chunk"],
      # the configuration names its attention path ("splash": the TPU's
      # kernel, so a run that finds no TPU fails instead of timing
      # something else); a toy copy for the CPU names "xla" itself
      attention=str(config["attention"]))
  model = OlmoHybrid(cfg)
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
