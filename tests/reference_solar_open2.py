"""The plain reference of Solar-Open2's training step: the whole forward, the
next-token loss and (through ``jax.grad`` of :func:`loss`) every gradient, in
straightforward ``jax.numpy`` and float32. The delta rule with a decay per
key channel (Kimi Delta Attention) ONE TOKEN AT A TIME (a ``lax.scan`` over
``t`` of the definition: no chunks), the short convolution tap by tap from
positions, attention by full ``[L, L]`` scores under the causal-and-document
mask and with no positions, the experts by a loop, no kernel, no remat;
callers run it under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program.

It follows the published config (``solar_open2``) and the mechanisms its keys
name: ``fla``'s ``KimiDeltaAttention`` (``linear_attn_config``,
``kda_use_full_proj`` false, ``kda_allow_neg_eigval``), gated grouped-query
attention without rotary positions (``gqa_layers``, ``use_gqa_gate``,
``use_rope`` false) and ``glm4_moe``'s expert layer. Departures and
assumptions, each shared with the program and stated in
``benchmark/configs/solar-open2-250b-ep40tp8share.json``:

- the weights may be those of a sub-range of the heads (one chip's share of a
  tensor-parallel group: ``W_o`` over those alone; ``W_fa``, ``W_ga`` and
  ``o_norm`` are whole on every chip) and of the experts
  (``cfg["experts_held"]``); what the absent ones would add is left out;
- document starts come in as numbers (one uniform a position), so that two
  implementations pack alike.

The keyword arguments of :func:`forward` are four WRONG models, which the
tests hold the comparison against: the rule in bfloat16, the attention
layer's gate dropped, one decay a head (the channels' mean) in place of one a
channel, and a rotary pass on the attention layer.
"""

import jax
import jax.numpy as jnp

KDA, GQA = "kda", "gqa"


def rms(x, gain, eps):
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def l2norm(x, eps=1e-6):
  return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def document_starts(cfg, numerical):
  """``[B, L]`` uniforms -> bool: position 0, and ``u_i < 1 / mean``."""
  starts = numerical < 1.0 / cfg["mean_document_length"]
  return starts.at[:, 0].set(True)


def first_position(starts):
  """``[B, L]``: the position of the first token of each position's
  document."""
  pos = jnp.arange(starts.shape[1])[None, :]
  return jax.lax.cummax(jnp.where(starts, pos, 0), axis=1)


def short_conv(x, w, starts):
  """``x [B, L, C]``, ``w [K, C]``: ``y_t = sum_j w_j x_{t-(K-1)+j}``, taps
  before the document's first token read 0."""
  taps, length = w.shape[0], x.shape[1]
  first = first_position(starts)
  pos = jnp.arange(length)[None, :]
  y = jnp.zeros_like(x)
  for j in range(taps):
    src = pos - (taps - 1) + j
    tap = jnp.take_along_axis(x, jnp.clip(src, 0)[..., None], axis=1)
    y = y + jnp.where((src >= first)[..., None], tap, 0.0) * w[j]
  return y


def kda_rule(q, k, v, g, beta, starts):
  """One token at a time. ``q, k, g [B, L, H, dk]`` (``g`` the log of the
  decay, one a key channel), ``v [B, L, H, dv]``, ``beta [B, L, H]``,
  ``starts [B, L]`` -> (``o [B, L, H, dv]``, the last state
  ``[B, H, dk, dv]``)."""
  b, _, h, dk = q.shape

  def step(s, x):
    q_t, k_t, v_t, g_t, b_t, new = x
    # Diag(exp(g_t)) S: a row of the state a key channel
    s = jnp.where(new[:, None, None, None], 0.0, jnp.exp(g_t)[..., None] * s)
    err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
    s = s + b_t[..., None, None] * jnp.einsum("bhk,bhv->bhkv", k_t, err)
    return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)
  t_major = lambda x: jnp.moveaxis(x, 1, 0)
  last, o = jax.lax.scan(
      step, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
      tuple(t_major(x) for x in (q, k, v, g, beta, starts)))
  return jnp.moveaxis(o, 0, 1), last


def kda_mixer(cfg, p, u, starts, scalar_decay=False, rule_dtype=None):
  """The mixer's part of ``o Wo`` for the heads whose weights ``p`` holds,
  on the normalised input ``u``."""
  b, length, _ = u.shape
  hd = cfg["linear_head_dim"]
  h = p["a_log"].shape[0]
  heads = lambda x: x.reshape(b, length, h, hd)
  conv = lambda x, w: heads(jax.nn.silu(short_conv(x, w, starts)))
  q = conv(u @ p["wq"], p["conv_q"])
  k = conv(u @ p["wk"], p["conv_k"])
  v = conv(u @ p["wv"], p["conv_v"])
  g = -jnp.exp(p["a_log"])[:, None] * heads(jax.nn.softplus(
      (u @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]))
  if scalar_decay:   # WRONG: one decay a head, the channels' mean
    g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
  beta = jax.nn.sigmoid(u @ p["wb"])
  if cfg["kda_allow_neg_eigval"]:
    beta = 2.0 * beta
  operands = (l2norm(q) * hd ** -0.5, l2norm(k), v, g, beta)
  if rule_dtype is not None:   # WRONG: the rule in a lower precision
    operands = tuple(x.astype(rule_dtype) for x in operands)
  o = kda_rule(*operands, starts)[0].astype(u.dtype)
  gate = jax.nn.sigmoid(heads((u @ p["w_ga"]) @ p["w_gb"] + p["b_g"]))
  o = rms(o, p["o_norm"], cfg["rms_norm_eps"]) * gate
  return o.reshape(b, length, h * hd) @ p["wo"]


def attention_mask(starts):
  """``[B, L, L]``: may query ``i`` see key ``j``: ``j <= i`` and no
  document starts in ``(j, i]``."""
  first = first_position(starts)
  j = jnp.arange(starts.shape[1])
  return (j[None, None, :] <= j[None, :, None]) \
      & (j[None, None, :] >= first[:, :, None])


def rotate(x, theta):
  """Rotate-half RoPE over all of the last axis, positions from 0."""
  length, hd = x.shape[1], x.shape[-1]
  inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
  ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
  ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
  x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
  return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def gqa_mixer(cfg, p, u, starts, gate=True, rope=False):
  """The mixer's part of ``(gate * o) Wo`` for the query heads whose weights
  ``p`` holds and the key-value heads they read."""
  b, length, _ = u.shape
  hd = cfg["head_dim"]
  hq, hkv = p["wq"].shape[1] // hd, p["wk"].shape[1] // hd
  q = (u @ p["wq"]).reshape(b, length, hq, hd)
  k = (u @ p["wk"]).reshape(b, length, hkv, hd)
  v = (u @ p["wv"]).reshape(b, length, hkv, hd)
  if rope:   # WRONG: use_rope is false
    q, k = rotate(q, 10000.0), rotate(k, 10000.0)
  # query head i reads key-value head i // (hq / hkv)
  k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
  s = jnp.einsum("bqhd,bshd->bhqs", q, k) * hd ** -0.5
  s = jnp.where(attention_mask(starts)[:, None], s, -jnp.inf)
  o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, axis=-1), v)
  o = o.reshape(b, length, hq * hd)
  if gate:   # dropped: WRONG, use_gqa_gate is true
    o = jax.nn.sigmoid(u @ p["wg"]) * o
  return o @ p["wo"]


def swiglu(h, w_gate, w_up, w_down):
  return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router_weights(cfg, h, w_router, bias):
  """``[T, E]``: the weight of every expert for every token, 0 where it was
  not chosen: sigmoid scores, the choice on ``s + bias``, the weights from
  ``s`` renormalised and scaled."""
  s = jax.nn.sigmoid(h.astype(jnp.float32) @ w_router.astype(jnp.float32))
  _, chosen = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
  picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=s.dtype), axis=-2)
  w = s * picked
  if cfg["norm_topk_prob"]:
    w = w / jnp.sum(w, axis=-1, keepdims=True)
  return cfg["routed_scaling_factor"] * w


def routed_experts(cfg, p, h):
  """The held experts' part: ``sum_e p_e SwiGLU_e(h)`` over the experts
  ``cfg["experts_held"]`` names, whose weights ``p`` holds."""
  first, held = cfg["experts_held"]
  w = router_weights(cfg, h, p["router"], p["expert_bias"])
  y = jnp.zeros_like(h)
  for e in range(held):
    y = y + w[:, first + e, None] * swiglu(
        h, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
  return y


def experts(cfg, p, h):
  return routed_experts(cfg, p, h) + swiglu(
      h, p["shared_gate"], p["shared_up"], p["shared_down"])


def layer(cfg, p, x, starts, kind, **wrong):
  eps = cfg["rms_norm_eps"]
  u = rms(x, p["input_norm"], eps)
  if kind == KDA:
    x = x + kda_mixer(cfg, p, u, starts, **{
        k: v for k, v in wrong.items() if k in ("scalar_decay", "rule_dtype")})
  else:
    x = x + gqa_mixer(cfg, p, u, starts, **{
        k: v for k, v in wrong.items() if k in ("gate", "rope")})
  h = rms(x, p["post_attention_norm"], eps)
  return x + experts(cfg, p, h.reshape(-1, h.shape[-1])).reshape(x.shape)


def leaves_of(tree, prefix):
  return {n[len(prefix):]: w for n, w in tree.items() if n.startswith(prefix)}


def kinds(cfg):
  return tuple(GQA if i in cfg["gqa_layers"] else KDA
               for i in cfg["layers_here"])


def forward(cfg, params, rows, numerical, **wrong):
  """``rows [B, L, d]`` the tokens' embeddings, ``numerical [B, L]`` ->
  (logits ``[B, L, V]``, weight ``[B, L]``)."""
  starts = document_starts(cfg, numerical)
  x = rows
  for i, kind in enumerate(kinds(cfg)):
    x = layer(cfg, leaves_of(params, f"layer_{i}_"), x, starts, kind, **wrong)
  logits = rms(x, params["norm"], cfg["rms_norm_eps"]) @ params["head"]
  # a position counts where its next token continues its document
  weight = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  return logits, weight.astype(jnp.float32)


def loss(cfg, params, rows, numerical, targets, **wrong):
  logits, weight = forward(cfg, params, rows, numerical, **wrong)
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * nll) / jnp.maximum(jnp.sum(weight), 1.0)
