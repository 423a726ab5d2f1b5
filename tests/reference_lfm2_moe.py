"""LFM2-MoE's plain reference: the layer equations of ``lfm2_moe`` in
straightforward ``jax.numpy``, float32, no kernel, no sort, no tiles, nothing
of the package. The caller sets ``jax.default_matmul_precision("highest")``.

``cfg`` is a dict of the published keys (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``num_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``use_expert_bias``, ``norm_eps``,
``rope_theta``, ``num_dense_layers``, ``layer_types``) and of the share
(``layers_here``, ``experts_held``, ``mean_document_length``); ``params``
holds ``layer_<i>_<name>`` for the ``i``-th layer that runs here,
``embedding_norm`` and ``head``.

Per layer: ``x += mixer(rms(x; operator_norm))``; ``x += ffn(rms(x;
ffn_norm))``. Mixer ``conv``: ``[B, C, u] = split3(h W_in)``,
``c_t = sum_j w_j (B u)_{t-2+j}`` inside the document, ``y = (C c) W_out``.
Mixer ``full_attention``: q/k RMSNorm over the head, rotate-half RoPE over
the whole head, causal softmax inside a document, grouped queries. Dense
MLP: SwiGLU. Experts: ``s = sigmoid(h W_r)``, the top k of ``s + bias``,
weights ``s_e / sum of the chosen s`` times the scaling factor, the held
experts one by one over every token.
"""

import jax
import jax.numpy as jnp
import numpy as np

CONV, FULL = "conv", "full_attention"


def rms(x, gain, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
      * gain


def document_starts(cfg, numerical):
  """``[B, L]`` bool: position 0, and where the feature is under
  ``1 / mean_document_length``."""
  return (numerical < 1.0 / cfg["mean_document_length"]) \
      | (jnp.arange(numerical.shape[1]) == 0)[None, :]


def same_document(starts):
  """``[B, L, L]`` bool: query ``i`` and key ``j`` lie in one document."""
  doc = jnp.cumsum(starts, axis=1)
  return doc[:, :, None] == doc[:, None, :]


def short_conv(p, h, starts, reset=True):
  """A tap reads ``(B u)`` of an earlier position only where that position
  lies in the query's document."""
  length = h.shape[1]
  gate_in, gate_out, u = jnp.split(h @ p["w_in"], 3, axis=-1)
  z = gate_in * u
  taps = p["conv"].shape[0]
  same = same_document(starts) if reset \
      else jnp.ones((h.shape[0], length, length), bool)
  c = jnp.zeros_like(z)
  for j in range(taps):
    back = taps - 1 - j           # tap j reads the position `back` before
    earlier = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :length]
    seen = jnp.pad(jnp.diagonal(same, offset=-back, axis1=1, axis2=2),
                   ((0, 0), (back, 0)))                    # same[t, t - back]
    c = c + jnp.where(seen[..., None], earlier, 0) * p["conv"][j]
  return (gate_out * c) @ p["w_out"]


def rotary(cfg, length):
  hd = cfg["head_dim"]
  inv = 1.0 / cfg["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float32)
                                    / hd)
  ang = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  return jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))


def rotate(x, cos, sin):
  half = x.shape[-1] // 2
  turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
  return x * cos[:, None, :] + turned * sin[:, None, :]


def attention(cfg, p, h, starts):
  b, length, _ = h.shape
  hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
      cfg["head_dim"]
  eps = cfg["norm_eps"]
  cos, sin = rotary(cfg, length)
  q = rotate(rms((h @ p["wq"]).reshape(b, length, hq, hd), p["q_norm"], eps),
             cos, sin)
  k = rotate(rms((h @ p["wk"]).reshape(b, length, hkv, hd), p["k_norm"], eps),
             cos, sin)
  v = (h @ p["wv"]).reshape(b, length, hkv, hd)
  k = jnp.repeat(k, hq // hkv, axis=2)   # query head n reads key-value head
  v = jnp.repeat(v, hq // hkv, axis=2)   # n // (hq / hkv)
  allowed = same_document(starts) & jnp.tril(jnp.ones((length, length), bool))
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
  prob = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
  return jnp.einsum("bhqk,bkhd->bqhd", prob, v).reshape(b, length, hq * hd) \
      @ p["wo"]


def router_weights(cfg, h, w_router, bias=None):
  """``[T, num_experts]``: an expert's weight for a token, 0 where it is not
  among the chosen; the choice on ``s + bias``, the weight from ``s``."""
  s = jax.nn.sigmoid(h.astype(jnp.float32) @ w_router.astype(jnp.float32))
  chosen_by = s if bias is None else s + bias
  _, top_e = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
  chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype), axis=1)
  w = s * chosen
  if cfg["norm_topk_prob"]:
    w = w / jnp.sum(w, axis=-1, keepdims=True)
  return w * cfg["routed_scaling_factor"]


def experts(cfg, p, h):
  """``h [T, d]`` -> the held experts' part of the layer."""
  first, held = cfg["experts_held"]
  w = router_weights(cfg, h, p["router"],
                     p["expert_bias"] if cfg["use_expert_bias"] else None)
  y = jnp.zeros_like(h)
  for e in range(held):
    out = (jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])) \
        @ p["w_down"][e]
    y = y + w[:, first + e, None] * out
  return y


def dense_mlp(p, h):
  return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def layer_of(params, i):
  prefix = f"layer_{i}_"
  return {n[len(prefix):]: w for n, w in params.items()
          if n.startswith(prefix)}


def forward(cfg, params, rows, numerical):
  """-> (logits ``[B, L, V]``, weight ``[B, L]``: 1 where the next token
  continues the document)."""
  eps = cfg["norm_eps"]
  starts = document_starts(cfg, numerical)
  x = rows
  b, length, d = x.shape
  for i, layer in enumerate(cfg["layers_here"]):
    p = layer_of(params, i)
    h = rms(x, p["operator_norm"], eps)
    if cfg["layer_types"][layer] == CONV:
      x = x + short_conv(p, h, starts)
    else:
      x = x + attention(cfg, p, h, starts)
    h = rms(x, p["ffn_norm"], eps)
    if layer < cfg["num_dense_layers"]:
      x = x + dense_mlp(p, h)
    else:
      x = x + experts(cfg, p, h.reshape(b * length, d)).reshape(b, length, d)
  logits = rms(x, params["embedding_norm"], eps) @ params["head"]
  weight = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  return logits, weight.astype(logits.dtype)


def loss(cfg, params, rows, numerical, targets):
  logits, weight = forward(cfg, params, rows, numerical)
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * nll) / jnp.maximum(jnp.sum(weight), 1.0)
