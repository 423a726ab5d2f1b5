"""GLM-4.7-Flash's plain reference: the layer equations of ``glm4_moe_lite``
in straightforward ``jax.numpy``, float32, no kernel, no sort, no tiles,
nothing of the package. The caller sets
``jax.default_matmul_precision("highest")``.

``cfg`` is a dict of the published keys (``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``n_routed_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``first_k_dense_replace``, ``rms_norm_eps``,
``rope_theta``) and of the share (``layers_here``, ``experts_held``,
``mean_document_length``); ``params`` holds ``layer_<i>_<name>`` for the
``i``-th trunk layer that runs here, ``mtp_<name>`` for the prediction
module (its layer's leaves ``mtp_layer_<name>``), ``norm`` and ``head``.

Per layer: ``x += attn(rms(x; input_norm))``; ``x += ffn(rms(x;
post_attention_norm))``. Attention: ``c_q = rms(h W_dq)``, ``[q_n ; q_r] =
c_q W_uq`` a head; ``[c_kv ; k_r] = h W_dkv``, ``c_kv = rms(c_kv)``,
``[k_n ; v] = c_kv W_ukv`` a head; rotate-half RoPE on ``q_r`` of every head
and on the ONE ``k_r``, which every head's key ends in; causal softmax inside
a document at ``1 / sqrt(nope + rope)``; ``W_o``. Dense MLP: SwiGLU.
Experts: ``s = sigmoid(h W_r)``, the top k of ``s + bias``, weights ``s_e /
sum of the chosen s`` times the scaling factor, the held experts one by one
over every token, plus the shared expert. The prediction module: ``z_i =
[rms(e_{i+1}) ; rms(x_i)] W_eh``, an expert layer of its own, its norm, THE
SAME head; its target is token ``i + 2``.
"""

import jax
import jax.numpy as jnp
import numpy as np


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


def rotary(cfg, length):
  dr = cfg["qk_rope_head_dim"]
  inv = 1.0 / cfg["rope_theta"] ** (np.arange(0, dr, 2, dtype=np.float32)
                                    / dr)
  ang = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  return jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))


def rotate(x, cos, sin):
  """``x [B, L, heads, rope]``."""
  half = x.shape[-1] // 2
  turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
  return x * cos[:, None, :] + turned * sin[:, None, :]


def attention(cfg, p, h, starts, shared_key=True):
  """``shared_key=False``: the rotary key written out as ``heads`` explicit
  copies before the rotation (what the shared one must equal)."""
  b, length, _ = h.shape
  heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
  eps = cfg["rms_norm_eps"]
  cos, sin = rotary(cfg, length)
  c_q = rms(h @ p["w_dq"], p["q_a_norm"], eps)
  q = (c_q @ p["w_uq"]).reshape(b, length, heads, dn + dr)
  down = h @ p["w_dkv"]
  c_kv = rms(down[..., :cfg["kv_lora_rank"]], p["kv_a_norm"], eps)
  k_r = down[..., cfg["kv_lora_rank"]:]                         # [B, L, rope]
  kv = (c_kv @ p["w_ukv"]).reshape(b, length, heads, dn + dv)
  if shared_key:
    k_r = jnp.broadcast_to(rotate(k_r[:, :, None, :], cos, sin),
                           (b, length, heads, dr))
  else:
    k_r = rotate(jnp.stack([k_r] * heads, axis=2), cos, sin)
  q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], cos, sin)], axis=-1)
  k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
  v = kv[..., dn:]
  allowed = same_document(starts) & jnp.tril(jnp.ones((length, length), bool))
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dn + dr) ** -0.5
  prob = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
  return jnp.einsum("bhqk,bkhd->bqhd", prob, v).reshape(
      b, length, heads * dv) @ p["w_o"]


def router_weights(cfg, h, w_router, bias):
  """``[T, n_routed_experts]``: an expert's weight for a token, 0 where it
  is not among the chosen; the choice on ``s + bias``, the weight from
  ``s``."""
  s = jax.nn.sigmoid(h.astype(jnp.float32) @ w_router.astype(jnp.float32))
  _, top_e = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
  chosen = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=s.dtype), axis=1)
  w = s * chosen
  if cfg["norm_topk_prob"]:
    w = w / jnp.sum(w, axis=-1, keepdims=True)
  return w * cfg["routed_scaling_factor"]


def swiglu(h, w_gate, w_up, w_down):
  return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routed_experts(cfg, p, h):
  """``h [T, d]`` -> the held experts' part of the layer."""
  first, held = cfg["experts_held"]
  w = router_weights(cfg, h, p["router"], p["expert_bias"])
  y = jnp.zeros_like(h)
  for e in range(held):
    y = y + w[:, first + e, None] * swiglu(h, p["w_gate"][e], p["w_up"][e],
                                           p["w_down"][e])
  return y


def experts(cfg, p, h):
  """The held experts' part and the shared expert."""
  return routed_experts(cfg, p, h) + swiglu(
      h, p["shared_gate"], p["shared_up"], p["shared_down"])


def leaves_of(params, prefix):
  return {n[len(prefix):]: w for n, w in params.items()
          if n.startswith(prefix)}


def layer(cfg, p, x, starts, dense):
  eps = cfg["rms_norm_eps"]
  b, length, d = x.shape
  x = x + attention(cfg, p, rms(x, p["input_norm"], eps), starts)
  h = rms(x, p["post_attention_norm"], eps)
  if dense:
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
  return x + experts(cfg, p, h.reshape(b * length, d)).reshape(b, length, d)


def forward(cfg, params, rows, numerical):
  """-> ``{"logits", "weight", "mtp_logits", "mtp_weight"}``: ``weight`` 1
  where the next token continues the document, ``mtp_weight`` 1 where the
  next two do."""
  eps = cfg["rms_norm_eps"]
  starts = document_starts(cfg, numerical)
  x = rows
  for i, number in enumerate(cfg["layers_here"]):
    x = layer(cfg, leaves_of(params, f"layer_{i}_"), x, starts,
              number < cfg["first_k_dense_replace"])
  logits = rms(x, params["norm"], eps) @ params["head"]
  mtp = leaves_of(params, "mtp_")
  following = jnp.concatenate([rows[:, 1:], jnp.zeros_like(rows[:, :1])],
                              axis=1)                             # e_{i+1}
  z = jnp.concatenate([rms(following, mtp["enorm"], eps),
                       rms(x, mtp["hnorm"], eps)], axis=-1) @ mtp["w_eh"]
  z = layer(cfg, leaves_of(mtp, "layer_"), z, starts, False)
  mtp_logits = rms(z, mtp["norm"], eps) @ params["head"]
  goes_on = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  twice = goes_on & jnp.concatenate(
      [goes_on[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  return {"logits": logits, "weight": goes_on.astype(logits.dtype),
          "mtp_logits": mtp_logits, "mtp_weight": twice.astype(logits.dtype)}


def cross_entropy(logits, weight, targets):
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * nll) / jnp.maximum(jnp.sum(weight), 1.0)


def losses(cfg, params, rows, numerical, targets, targets_2):
  """-> (the next-token loss, the prediction module's)."""
  out = forward(cfg, params, rows, numerical)
  return (cross_entropy(out["logits"], out["weight"], targets),
          cross_entropy(out["mtp_logits"], out["mtp_weight"], targets_2))


def loss(cfg, params, rows, numerical, targets, targets_2, weight=0.3):
  first, second = losses(cfg, params, rows, numerical, targets, targets_2)
  return first + weight * second
