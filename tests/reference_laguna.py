"""The plain reference of Laguna's training step: the whole forward, the
next-token loss and (through ``jax.grad`` of :func:`loss`) every gradient, in
straightforward ``jax.numpy`` and float32. Attention by full ``[L, L]``
scores under each layer's mask, every query head against its key-value head
by repeating keys and values, the rotary tables from the published keys
written out here, the experts by a loop over every expert held, no kernel,
no sort, no remat; callers run it under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program.

``cfg`` is a dict of the published keys (``rope_parameters`` as the
published nested dict) and of the share: ``experts_held``, ``vocab_size`` as
held, ``num_hidden_layers`` as run. Departures from the published
description, each shared with the program and stated in
``benchmark/configs/laguna-xs2-ep8share.json``:

- the routed experts may be a sub-range of the layer's (one chip's share of
  an expert-parallel group): the router scores all ``num_experts``, and what
  the absent experts would add is left out; ``shared=False`` leaves the
  shared expert out too, so that shares can be added up with it counted once;
- document starts come in as numbers (one uniform a position), so that two
  implementations pack alike.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"


def rms(x, gain, eps):
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def document_starts(numerical, mean_document_length):
  """``[B, L]`` uniforms -> bool: position 0, and ``u_i < 1 / mean``."""
  starts = numerical < 1.0 / mean_document_length
  return starts.at[:, 0].set(True)


def rotary(cfg, kind, length):
  """-> (cos, sin) ``[L, rotated width]`` of the layers of ``kind``, as the
  published ``rope_parameters[kind]`` define them (the family's
  ``_compute_default_rope_parameters`` / ``_compute_yarn_parameters``)."""
  p = cfg["rope_parameters"][kind]
  dim = int(cfg["head_dim"] * p.get("partial_rotary_factor", 1.0))
  base = float(p["rope_theta"])
  inv_freq = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
  scale = 1.0
  if p["rope_type"] == "yarn":
    factor, original = p["factor"], p["original_max_position_embeddings"]
    # the dimension whose wavelength makes `turns` turns in the original
    # context; below `low` nothing is scaled, above `high` everything is
    turn_dim = lambda turns: dim * math.log(
        original / (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turn_dim(p["beta_fast"])), 0)
    high = min(math.ceil(turn_dim(p["beta_slow"])), dim - 1)
    for i in range(dim // 2):
      ramp = min(max((i - low) / (high - low), 0.0), 1.0)
      inv_freq[i] = inv_freq[i] / factor * ramp + inv_freq[i] * (1 - ramp)
    scale = p["attention_factor"]
  ang = np.arange(length, dtype=np.float64)[:, None] \
      * np.asarray(inv_freq, np.float64)[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  return np.cos(ang) * scale, np.sin(ang) * scale


def rotate(x, cos, sin):
  """``x [B, L, H, hd]``: rotate-half over the leading ``cos.shape[-1]``
  dimensions of a head, the rest pass."""
  n = cos.shape[-1]
  turned, kept = x[..., :n], x[..., n:]
  half = jnp.concatenate([-turned[..., n // 2:], turned[..., :n // 2]], -1)
  cos, sin = (jnp.asarray(t, x.dtype)[None, :, None, :] for t in (cos, sin))
  return jnp.concatenate([turned * cos + half * sin, kept], axis=-1)


def allowed_pairs(cfg, kind, starts):
  """``[B, L, L]`` bool, query x key: causal, same document, and on a
  sliding layer ``i - j < sliding_window``."""
  length = starts.shape[1]
  doc = jnp.cumsum(starts, axis=1)
  i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
  ok = (j <= i)[None] & (doc[:, :, None] == doc[:, None, :])
  if kind == SLIDING:
    ok = ok & ((i - j) < cfg["sliding_window"])[None]
  return ok


def attention(cfg, kind, heads, p, h, starts):
  b, length, _ = h.shape
  hkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
  cos, sin = rotary(cfg, kind, length)
  q = rotate((h @ p["wq"]).reshape(b, length, heads, hd), cos, sin)
  k = rotate((h @ p["wk"]).reshape(b, length, hkv, hd), cos, sin)
  v = (h @ p["wv"]).reshape(b, length, hkv, hd)
  k = jnp.repeat(k, heads // hkv, axis=2)   # query head n reads key-value
  v = jnp.repeat(v, heads // hkv, axis=2)   # head n // (heads / hkv)
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
  scores = jnp.where(allowed_pairs(cfg, kind, starts)[:, None], scores,
                     -jnp.inf)
  a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
  gate = jax.nn.sigmoid(h @ p["wg"])
  return (gate * a.reshape(b, length, heads * hd)) @ p["wo"]


def swiglu(h, w_gate, w_up, w_down):
  return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router_weights(cfg, h, w_router):
  """-> ``[..., num_experts]``: ``moe_routed_scaling_factor * s_e / sum of
  the chosen s`` at the ``num_experts_per_tok`` largest sigmoid scores, 0
  elsewhere."""
  s = jax.nn.sigmoid(h @ w_router)
  kth = jnp.sort(s, axis=-1)[..., -cfg["num_experts_per_tok"]][..., None]
  chosen = jnp.where(s >= kth, s, 0.0)
  return cfg["moe_routed_scaling_factor"] * chosen \
      / jnp.sum(chosen, axis=-1, keepdims=True)


def sparse_mlp(cfg, p, h, shared=True):
  first, held = cfg["experts_held"]
  w = router_weights(cfg, h, p["router"])
  y = swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"]) \
      if shared else jnp.zeros_like(h)
  for e in range(held):
    y = y + w[..., first + e, None] * swiglu(
        h, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
  return y


def forward(cfg, params, rows, numerical):
  """-> (logits ``[B, L, V]``, the loss's weight ``[B, L]``)."""
  eps = cfg["rms_norm_eps"]
  starts = document_starts(numerical, cfg["mean_document_length"])
  x = rows
  for i in range(cfg["num_hidden_layers"]):
    prefix = f"layer_{i}_"
    p = {n[len(prefix):]: w for n, w in params.items() if n.startswith(prefix)}
    x = x + attention(cfg, cfg["layer_types"][i],
                      cfg["num_attention_heads_per_layer"][i], p,
                      rms(x, p["attn_norm"], eps), starts)
    h = rms(x, p["mlp_norm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
      x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    else:
      x = x + sparse_mlp(cfg, p, h)
  logits = rms(x, params["final_norm"], eps) @ params["head"]
  weight = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  return logits, weight.astype(logits.dtype)


def loss(cfg, params, rows, numerical, targets):
  """Mean over the positions that are not a document's last of
  ``CE(logits_t, targets_t)``."""
  logits, weight = forward(cfg, params, rows, numerical)
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * nll) / jnp.maximum(jnp.sum(weight), 1.0)
