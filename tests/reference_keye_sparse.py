"""The plain reference of Keye-VL-2.0's language model on the training path:
the whole forward, both loss terms and (through ``jax.grad`` of
:func:`loss`) every gradient, in straightforward ``jax.numpy``. Everything by
full ``[L, L]`` arrays: the indexer's scores, the visible pairs, the
selection by ``lax.top_k`` scattered into a mask, attention under it with
keys and values repeated to the query heads, the indexer's KL against the
heads' mean of those probabilities; the experts by a loop over every expert
held; no tile, no packed mask, no written-out backward, no remat. Callers
run it under ``jax.default_matmul_precision("highest")``. It imports nothing
of the program.

``cfg`` is a dict of the published keys (``sa_config``'s side by side with
the others) and of the share: ``experts_held``, ``vocab_size`` as held,
``num_hidden_layers`` as run, ``indexer_rotary_dim``. Departures from the
published description, each shared with the program and stated in
``benchmark/configs/keye-vl2-30b-a3b-ep8share.json``: the held experts may be
a sub-range of the layer's; document starts come in as numbers (one uniform a
position); the indexer scores in float32, not FP8.

``detach_input=False`` and ``select=False`` are the wrong models the tests
hold the right one against: the indexer's input left attached, and dense
causal attention in the selection's place.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms(x, gain, eps):
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
  mean = jnp.mean(x, axis=-1, keepdims=True)
  var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
  return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def document_starts(numerical, mean_document_length):
  """``[B, L]`` uniforms -> bool: position 0, and ``u_i < 1 / mean``."""
  starts = numerical < 1.0 / mean_document_length
  return starts.at[:, 0].set(True)


def rotate(x, theta, width):
  """``x [B, L, H, hd]``: rotate-half over the leading ``width`` dimensions
  of a head at base ``theta``, the rest pass; positions from 0."""
  length = x.shape[1]
  inv_freq = np.asarray([theta ** (-2.0 * i / width)
                         for i in range(width // 2)], np.float64)
  ang = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
  ang = np.concatenate([ang, ang], axis=-1)
  cos, sin = (jnp.asarray(t, x.dtype)[None, :, None, :]
              for t in (np.cos(ang), np.sin(ang)))
  turned, kept = x[..., :width], x[..., width:]
  half = jnp.concatenate([-turned[..., width // 2:],
                          turned[..., :width // 2]], -1)
  return jnp.concatenate([turned * cos + half * sin, kept], axis=-1)


def visible_pairs(starts):
  """``[B, L, L]`` bool, query x key: causal and of one document."""
  length = starts.shape[1]
  doc = jnp.cumsum(starts, axis=1)
  i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
  return (j <= i)[None] & (doc[:, :, None] == doc[:, None, :])


def index_scores(cfg, p, hd):
  """``I [B, L, L]`` from the (detached) normalised input ``hd``."""
  b, length, _ = hd.shape
  hi, di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
  theta, width = cfg["rope_theta"], cfg["indexer_rotary_dim"]
  qi = rotate((hd @ p["index_wq"]).reshape(b, length, hi, di), theta, width)
  ki = layer_norm(hd @ p["index_wk"], p["index_norm_gain"],
                  p["index_norm_bias"], cfg["rms_norm_eps"])
  ki = rotate(ki[:, :, None, :], theta, width)[:, :, 0, :]
  w = (hd @ p["index_ww"]) / math.sqrt(hi) / math.sqrt(di)
  products = jnp.einsum("bqhd,bkd->bqhk", qi, ki)
  return jnp.einsum("bqh,bqhk->bqk", w, jax.nn.relu(products))


def selected_pairs(scores, visible, topk):
  """``[B, L, L]`` bool: a query's ``topk`` visible keys of largest score
  (``lax.top_k``: of equal scores the lower index), all where there are
  fewer."""
  length = scores.shape[-1]
  if topk >= length:
    return visible
  _, best = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), topk)
  b, q = np.ogrid[:scores.shape[0], :scores.shape[1]]
  chosen = jnp.zeros(scores.shape, bool).at[b[..., None], q[..., None],
                                            best].set(True)
  return chosen & visible


def attention(cfg, p, h, starts, detach_input=True, select=True):
  """-> (``o Wo``, the layer's indexer loss, the selected pairs)."""
  b, length, _ = h.shape
  hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
  eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
  q = rotate(rms((h @ p["wq"]).reshape(b, length, hq, hd), p["q_norm"], eps),
             theta, hd)
  k = rotate(rms((h @ p["wk"]).reshape(b, length, hkv, hd), p["k_norm"], eps),
             theta, hd)
  v = (h @ p["wv"]).reshape(b, length, hkv, hd)
  k = jnp.repeat(k, hq // hkv, axis=2)   # query head n reads key-value
  v = jnp.repeat(v, hq // hkv, axis=2)   # head n // (hq / hkv)

  visible = visible_pairs(starts)
  index = index_scores(cfg, p, jax.lax.stop_gradient(h) if detach_input
                       else h)
  chosen = selected_pairs(index, visible, cfg["topk"]) if select else visible
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
  prob = jax.nn.softmax(jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1)
  o = jnp.einsum("bhqk,bkhd->bqhd", prob, v).reshape(b, length, hq * hd)

  target = jax.lax.stop_gradient(jnp.mean(prob, axis=1))        # [B, L, L]
  log_index = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
  live = chosen & (target > 0)
  kl = jnp.sum(jnp.where(
      live, target * (jnp.log(jnp.where(live, target, 1.0))
                      - jnp.where(live, log_index, 0.0)), 0.0), axis=-1)
  return o @ p["wo"], jnp.mean(kl), chosen


def swiglu(h, w_gate, w_up, w_down):
  return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router_weights(cfg, h, w_router):
  """-> ``[..., num_experts]``: the softmax over every expert at the
  ``num_experts_per_tok`` largest, renormalised to 1; 0 elsewhere."""
  s = jax.nn.softmax(h @ w_router, axis=-1)
  kth = jnp.sort(s, axis=-1)[..., -cfg["num_experts_per_tok"]][..., None]
  chosen = jnp.where(s >= kth, s, 0.0)
  return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def moe(cfg, p, h):
  first, held = cfg["experts_held"]
  w = router_weights(cfg, h, p["router"])
  y = jnp.zeros_like(h)
  for e in range(held):
    y = y + w[..., first + e, None] * swiglu(
        h, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
  return y


def layer_of(params, i):
  prefix = f"layer_{i}_"
  return {n[len(prefix):]: w for n, w in params.items() if n.startswith(prefix)}


def forward(cfg, params, rows, numerical, **wrong):
  """-> (logits ``[B, L, V]``, the loss's weight ``[B, L]``, the layers'
  indexer losses summed, the selected pairs of every layer)."""
  eps = cfg["rms_norm_eps"]
  starts = document_starts(numerical, cfg["mean_document_length"])
  x, index_kl, chosen = rows, 0.0, []
  for i in range(cfg["num_hidden_layers"]):
    p = layer_of(params, i)
    o, kl, c = attention(cfg, p, rms(x, p["attn_norm"], eps), starts, **wrong)
    x, index_kl = x + o, index_kl + kl
    chosen.append(c)
    x = x + moe(cfg, p, rms(x, p["moe_norm"], eps))
  logits = rms(x, params["final_norm"], eps) @ params["head"]
  weight = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  return logits, weight.astype(logits.dtype), index_kl, chosen


def loss_terms(cfg, params, rows, numerical, targets, **wrong):
  """-> (the next-token loss: the mean over the positions that are not a
  document's last of ``CE(logits_t, targets_t)``; the indexers' KL)."""
  logits, weight, index_kl, _ = forward(cfg, params, rows, numerical, **wrong)
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * nll) / jnp.maximum(jnp.sum(weight), 1.0), index_kl


def loss(cfg, params, rows, numerical, targets, **wrong):
  return sum(loss_terms(cfg, params, rows, numerical, targets, **wrong))
