"""The plain reference of SDAR-MoE's training step: the whole forward, the
block-diffusion loss and (through ``jax.grad`` of :func:`loss`) every
gradient, in straightforward ``jax.numpy`` and float32. Full ``[S, S]`` mask
built position by position, every expert as a loop over all tokens, no
kernel, no remat, no chunking; callers run it under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program.

It follows the published description (Qwen3-MoE's decoder as ``sdar_moe``
uses it; block diffusion as SDAR trains it). Departures, each shared with the
program and stated in ``benchmark/configs/sdar-30b-a3b-ep8share.json``:

- the mask token's embedding is an argument of its own (``mask_embedding``),
  not row 151,669 of ``embed_tokens``: the noisy copy is chosen, not looked up;
- ``experts`` may be a sub-range of the layer's experts (one chip's share of an
  expert-parallel group): the router still chooses among all of them, and what
  the absent experts would add is left out;
- the noise comes in as numbers (one uniform per position, one per block), so
  that two implementations mask alike; ``t = t_min + (1 - t_min) u`` per block,
  the loss's weight is ``1 / t``; there is no auxiliary router loss.
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms(x, gain, eps):
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotate(x, theta):
  """``x [B, S, H, hd]`` at positions ``0 .. S-1``."""
  hd = x.shape[-1]
  inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
  ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv[None, :]
  emb = np.concatenate([ang, ang], axis=-1)
  cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
  x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
  return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def mask_by_hand(length, block):
  """``[2 L, 2 L]``: may query ``i`` see key ``j``, over ``[xt ; x0]``."""
  m = np.zeros((2 * length, 2 * length), bool)
  for i in range(2 * length):
    for j in range(2 * length):
      bi, bj = (i % length) // block, (j % length) // block
      if i < length:   # a noisy query
        m[i, j] = (bj == bi) if j < length else (bj < bi)
      else:            # a clean query
        m[i, j] = j >= length and bj <= bi
  return m


def forward(cfg, params, rows, noise):
  """``rows [B, L, d]`` the clean tokens' embeddings, ``noise [B, L + L/Bl]``
  -> (logits ``[B, L, V]``, weight ``[B, L]``)."""
  length, block = cfg["seq_len"], cfg["block_length"]
  hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
  eps, k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
  first, count = cfg["experts_held"]
  t = cfg["t_min"] + (1.0 - cfg["t_min"]) * noise[:, length:]
  t = jnp.repeat(t, block, axis=1)
  masked = noise[:, :length] < t
  xt = jnp.where(masked[..., None], params["mask_embedding"], rows)
  x = jnp.concatenate([xt, rows], axis=1)
  b, s, _ = x.shape
  allowed = jnp.asarray(mask_by_hand(length, block))
  for i in range(cfg["num_hidden_layers"]):
    p = {n: params[f"layer_{i}_{n}"] for n in (
        "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "moe_norm",
        "router", "w_gate", "w_up", "w_down")}
    h = rms(x, p["attn_norm"], eps)
    q = rms((h @ p["wq"]).reshape(b, s, hq, hd), p["q_norm"], eps)
    kk = rms((h @ p["wk"]).reshape(b, s, hkv, hd), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(b, s, hkv, hd)
    # both halves are numbered 0 .. L-1
    q = jnp.concatenate([rotate(q[:, :length], cfg["rope_theta"]),
                         rotate(q[:, length:], cfg["rope_theta"])], axis=1)
    kk = jnp.concatenate([rotate(kk[:, :length], cfg["rope_theta"]),
                          rotate(kk[:, length:], cfg["rope_theta"])], axis=1)
    kk = jnp.repeat(kk, hq // hkv, axis=2)   # query head n reads key head
    v = jnp.repeat(v, hq // hkv, axis=2)     # n // (hq / hkv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * hd ** -0.5
    scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(b, s, hq * hd) @ p["wo"]
    h = rms(x, p["moe_norm"], eps)
    with jax.default_matmul_precision("highest"):
      probs = jax.nn.softmax(h @ p["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(count):
      chosen = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
      out = (jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])) \
          @ p["w_down"][e]
      y = y + chosen[..., None] * out
    x = x + y
  h = rms(x[:, :length], params["final_norm"], eps)
  return h @ params["head"], jnp.where(masked, 1.0 / t, 0.0)


def loss(cfg, params, rows, noise, targets):
  logits, weight = forward(cfg, params, rows, noise)
  lse = jax.nn.logsumexp(logits, axis=-1)
  picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * (lse - picked)) / (targets.shape[0]
                                             * targets.shape[1])
