"""The plain reference of Olmo-Hybrid's training step: the whole forward, the
next-token loss and (through ``jax.grad`` of :func:`loss`) every gradient, in
straightforward ``jax.numpy`` and float32. The gated delta rule one token at
a time (a ``lax.scan`` over ``t`` of the definition: no chunks), the short
convolution tap by tap from positions, attention by full ``[L, L]`` scores
under the causal-and-document mask, no kernel, no remat; callers run it under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program.

It follows the published description (``olmo_hybrid``: Olmo 3's post-norm
block, ``fla``'s gated delta net as the linear-attention mixer). Departures,
each shared with the program and stated in
``benchmark/configs/olmo-hybrid-7b-tp2share.json``:

- the weights may be those of a sub-range of the heads (one chip's share of a
  tensor-parallel group): ``Wo`` is applied over those alone and what the
  absent heads would add is left out;
- the q/k norm of a full-attention layer is an RMSNorm across all the
  channels of ``norm_groups`` equal groups of heads, each group on its own
  (published: one group; a chip of a tensor-parallel pair normalises across
  the channels it holds, so the whole layer of that deployment has two);
- the full-attention layers apply no rotary embedding (the published
  ``rope_theta`` is ``null``);
- document starts come in as numbers (one uniform a position), so that two
  implementations pack alike.
"""

import jax
import jax.numpy as jnp

LINEAR, FULL = "linear_attention", "full_attention"


def rms(x, gain, eps):
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def l2norm(x, eps=1e-6):
  return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def document_starts(numerical, mean_document_length):
  """``[B, L]`` uniforms -> bool: position 0, and ``u_i < 1 / mean``."""
  starts = numerical < 1.0 / mean_document_length
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


def delta_rule(q, k, v, alpha, beta, starts):
  """One token at a time. ``q, k [B, L, H, dk]``, ``v [B, L, H, dv]``,
  ``alpha, beta [B, L, H]``, ``starts [B, L]`` -> (``o [B, L, H, dv]``, the
  last state ``[B, H, dk, dv]``)."""
  b, _, h, dk = q.shape

  def step(s, x):
    q_t, k_t, v_t, a_t, b_t, new = x
    s = jnp.where(new[:, None, None, None], 0.0, a_t[..., None, None] * s)
    err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
    s = s + b_t[..., None, None] * jnp.einsum("bhk,bhv->bhkv", k_t, err)
    return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)
  t_major = lambda x: jnp.moveaxis(x, 1, 0)
  last, o = jax.lax.scan(
      step, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
      tuple(t_major(x) for x in (q, k, v, alpha, beta, starts)))
  return jnp.moveaxis(o, 0, 1), last


def linear_mixer(cfg, p, u, starts):
  """The mixer's part of ``o Wo`` for the heads whose weights ``p`` holds
  (before the sublayer's norm), and the rule's last state."""
  b, length, _ = u.shape
  dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
  h = p["a_log"].shape[0]
  conv = lambda x, w: jax.nn.silu(short_conv(x, w, starts))
  q = conv(u @ p["wq"], p["conv_q"]).reshape(b, length, h, dk)
  k = conv(u @ p["wk"], p["conv_k"]).reshape(b, length, h, dk)
  v = conv(u @ p["wv"], p["conv_v"]).reshape(b, length, h, dv)
  z = (u @ p["wg"]).reshape(b, length, h, dv)
  beta = jax.nn.sigmoid(u @ p["wb"])
  if cfg["linear_allow_neg_eigval"]:
    beta = 2.0 * beta
  alpha = jnp.exp(-jnp.exp(p["a_log"])
                  * jax.nn.softplus(u @ p["wa"] + p["dt_bias"]))
  o, last = delta_rule(l2norm(q) * dk ** -0.5, l2norm(k), v, alpha, beta,
                       starts)
  o = rms(o, p["o_norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
  return o.reshape(b, length, h * dv) @ p["wo"], last


def attention_mask(starts):
  """``[B, L, L]``: may query ``i`` see key ``j``: ``j <= i`` and no
  document starts in ``(j, i]``."""
  first = first_position(starts)
  j = jnp.arange(starts.shape[1])
  return (j[None, None, :] <= j[None, :, None]) \
      & (j[None, None, :] >= first[:, :, None])


def full_mixer(cfg, p, u, starts, norm_groups=1):
  b, length, _ = u.shape
  hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
  h = p["wq"].shape[1] // hd

  def qk_norm(x, gain):
    grouped = x.reshape(b, length, norm_groups, -1)
    return rms(grouped, gain.reshape(norm_groups, -1), eps).reshape(x.shape)
  heads = lambda x: x.reshape(b, length, h, hd)
  q = heads(qk_norm(u @ p["wq"], p["q_norm"]))
  k = heads(qk_norm(u @ p["wk"], p["k_norm"]))
  v = heads(u @ p["wv"])
  s = jnp.einsum("bqhd,bshd->bhqs", q, k) * hd ** -0.5
  s = jnp.where(attention_mask(starts)[:, None], s, -jnp.inf)
  o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, axis=-1), v)
  return o.reshape(b, length, h * hd) @ p["wo"]


def mlp(p, u):
  return (jax.nn.silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]


def forward(cfg, params, rows, numerical):
  """``rows [B, L, d]`` the tokens' embeddings, ``numerical [B, L]`` ->
  (logits ``[B, L, V]``, weight ``[B, L]``)."""
  eps = cfg["rms_norm_eps"]
  starts = document_starts(numerical, cfg["mean_document_length"])
  x = rows
  for i, kind in enumerate(cfg["layer_types"]):
    p = {n[len(f"layer_{i}_"):]: w for n, w in params.items()
         if n.startswith(f"layer_{i}_")}
    y = linear_mixer(cfg, p, x, starts)[0] if kind == LINEAR \
        else full_mixer(cfg, p, x, starts)
    x = x + rms(y, p["mixer_norm"], eps)
    x = x + rms(mlp(p, x), p["mlp_norm"], eps)
  logits = rms(x, params["final_norm"], eps) @ params["head"]
  # a position counts where its next token continues its document
  weight = jnp.concatenate(
      [~starts[:, 1:], jnp.zeros_like(starts[:, :1])], axis=1)
  return logits, weight.astype(jnp.float32)


def loss(cfg, params, rows, numerical, targets):
  logits, weight = forward(cfg, params, rows, numerical)
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
  return jnp.sum(weight * nll) / jnp.maximum(jnp.sum(weight), 1.0)
