"""SDAR-MoE (``model_type`` ``sdar_moe``): a block-diffusion language model
over a mixture of experts, on the training path.

The decoder follows Qwen3-MoE's modelling code: per layer, on a residual
stream ``x [B, S, d]``: ``h = RMSNorm(x)``; ``q, k, v = h Wq, h Wk, h Wv``
without bias; RMSNorm over the head dimension on ``q`` and on ``k``; RoPE
(rotate-half) on both; grouped-query attention at scale ``head_dim ** -0.5``;
``x += attn Wo``. Then ``h = RMSNorm(x)`` and ``x += moe(h)``: router over all
experts in float32, top-k, the k probabilities renormalised to 1, experts
``(silu(h Wg) * (h Wu)) Wd`` (:mod:`..layers.moe`, which computes the experts
this chip holds). A final RMSNorm and an untied head.

Block diffusion, training. A clean sequence ``x0`` of ``L`` tokens is cut in
blocks of ``block_length``. Each block draws a noise level ``t`` and each of
its positions is masked with probability ``t``; the noisy copy ``xt`` holds the
mask token's embedding at masked positions and ``x0``'s elsewhere. The model
sees ``[xt ; x0]``, ``S = 2 L`` positions, both halves numbered ``0 .. L-1``
for RoPE; who sees whom is :class:`..layers.attention.BlockDiffusion`. Logits
are taken at the noisy half and the loss is ``sum over masked positions of
CE / t``, over ``B L`` (:func:`block_diffusion_loss`).

On the sparse train step the token table is a sequence input
(``TableConfig(combiner=None)`` read at hotness ``L``): ``emb_acts`` is
``[rows [B, L, d]]``. The noise is the batch's numerical features,
``[B, L + L / block_length]`` uniforms in [0, 1): one per position, then one
per block (``t = t_min + (1 - t_min) u``). The mask token's embedding is a
dense leaf of its own (published: a row of ``embed_tokens``; the same
mathematics), because the noisy copy is chosen, not looked up.

Attention never holds ``[S, S]`` scores: it is :mod:`..layers.attention`'s
pair under that static mask, no segment ids (the kernel's block map skips the
blocks of keys the mask empties, about 3/4 of them). Without a TPU the model
raises rather than compute something else; ``attention="xla"`` names the
other path, for tests and counting tools on any backend.

The projections' and the head's products are :func:`..layers.dense.mxu_dot`:
on a TPU handed bfloat16 operands, float32 out of both passes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..layers.attention import (
    BlockDiffusion,
    attention_path,
    attention_splash,
    attention_xla,
    rope,
    rope_frequencies,
)
from ..layers.decoder import rms_norm
from ..layers.dense import mxu_dot
from ..layers.moe import MoEShare, moe_share
from ..layers.remat import checkpoint_layer
from ..telemetry import scopes


@dataclasses.dataclass(frozen=True)
class SDARMoEConfig:
  """Widths as the published ``config.json`` names them, and the share of the
  model that lives here."""
  hidden_size: int = 2048
  num_attention_heads: int = 32
  num_key_value_heads: int = 4
  head_dim: int = 128
  moe_intermediate_size: int = 768
  num_experts: int = 128
  num_experts_per_tok: int = 8
  rms_norm_eps: float = 1e-6
  rope_theta: float = 1e6
  num_hidden_layers: int = 48
  vocab_size: int = 151936              # rows of the head (a slice: fewer)
  experts_held: Tuple[int, int] = (0, 128)
  block_length: int = 4
  t_min: float = 0.1                    # t is uniform on [t_min, 1]
  seq_len: int = 4096                   # L, tokens of a clean sequence
  attention: str = "splash"             # splash: the TPU's kernel | xla: tests

  @property
  def n_numerical(self) -> int:
    return self.seq_len + self.seq_len // self.block_length

  @property
  def share(self) -> MoEShare:
    """This chip's share of every layer."""
    return MoEShare(self.num_experts, self.num_experts_per_tok,
                    tuple(self.experts_held))


def noise_of(numerical, seq_len: int, block_length: int, t_min: float):
  """The batch's numerical features -> (masked ``[B, L]`` bool, the loss's
  weight ``[B, L]``: ``1 / t`` at masked positions, 0 elsewhere)."""
  u = numerical[:, :seq_len]
  t = t_min + (1.0 - t_min) * numerical[:, seq_len:]     # [B, L / Bl]
  t = jnp.repeat(t, block_length, axis=1)                # [B, L]
  masked = u < t
  return masked, jnp.where(masked, 1.0 / t, 0.0).astype(jnp.float32)


def decoder_layer(cfg: SDARMoEConfig, p, x):
  """One layer on ``x [B, S, d]`` with its parameters ``p`` (a dict)."""
  b, s, d = x.shape
  hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
  positions = jnp.tile(jnp.arange(cfg.seq_len), 2)
  with jax.named_scope(scopes.ATTENTION):
    h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope(scopes.ATTN_PROJ):
      q = mxu_dot(h, p["wq"]).reshape(b, s, hq, hd)
      k = mxu_dot(h, p["wk"]).reshape(b, s, hkv, hd)
      v = mxu_dot(h, p["wv"]).reshape(b, s, hkv, hd)
    inv_freq = rope_frequencies(cfg.rope_theta, hd)
    with jax.named_scope(scopes.ATTN_QK):
      q = rope(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions,
               inv_freq) * (hd ** -0.5)
      k = rope(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions,
               inv_freq)
    q = q.reshape(b, s, hkv, hq // hkv, hd)
    attend = attention_path(cfg.attention, attention_xla, attention_splash)
    with jax.named_scope(scopes.ATTN_CORE):
      o = attend(q, k, v, BlockDiffusion(cfg.block_length))
    with jax.named_scope(scopes.ATTN_PROJ):
      o = mxu_dot(o.reshape(b, s, hq * hd), p["wo"])
    x = x + o
  with jax.named_scope(scopes.MOE):
    h = rms_norm(x, p["moe_norm"], cfg.rms_norm_eps)
  y, counters = moe_share(h.reshape(b * s, d), p["router"], p["w_gate"],
                          p["w_up"], p["w_down"], cfg.share)
  return x + y.reshape(b, s, d), counters


class SDARMoE(nn.Module):
  """``__call__(numerical, cats, emb_acts=[rows [B, L, d]])`` ->
  ``{"logits" [B, L, V], "weight" [B, L]}`` (and ``"moe"``, the layers'
  counters stacked, where ``with_counters``)."""

  config: SDARMoEConfig
  with_counters: bool = False

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    del cats
    cfg = self.config
    if emb_acts is None or len(emb_acts) != 1:
      raise ValueError("SDARMoE takes its token rows as one sequence input: "
                       "emb_acts=[rows [B, L, hidden_size]]")
    (x0,) = emb_acts
    d, hq, hkv, hd = (cfg.hidden_size, cfg.num_attention_heads,
                      cfg.num_key_value_heads, cfg.head_dim)
    f, held = cfg.moe_intermediate_size, cfg.experts_held[1]
    normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
    shapes = {
        "attn_norm": ((d,), ones), "wq": ((d, hq * hd), normal),
        "wk": ((d, hkv * hd), normal), "wv": ((d, hkv * hd), normal),
        "wo": ((hq * hd, d), normal), "q_norm": ((hd,), ones),
        "k_norm": ((hd,), ones), "moe_norm": ((d,), ones),
        "router": ((d, cfg.num_experts), normal),
        "w_gate": ((held, d, f), normal), "w_up": ((held, d, f), normal),
        "w_down": ((held, f, d), normal)}
    mask_embedding = self.param("mask_embedding", normal, (d,))
    layers = [{name: self.param(f"layer_{i}_{name}", init, shape)
               for name, (shape, init) in shapes.items()}
              for i in range(cfg.num_hidden_layers)]
    final_norm = self.param("final_norm", ones, (d,))
    head = self.param("head", normal, (d, cfg.vocab_size))

    masked, weight = noise_of(numerical, cfg.seq_len, cfg.block_length,
                              cfg.t_min)
    xt = jnp.where(masked[..., None], mask_embedding.astype(x0.dtype), x0)
    x = jnp.concatenate([xt, x0], axis=1)                    # [B, 2 L, d]
    # one layer's activations at a time, plus what layers/remat.py names:
    # the rest of the other layers is recomputed
    layer = checkpoint_layer(functools.partial(decoder_layer, cfg))
    counters = []
    for p in layers:
      x, c = layer(p, x)
      counters.append(c)
    with jax.named_scope(scopes.LM_HEAD):
      h = rms_norm(x[:, :cfg.seq_len], final_norm, cfg.rms_norm_eps)
      logits = mxu_dot(h, head)
    out = {"logits": logits, "weight": weight}
    if self.with_counters:
      out["moe"] = jax.tree_util.tree_map(lambda *c: jnp.stack(c), *counters)
      out["masked"] = masked
    return out


def block_diffusion_loss(outputs, labels):
  """``sum over masked positions of (1 / t) CE(logits, x0) / (B L)``:
  ``outputs`` as :class:`SDARMoE` returns them, ``labels["targets"] [B, L]``
  the clean tokens."""
  logits = outputs["logits"].astype(jnp.float32)
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, labels["targets"][..., None], -1)[..., 0]
  return jnp.mean(outputs["weight"] * nll)
