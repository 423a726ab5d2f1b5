"""LFM2-MoE (``model_type`` ``lfm2_moe``): a decoder in which the mixer's
kind and the feed-forward's kind vary independently by layer, on the
training path over sequences of packed documents. Three mixers in four are
double-gated short convolutions, the fourth is grouped-query attention at a
head of 64; the first ``num_dense_layers`` layers carry a dense MLP, the
others a mixture of experts chosen under a selection bias.

Per layer ``l``, pre-norm, on a residual stream ``x [B, L, d]``:
``x += mixer_l(RMSNorm(x; operator_norm))``, then
``x += ffn_l(RMSNorm(x; ffn_norm))``; a final RMSNorm (``embedding_norm``)
and an untied head.

*Short convolution* (``layer_types[l]`` ``conv``):
:func:`..layers.short_conv.short_conv_mixer`: ``[B, C, u] = h W_in``,
``y = (C * conv(B * u)) W_out``, ``conv_L_cache`` taps a channel, reset at a
document's first token.

*Attention* (``full_attention``): ``q, k, v = h Wq, h Wk, h Wv`` without
bias; RMSNorm over the head dimension on ``q`` and on ``k``; RoPE
(rotate-half over the whole head at ``rope_theta``, positions from the
sequence's start) on both; ``softmax(q k^T / sqrt(head_dim))`` causal and
inside a document with ``num_attention_heads / num_key_value_heads`` query
heads a key-value head; ``Wo``. The attention proper is
:mod:`..layers.attention`'s pair under ``Causal()``, the documents as segment
ids; ``attention="xla"`` names the path that runs without a TPU (tests).

*Dense MLP* (layers below ``num_dense_layers``):
``(SiLU(h W1) * (h W3)) W2`` at ``intermediate_size``.

*Experts* (the others): ``s = sigmoid(h Wr)`` over all experts in float32;
the ``num_experts_per_tok`` largest of ``s + expert_bias`` are chosen; their
weights are the unbiased ``s``, renormalised to 1 (``norm_topk_prob``) and
times ``routed_scaling_factor``; :func:`..layers.moe.moe_share` computes
the experts this chip holds. ``expert_bias`` is a leaf of the model that
enters the choice alone: its gradient is zero and an optimizer step from
zero moments leaves it where it was (the published config names no rule
that updates it).

Which of the published layers run here is ``layers_here`` (their numbers in
the published model: a layer's kinds follow from its number); the leaves of
the ``i``-th of them are named ``layer_<i>_*``.

*Packed documents* and the loss (:func:`..layers.decoder.next_token_loss`)
are :mod:`..layers.decoder`'s; ``emb_acts`` is ``[rows [B, L, d]]``.

The plain products are :func:`..layers.dense.mxu_dot`: on a TPU handed
bfloat16 operands, float32 out of both passes; the router's is float32 at
``highest`` (:func:`..layers.moe.route`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..layers.attention import (
    Causal,
    attention_path,
    attention_splash,
    attention_xla,
    rope,
    rope_frequencies,
)
from ..layers.decoder import document_segments, rms_norm
from ..layers.dense import mxu_dot
from ..layers.moe import MoEShare, Router, moe_share
from ..layers.remat import checkpoint_layer
from ..layers.short_conv import short_conv_mixer
from ..telemetry import scopes

CONV, FULL = "conv", "full_attention"
DENSE, EXPERTS = "dense", "experts"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
  """Widths as the published ``config.json`` names them, and the share of
  the model that lives here."""
  hidden_size: int = 2048
  intermediate_size: int = 11776
  num_attention_heads: int = 32
  num_key_value_heads: int = 8
  head_dim: int = 64                    # hidden_size / num_attention_heads
  moe_intermediate_size: int = 1536
  num_experts: int = 64
  num_experts_per_tok: int = 4
  norm_topk_prob: bool = True
  routed_scaling_factor: float = 1.0
  use_expert_bias: bool = True
  conv_L_cache: int = 3
  norm_eps: float = 1e-5
  rope_theta: float = 1e6
  num_dense_layers: int = 2
  layer_types: Tuple[str, ...] = (CONV, CONV) + (FULL, CONV, CONV, CONV) * 9 \
      + (FULL, CONV)
  layers_here: Tuple[int, ...] = tuple(range(40))   # published numbers
  vocab_size: int = 65536               # rows of the head (a slice: fewer)
  experts_held: Tuple[int, int] = (0, 64)
  seq_len: int = 16384
  mean_document_length: int = 4096
  attention: str = "splash"             # splash: the TPU's kernel | xla: tests

  def __post_init__(self):
    stray = set(self.layer_types) - {CONV, FULL}
    if stray:
      raise ValueError(f"layer_types names {sorted(stray)}: "
                       f"{CONV} or {FULL}")
    for layer in self.layers_here:
      if not 0 <= layer < len(self.layer_types):
        raise ValueError(f"layers_here names layer {layer} of "
                         f"{len(self.layer_types)}")
    if self.num_attention_heads % self.num_key_value_heads:
      raise ValueError(f"{self.num_attention_heads} query heads over "
                       f"{self.num_key_value_heads} key-value heads")

  @property
  def kinds(self) -> Tuple[Tuple[str, str], ...]:
    """(mixer, feed-forward) of every layer that runs here."""
    return tuple((self.layer_types[layer],
                  DENSE if layer < self.num_dense_layers else EXPERTS)
                 for layer in self.layers_here)

  @property
  def share(self) -> MoEShare:
    """This chip's share of every expert layer, and the layers' router."""
    return MoEShare(
        self.num_experts, self.num_experts_per_tok, tuple(self.experts_held),
        Router("sigmoid", bool(self.norm_topk_prob),
               float(self.routed_scaling_factor),
               selection_bias=bool(self.use_expert_bias)))


def attention_mixer(cfg: Lfm2MoeConfig, p, h, seg):
  """One layer's attention on its normalised input ``h [B, L, d]`` ->
  ``[B, L, d]``."""
  b, length, _ = h.shape
  hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
  inv_freq = rope_frequencies(cfg.rope_theta, hd)
  positions = jnp.arange(length)

  def proj(x, w):
    with jax.named_scope(scopes.ATTN_PROJ):
      return mxu_dot(x, p[w])

  q = proj(h, "wq").reshape(b, length, hq, hd)
  with jax.named_scope(scopes.ATTN_QK):
    q = rope(rms_norm(q, p["q_norm"], cfg.norm_eps), positions,
             inv_freq) * hd ** -0.5
  k = proj(h, "wk").reshape(b, length, hkv, hd)
  with jax.named_scope(scopes.ATTN_QK):
    k = rope(rms_norm(k, p["k_norm"], cfg.norm_eps), positions, inv_freq)
  v = proj(h, "wv").reshape(b, length, hkv, hd)
  attend = attention_path(cfg.attention, attention_xla, attention_splash)
  with jax.named_scope(scopes.ATTN_CORE):
    a = attend(q.reshape(b, length, hkv, hq // hkv, hd), k, v, Causal(), seg)
  return proj(a.reshape(b, length, hq * hd), "wo")


def decoder_layer(cfg: Lfm2MoeConfig, mixer: str, ffn: str, p, x, seg):
  """One layer of mixer ``mixer`` and feed-forward ``ffn`` on ``x [B, L, d]``
  with its parameters ``p`` -> (``x``, the expert layer's counters or
  ``None``)."""
  b, length, d = x.shape
  if mixer == CONV:
    with jax.named_scope(scopes.SHORT_CONV):
      h = rms_norm(x, p["operator_norm"], cfg.norm_eps)
      x = x + short_conv_mixer(p, h, seg)
  else:
    with jax.named_scope(scopes.ATTENTION):
      h = rms_norm(x, p["operator_norm"], cfg.norm_eps)
      x = x + attention_mixer(cfg, p, h, seg)
  if ffn == DENSE:
    with jax.named_scope(scopes.MLP):
      h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
      y = mxu_dot(jax.nn.silu(mxu_dot(h, p["w_gate"]))
                  * mxu_dot(h, p["w_up"]), p["w_down"])
    return x + y, None
  with jax.named_scope(scopes.MOE):
    h = rms_norm(x, p["ffn_norm"], cfg.norm_eps).reshape(b * length, d)
  y, counters = moe_share(
      h, p["router"], p["w_gate"], p["w_up"], p["w_down"], cfg.share,
      p["expert_bias"] if cfg.use_expert_bias else None)
  return x + y.reshape(b, length, d), counters


def layer_shapes(cfg: Lfm2MoeConfig, mixer: str, ffn: str
                 ) -> Dict[str, Tuple[Any, str]]:
  """name -> (shape, kind of leaf) of one layer's parameters: ``matrix``,
  ``gain`` (starts at 1), ``conv`` (taps x channels), ``bias`` (the
  selection bias, starts at 0)."""
  d, hd = cfg.hidden_size, cfg.head_dim
  shapes = {"operator_norm": ((d,), "gain"), "ffn_norm": ((d,), "gain")}
  if mixer == CONV:
    shapes.update({"w_in": ((d, 3 * d), "matrix"),
                   "conv": ((cfg.conv_L_cache, d), "conv"),
                   "w_out": ((d, d), "matrix")})
  else:
    cq, ckv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    shapes.update({"wq": ((d, cq), "matrix"), "wk": ((d, ckv), "matrix"),
                   "wv": ((d, ckv), "matrix"), "wo": ((cq, d), "matrix"),
                   "q_norm": ((hd,), "gain"), "k_norm": ((hd,), "gain")})
  if ffn == DENSE:
    f = cfg.intermediate_size
    return {**shapes, "w_gate": ((d, f), "matrix"),
            "w_up": ((d, f), "matrix"), "w_down": ((f, d), "matrix")}
  f, held = cfg.moe_intermediate_size, cfg.experts_held[1]
  shapes.update({"router": ((d, cfg.num_experts), "matrix"),
                 "w_gate": ((held, d, f), "matrix"),
                 "w_up": ((held, d, f), "matrix"),
                 "w_down": ((held, f, d), "matrix")})
  if cfg.use_expert_bias:
    shapes["expert_bias"] = ((cfg.num_experts,), "bias")
  return shapes


def _conv_taps(key, shape, dtype=jnp.float32):
  """PyTorch's ``Conv1d`` default for a depthwise kernel: uniform within
  ``taps ** -0.5``."""
  bound = shape[0] ** -0.5
  return jax.random.uniform(key, shape, dtype, -bound, bound)


INITIALISERS = {"matrix": nn.initializers.normal(0.02),
                "gain": nn.initializers.ones, "conv": _conv_taps,
                "bias": nn.initializers.zeros}


class Lfm2Moe(nn.Module):
  """``__call__(numerical, cats, emb_acts=[rows [B, L, d]])`` ->
  ``{"logits" [B, L, V], "weight" [B, L]}``: ``weight`` is 1 where the next
  token belongs to the same document, 0 at a document's last token (and
  ``"moe"``, the expert layers' counters stacked, where
  ``with_counters``)."""

  config: Lfm2MoeConfig
  with_counters: bool = False

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    del cats
    cfg = self.config
    if emb_acts is None or len(emb_acts) != 1:
      raise ValueError("Lfm2Moe takes its token rows as one sequence input: "
                       "emb_acts=[rows [B, L, hidden_size]]")
    (x,) = emb_acts
    layers = [{name: self.param(f"layer_{i}_{name}", INITIALISERS[leaf],
                                shape)
               for name, (shape, leaf) in layer_shapes(cfg, *kinds).items()}
              for i, kinds in enumerate(cfg.kinds)]
    embedding_norm = self.param("embedding_norm", nn.initializers.ones,
                                (cfg.hidden_size,))
    head = self.param("head", INITIALISERS["matrix"],
                      (cfg.hidden_size, cfg.vocab_size))

    seg = document_segments(numerical, cfg.mean_document_length)
    counters = []
    # one layer's activations at a time, plus what layers/remat.py names:
    # the rest of the other layers is recomputed
    for kinds, p in zip(cfg.kinds, layers):
      x, c = checkpoint_layer(functools.partial(decoder_layer, cfg, *kinds))(
          p, x, seg)
      if c is not None:
        counters.append(c)
    with jax.named_scope(scopes.LM_HEAD):
      logits = mxu_dot(rms_norm(x, embedding_norm, cfg.norm_eps), head)
    same = jnp.pad(seg[:, 1:] == seg[:, :-1], ((0, 0), (0, 1)))
    out = {"logits": logits, "weight": same.astype(logits.dtype)}
    if self.with_counters and counters:
      out["moe"] = jax.tree_util.tree_map(lambda *c: jnp.stack(c), *counters)
    return out
