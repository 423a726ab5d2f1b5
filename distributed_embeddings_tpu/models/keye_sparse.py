"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``): a Qwen3-MoE
decoder whose every attention layer chooses its keys by a learned indexer
(DeepSeek-Sparse-Attention, sized by the published ``sa_config``), on the
training path over sequences of packed documents.

Per layer, pre-norm, on a residual stream ``x [B, L, d]`` with ``seg`` the
document of each position (positions count from the sequence's start):

*Attention*, the Qwen3-MoE block's: ``h = RMSNorm(x)``;
``q, k, v = h Wq, h Wk, h Wv`` without bias; RMSNorm over the head dimension
on ``q`` and on ``k``; RoPE (rotate-half, every dimension of a head) on both;
grouped queries at scale ``head_dim ** -0.5``. The published
``mrope_section`` splits a head's rotary pairs over three position
components; a text token carries the same position in all three, which is
plain RoPE.

*The indexer* reads ``hd = stop_gradient(h)``: ``qI = hd W_qI``
(``indexer_num_heads`` of ``indexer_head_dim``), one shared key
``kI = LayerNorm(hd W_kI)``, a weight a head
``w = (hd W_w) * indexer_num_heads ** -0.5 * indexer_head_dim ** -0.5``, RoPE
on the first ``indexer_rotary_dim`` dimensions of ``qI`` and ``kI``. Scores,
selection (the ``topk`` best visible keys a query, causal and inside its
document), attention over the selection and the indexer's KL loss are
:func:`..layers.sparse_index.sparse_attention`. ``x += o Wo``.

*The expert layer*: ``h = RMSNorm(x)``; ``x += moe_share(h)``, a softmax
router over all experts, top ``num_experts_per_tok`` renormalised, the
experts this chip holds (:mod:`..layers.moe`).

A final RMSNorm and an untied head. The model returns the logits, the next
token's weight and ``index_kl``, the layers' indexer losses summed;
:func:`sparse_training_loss` is ``next_token_loss + index_kl``, the
sparse-training stage of the published recipe: everything trains, the indexer
by its KL alone (its input and its target are detached), the rest by the
language-model loss alone (a selection has no gradient). One
``value_and_grad`` serves both owners.

The vision tower is not built: the published language-model config sizes
none, and a text-only batch never reaches it. Packed documents are
:mod:`..layers.decoder`'s; ``emb_acts`` is ``[rows [B, L, d]]``.

The plain products are :func:`..layers.dense.mxu_dot` (on a TPU handed
bfloat16 operands, float32 out of both passes), the indexer's projections
among them; what decides the selection, the score product, is float32 at
``highest`` (:mod:`..layers.sparse_index`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..layers.attention import rope, rope_frequencies
from ..layers.decoder import document_segments, next_token_loss, rms_norm
from ..layers.dense import mxu_dot
from ..layers.moe import MoEShare, moe_share
from ..layers.remat import checkpoint_layer
from ..layers.sparse_index import sparse_attention
from ..telemetry import scopes


@dataclasses.dataclass(frozen=True)
class KeyeSparseConfig:
  """Widths as the published ``config.json`` names them (``sa_config``'s keys
  side by side with the others), and the share of the model that lives
  here."""
  hidden_size: int = 2048
  num_attention_heads: int = 32
  num_key_value_heads: int = 4
  head_dim: int = 128
  moe_intermediate_size: int = 768
  num_experts: int = 128
  num_experts_per_tok: int = 8
  rms_norm_eps: float = 1e-6
  rope_theta: float = 1e7
  num_hidden_layers: int = 48
  vocab_size: int = 151936              # rows of the head (a slice: fewer)
  experts_held: Tuple[int, int] = (0, 128)
  indexer_num_heads: int = 16
  indexer_head_dim: int = 64
  indexer_num_kv_heads: int = 1
  topk: int = 2048
  q_chunk_size: int = 512               # queries a tile of the attention
  indexer_rotary_dim: int = 32          # of indexer_head_dim, leading
  seq_len: int = 16384
  mean_document_length: int = 8192

  def __post_init__(self):
    if self.indexer_num_kv_heads != 1:
      raise ValueError("the indexer's keys are one shared head; "
                       f"indexer_num_kv_heads={self.indexer_num_kv_heads}")
    if self.num_attention_heads % self.num_key_value_heads:
      raise ValueError(f"{self.num_attention_heads} query heads over "
                       f"{self.num_key_value_heads} key-value heads")
    if not 0 < self.indexer_rotary_dim <= self.indexer_head_dim \
        or self.indexer_rotary_dim % 2:
      raise ValueError(f"indexer_rotary_dim={self.indexer_rotary_dim} of "
                       f"{self.indexer_head_dim}")

  @property
  def share(self) -> MoEShare:
    """This chip's share of every layer."""
    return MoEShare(self.num_experts, self.num_experts_per_tok,
                    tuple(self.experts_held))


def layer_norm(x, gain, bias, eps):
  """Over the last axis, in float32 at least."""
  dt = jnp.promote_types(x.dtype, jnp.float32)
  wide = x.astype(dt)
  mean = jnp.mean(wide, axis=-1, keepdims=True)
  centred = wide - mean
  var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
  return (centred * jax.lax.rsqrt(var + eps) * gain.astype(dt)
          + bias.astype(dt)).astype(x.dtype)


def indexer_operands(cfg: KeyeSparseConfig, p, h, positions):
  """The layer's normalised input ``h [B, L, d]``, detached here -> ``qI
  [B, L, Hi, di]``, ``kI [B, L, di]``, ``w [B, L, Hi]`` (scaled)."""
  b, length, _ = h.shape
  hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
  hd = jax.lax.stop_gradient(h)
  inv_freq = rope_frequencies(cfg.rope_theta, cfg.indexer_rotary_dim)
  qi = rope(mxu_dot(hd, p["index_wq"]).reshape(b, length, hi, di), positions,
            inv_freq)
  ki = layer_norm(mxu_dot(hd, p["index_wk"]), p["index_norm_gain"],
                  p["index_norm_bias"], cfg.rms_norm_eps)
  ki = rope(ki[:, :, None, :], positions, inv_freq)[:, :, 0, :]
  wi = mxu_dot(hd, p["index_ww"]) * (hi ** -0.5 * di ** -0.5)
  return qi, ki, wi


def decoder_layer(cfg: KeyeSparseConfig, p, x, seg):
  """One layer on ``x [B, L, d]`` with its parameters ``p`` (a dict) ->
  (``x``, the layer's indexer loss, the expert layer's counters, the
  indexer's)."""
  b, length, d = x.shape
  hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
  positions = jnp.arange(length)
  with jax.named_scope(scopes.ATTENTION):
    h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope(scopes.ATTN_PROJ):
      q = mxu_dot(h, p["wq"]).reshape(b, length, hq, hd)
      k = mxu_dot(h, p["wk"]).reshape(b, length, hkv, hd)
      v = mxu_dot(h, p["wv"]).reshape(b, length, hkv, hd)
    inv_freq = rope_frequencies(cfg.rope_theta, hd)
    with jax.named_scope(scopes.ATTN_QK):
      q = rope(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions,
               inv_freq) * (hd ** -0.5)
      k = rope(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions,
               inv_freq)
    with jax.named_scope(scopes.SPARSE_INDEX), \
        jax.named_scope(scopes.INDEX_SCORES):
      qi, ki, wi = indexer_operands(cfg, p, h, positions)
    # the indexer's scores, its selection, the attention under it and the
    # indexer's loss, each under its own scope inside
    o, index_kl, index_counters = sparse_attention(
        q.reshape(b, length, hkv, hq // hkv, hd), k, v, qi, ki, wi, seg,
        topk=cfg.topk, tile=cfg.q_chunk_size)
    with jax.named_scope(scopes.ATTN_PROJ):
      o = mxu_dot(o.reshape(b, length, hq * hd), p["wo"])
    x = x + o
  with jax.named_scope(scopes.MOE):
    h = rms_norm(x, p["moe_norm"], cfg.rms_norm_eps)
  y, counters = moe_share(h.reshape(b * length, d), p["router"], p["w_gate"],
                          p["w_up"], p["w_down"], cfg.share)
  return x + y.reshape(b, length, d), index_kl, counters, index_counters


def layer_shapes(cfg: KeyeSparseConfig):
  """name -> (shape, ``matrix``, ``gain`` or ``bias``) of a layer's
  parameters."""
  d, hq, hkv, hd = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
  f, held = cfg.moe_intermediate_size, cfg.experts_held[1]
  hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
  return {
      "attn_norm": ((d,), "gain"), "wq": ((d, hq * hd), "matrix"),
      "wk": ((d, hkv * hd), "matrix"), "wv": ((d, hkv * hd), "matrix"),
      "wo": ((hq * hd, d), "matrix"), "q_norm": ((hd,), "gain"),
      "k_norm": ((hd,), "gain"),
      "index_wq": ((d, hi * di), "matrix"), "index_wk": ((d, di), "matrix"),
      "index_ww": ((d, hi), "matrix"), "index_norm_gain": ((di,), "gain"),
      "index_norm_bias": ((di,), "bias"),
      "moe_norm": ((d,), "gain"), "router": ((d, cfg.num_experts), "matrix"),
      "w_gate": ((held, d, f), "matrix"), "w_up": ((held, d, f), "matrix"),
      "w_down": ((held, f, d), "matrix")}


INITIALISERS = {"matrix": nn.initializers.normal(0.02),
                "gain": nn.initializers.ones, "bias": nn.initializers.zeros}
# the leaves only the indexer's KL reaches; every other leaf is the
# language-model loss's alone
INDEXER_LEAVES = ("index_wq", "index_wk", "index_ww", "index_norm_gain",
                  "index_norm_bias")


class KeyeSparse(nn.Module):
  """``__call__(numerical, cats, emb_acts=[rows [B, L, d]])`` ->
  ``{"logits" [B, L, V], "weight" [B, L], "index_kl" scalar}``: ``weight`` is
  1 where the next token belongs to the same document; ``index_kl`` the
  layers' indexer losses summed (and, where ``with_counters``, ``"moe"`` and
  ``"index"``, the layers' counters stacked)."""

  config: KeyeSparseConfig
  with_counters: bool = False

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    del cats
    cfg = self.config
    if emb_acts is None or len(emb_acts) != 1:
      raise ValueError("KeyeSparse takes its token rows as one sequence "
                       "input: emb_acts=[rows [B, L, hidden_size]]")
    (x,) = emb_acts
    layers = [{name: self.param(f"layer_{i}_{name}", INITIALISERS[leaf], shape)
               for name, (shape, leaf) in layer_shapes(cfg).items()}
              for i in range(cfg.num_hidden_layers)]
    final_norm = self.param("final_norm", nn.initializers.ones,
                            (cfg.hidden_size,))
    head = self.param("head", INITIALISERS["matrix"],
                      (cfg.hidden_size, cfg.vocab_size))

    seg = document_segments(numerical, cfg.mean_document_length)
    # one layer's activations at a time, plus what layers/remat.py names:
    # the rest of the other layers is recomputed
    layer = checkpoint_layer(functools.partial(decoder_layer, cfg))
    index_kl, moe, index = 0.0, [], []
    for p in layers:
      x, kl, c, ci = layer(p, x, seg)
      index_kl = index_kl + kl
      moe.append(c)
      index.append(ci)
    with jax.named_scope(scopes.LM_HEAD):
      logits = mxu_dot(rms_norm(x, final_norm, cfg.rms_norm_eps), head)
    same = jnp.pad(seg[:, 1:] == seg[:, :-1], ((0, 0), (0, 1)))
    out = {"logits": logits, "weight": same.astype(logits.dtype),
           "index_kl": index_kl}
    if self.with_counters:
      stack = lambda cs: jax.tree_util.tree_map(
          lambda *c: jnp.stack(c), *cs)
      out["moe"], out["index"] = stack(moe), stack(index)
    return out


def sparse_training_loss(outputs, labels):
  """``next_token_loss + index_kl``: ``outputs`` as :class:`KeyeSparse`
  returns them. The two terms own disjoint leaves (module docstring)."""
  return next_token_loss(outputs, labels) + outputs["index_kl"]
