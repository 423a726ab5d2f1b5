"""Olmo-Hybrid (``model_type`` ``olmo_hybrid``): a decoder whose mixers are
mostly recurrent, three gated-delta-rule layers to one full-attention layer,
on the training path over sequences of packed documents.

Per layer, on a residual stream ``x [B, L, d]``, as the family's Olmo 2 /
Olmo 3 blocks do it: ``x += RMSNorm(mixer(x))``, then
``x += RMSNorm(mlp(x))``; a sublayer's OUTPUT is normalised, its input is not.
``layer_types`` names each layer's mixer.

*Linear attention* (the gated delta rule). From the input ``u``:
``q~, k~ = u Wq, u Wk`` (``H x d_k``), ``v~, z = u Wv, u Wg`` (``H x d_v``),
``b, a = u Wb, u Wa`` (``H``). Each channel of ``q~, k~, v~`` passes a causal
depthwise convolution of ``linear_conv_kernel_dim`` taps and SiLU. Per head
``q = l2norm(q~) d_k^-1/2``, ``k = l2norm(k~)``; ``beta = sigmoid(b)``, times 2
under ``linear_allow_neg_eigval``; ``g = -exp(A_log) softplus(a + dt_bias)``.
The rule itself is :func:`..layers.gated_delta.chunk_gated_delta_rule`; its
output goes through ``RMSNorm_{d_v}(o) * SiLU(z)`` and ``Wo``.

*Full attention*: ``q, k, v = u Wq, u Wk, u Wv`` without bias, RMSNorm on
``q`` and on ``k`` across all the channels held, no rotary embedding (the
published ``rope_theta`` is ``null``: position comes from the recurrent
layers), causal attention at scale ``head_dim^-1/2`` inside a document, ``Wo``.
That is :mod:`..layers.attention`'s pair under ``Causal()`` in its
``[B, L, H, hd]`` layout, the documents as segment ids; ``attention="xla"``
names the path that runs without a TPU (tests).

*MLP*: ``(SiLU(u Wgate) * (u Wup)) Wdown``.

*A head share.* Both mixers hold ``heads_held = (first, count)`` of the
published heads and compute ``Wo`` over those alone (one chip of a
tensor-parallel group, without its all-reduce): the partial sum goes on. The
q/k norm of the full-attention mixer is taken across the channels held.

*Packed documents* (:mod:`..layers.decoder`: where they start is the batch's
numerical features). A document's first token resets the rule's state and the
convolution's window, and attention stays inside a document. The loss is
:func:`..layers.decoder.next_token_loss`, imported here as this model's own.

On the sparse train step the token table is a sequence input
(``TableConfig(combiner=None)`` read at hotness ``L``): ``emb_acts`` is
``[rows [B, L, d]]``.

Both mixers' projections, the MLP's and the head's products are
:func:`..layers.dense.mxu_dot`: on a TPU handed bfloat16 operands, float32
out of both passes; the rule itself stays at ``highest``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..layers.attention import (
    Causal,
    attention_path,
    attention_splash,
    attention_xla,
)
# `next_token_loss` is this model's loss: the benchmark's families make their
# step with `models.olmo_hybrid.next_token_loss`
from ..layers.decoder import document_segments, next_token_loss, rms_norm
from ..layers.dense import mxu_dot
from ..layers.gated_delta import (
    causal_conv,
    chunk_gated_delta_rule,
    l2_norm,
)
from ..layers.remat import checkpoint_layer
from ..telemetry import scopes

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
  """Widths as the published ``config.json`` names them, and the share of
  the model that lives here."""
  hidden_size: int = 3840
  intermediate_size: int = 11008
  num_attention_heads: int = 30         # published, of both kinds of mixer
  head_dim: int = 128
  linear_key_head_dim: int = 96
  linear_value_head_dim: int = 192
  linear_conv_kernel_dim: int = 4
  linear_allow_neg_eigval: bool = True
  rms_norm_eps: float = 1e-6
  layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
  vocab_size: int = 100352              # rows of the head (a slice: fewer)
  heads_held: Tuple[int, int] = (0, 30)  # (first, count) of every mixer
  seq_len: int = 8192
  mean_document_length: int = 2048
  chunk: int = 64                       # tokens a step of the chunked rule
  attention: str = "splash"             # splash: the TPU's kernel | xla: tests

  def __post_init__(self):
    first, count = self.heads_held
    if not 0 <= first < first + count <= self.num_attention_heads:
      raise ValueError(f"heads_held {self.heads_held} of "
                       f"{self.num_attention_heads} heads")
    stray = set(self.layer_types) - {LINEAR, FULL}
    if stray:
      raise ValueError(f"layer_types names {sorted(stray)}: "
                       f"{LINEAR} or {FULL}")


def linear_attention_mixer(cfg: OlmoHybridConfig, p, u, seg):
  """The heads held here of one gated-delta-rule mixer, ``u [B, L, d]`` ->
  their part of ``o Wo``, ``[B, L, d]``."""
  b, length, _ = u.shape
  h, dk, dv = cfg.heads_held[1], cfg.linear_key_head_dim, \
      cfg.linear_value_head_dim

  def proj(x, w):
    with jax.named_scope(scopes.LINATTN_PROJ):
      return mxu_dot(x, p[w])

  def short(x, w):
    with jax.named_scope(scopes.LINATTN_CONV):
      return jax.nn.silu(causal_conv(x, p[w], seg))

  q = short(proj(u, "wq"), "conv_q").reshape(b, length, h, dk)
  k = short(proj(u, "wk"), "conv_k").reshape(b, length, h, dk)
  v = short(proj(u, "wv"), "conv_v").reshape(b, length, h, dv)
  z = proj(u, "wg").reshape(b, length, h, dv)
  beta = jax.nn.sigmoid(proj(u, "wb"))
  if cfg.linear_allow_neg_eigval:
    beta = 2.0 * beta
  g = -jnp.exp(p["a_log"]) * jax.nn.softplus(proj(u, "wa") + p["dt_bias"])
  o, _ = chunk_gated_delta_rule(l2_norm(q) * dk ** -0.5, l2_norm(k), v, g,
                                beta, seg, cfg.chunk)
  o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps) * jax.nn.silu(z)
  return proj(o.reshape(b, length, h * dv), "wo")


def full_attention_mixer(cfg: OlmoHybridConfig, p, u, seg):
  """The heads held here of one full-attention mixer -> their part of
  ``o Wo``, ``[B, L, d]``."""
  b, length, _ = u.shape
  h, hd = cfg.heads_held[1], cfg.head_dim

  def proj(x, w):
    with jax.named_scope(scopes.ATTN_PROJ):
      return mxu_dot(x, p[w])

  q = proj(u, "wq")
  with jax.named_scope(scopes.ATTN_QK):
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps) * hd ** -0.5
  k = proj(u, "wk")
  with jax.named_scope(scopes.ATTN_QK):
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
  heads = lambda x: x.reshape(b, length, h, hd)
  attend = attention_path(cfg.attention, attention_xla, attention_splash)
  q, k, v = heads(q), heads(k), heads(proj(u, "wv"))
  with jax.named_scope(scopes.ATTN_CORE):
    o = attend(q, k, v, Causal(), seg)
  return proj(o.reshape(b, length, h * hd), "wo")


def decoder_layer(cfg: OlmoHybridConfig, kind: str, p, x, seg):
  """One layer of ``kind`` on ``x [B, L, d]`` with its parameters ``p``."""
  mixer, scope = (linear_attention_mixer, scopes.LINEAR_ATTENTION) \
      if kind == LINEAR else (full_attention_mixer, scopes.ATTENTION)
  with jax.named_scope(scope):
    x = x + rms_norm(mixer(cfg, p, x, seg), p["mixer_norm"], cfg.rms_norm_eps)
  with jax.named_scope(scopes.MLP):
    y = mxu_dot(jax.nn.silu(mxu_dot(x, p["w_gate"]))
                * mxu_dot(x, p["w_up"]), p["w_down"])
    return x + rms_norm(y, p["mlp_norm"], cfg.rms_norm_eps)


def layer_shapes(cfg: OlmoHybridConfig, kind: str):
  """name -> (shape, kind of leaf) of one layer's parameters: ``matrix``,
  ``gain`` (starts at 1), ``conv`` (taps x channels), ``a_log``, ``dt_bias``
  (a head each)."""
  d, f, h = cfg.hidden_size, cfg.intermediate_size, cfg.heads_held[1]
  mlp = {"mixer_norm": ((d,), "gain"), "w_gate": ((d, f), "matrix"),
         "w_up": ((d, f), "matrix"), "w_down": ((f, d), "matrix"),
         "mlp_norm": ((d,), "gain")}
  if kind == FULL:
    c = h * cfg.head_dim
    return {"wq": ((d, c), "matrix"), "wk": ((d, c), "matrix"),
            "wv": ((d, c), "matrix"), "wo": ((c, d), "matrix"),
            "q_norm": ((c,), "gain"), "k_norm": ((c,), "gain"), **mlp}
  ck, cv, taps = h * cfg.linear_key_head_dim, h * cfg.linear_value_head_dim, \
      cfg.linear_conv_kernel_dim
  return {"wq": ((d, ck), "matrix"), "wk": ((d, ck), "matrix"),
          "wv": ((d, cv), "matrix"), "wg": ((d, cv), "matrix"),
          "wb": ((d, h), "matrix"), "wa": ((d, h), "matrix"),
          "conv_q": ((taps, ck), "conv"), "conv_k": ((taps, ck), "conv"),
          "conv_v": ((taps, cv), "conv"), "a_log": ((h,), "a_log"),
          "dt_bias": ((h,), "dt_bias"),
          "o_norm": ((cfg.linear_value_head_dim,), "gain"),
          "wo": ((cv, d), "matrix"), **mlp}


def _uniform(lo: float, hi: float):
  return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
      key, shape, dtype, lo, hi)


# ``fla``'s ranges: A uniform in (0, 16), here e^0 .. e^2; dt log-uniform in
# [0.001, 0.1] and dt_bias its inverse softplus, about log dt. So a seeded
# head's decay a token, exp(-A softplus(dt_bias)), lies in 0.48 .. 0.999
INITIALISERS = {
    "matrix": nn.initializers.normal(0.02), "gain": nn.initializers.ones,
    "conv": _uniform(-0.5, 0.5), "a_log": _uniform(0.0, 2.0),
    "dt_bias": _uniform(-6.9, -2.3)}


class OlmoHybrid(nn.Module):
  """``__call__(numerical, cats, emb_acts=[rows [B, L, d]])`` ->
  ``{"logits" [B, L, V], "weight" [B, L]}``: ``weight`` is 1 where the next
  token belongs to the same document, 0 at a document's last token."""

  config: OlmoHybridConfig

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    del cats
    cfg = self.config
    if emb_acts is None or len(emb_acts) != 1:
      raise ValueError("OlmoHybrid takes its token rows as one sequence "
                       "input: emb_acts=[rows [B, L, hidden_size]]")
    (x,) = emb_acts
    layers = [{name: self.param(f"layer_{i}_{name}", INITIALISERS[leaf], shape)
               for name, (shape, leaf) in layer_shapes(cfg, kind).items()}
              for i, kind in enumerate(cfg.layer_types)]
    final_norm = self.param("final_norm", nn.initializers.ones,
                            (cfg.hidden_size,))
    head = self.param("head", INITIALISERS["matrix"],
                      (cfg.hidden_size, cfg.vocab_size))

    seg = document_segments(numerical, cfg.mean_document_length)
    # one layer's activations at a time, plus what layers/remat.py names:
    # the rest of the other layers is recomputed
    for kind, p in zip(cfg.layer_types, layers):
      x = checkpoint_layer(functools.partial(decoder_layer, cfg, kind))(
          p, x, seg)
    with jax.named_scope(scopes.LM_HEAD):
      logits = mxu_dot(rms_norm(x, final_norm, cfg.rms_norm_eps), head)
    same = jnp.pad(seg[:, 1:] == seg[:, :-1], ((0, 0), (0, 1)))
    return {"logits": logits, "weight": same.astype(logits.dtype)}
