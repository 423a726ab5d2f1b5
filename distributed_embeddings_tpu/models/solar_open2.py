"""Solar-Open2 (``model_type`` ``solar_open2``): a decoder whose mixers are
mostly recurrent, three Kimi-Delta-Attention layers (a delta rule whose
decay is PER KEY CHANNEL) to one gated grouped-query softmax-attention layer
without positions, every layer over sigmoid-routed experts beside a shared
one, on the training path over sequences of packed documents.

Per layer, pre-norm, on a residual stream ``x [B, L, d]``:
``x += mixer(RMSNorm(x; input_norm))``, then
``x += experts(RMSNorm(x; post_attention_norm))``; a final RMSNorm and an
untied head. ``gqa_layers`` names the published layers whose mixer is
attention; the others' is the delta rule. ``layers_here`` names the
published layers that run here (leaves ``layer_<i>_*`` for the ``i``-th of
them).

*KDA* (``fla``'s ``KimiDeltaAttention``), from the normalised input ``u``:
``q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))``, a
causal depthwise convolution of ``short_conv_kernel_size`` taps a channel;
per head ``q^ = l2norm(q) d_k^-1/2``, ``k^ = l2norm(k)``;
``g = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)``, ``[B, L, H, d_k]``:
one log-decay a key channel, through a low-rank pair (``kda_use_full_proj``
false); ``beta = sigmoid(u W_b)``, times 2 under ``kda_allow_neg_eigval``.
The rule is :func:`..layers.gated_delta.chunk_kda_rule`; its output goes
through ``RMSNorm_{d_v}(o) * sigmoid((u W_ga) W_gb + b_g)`` and ``W_o``.

*Attention* (``use_rope`` false: no rotary pass, no positions at all; they
come from the recurrent layers): ``q, k, v = u W_q, u W_k, u W_v`` over
``num_attention_heads`` query heads on ``num_key_value_heads`` key-value
heads, causal attention at scale ``head_dim^-1/2`` inside a document, then
``(sigmoid(u W_g) * o) W_o`` (``use_gqa_gate``: a gate a channel from the
layer's input). That is :mod:`..layers.attention`'s pair under ``Causal()``
in its grouped ``[B, L, Hkv, G, hd]`` layout, the documents as segment ids;
``attention="xla"`` names the path that runs without a TPU (tests).

*Experts*: ``s = sigmoid(h W_r)`` over all experts in float32; the
``num_experts_per_tok`` largest of ``s + expert_bias`` are chosen; their
weights are the unbiased ``s``, renormalised to 1 (``norm_topk_prob``) and
times ``routed_scaling_factor``; :func:`..layers.moe.moe_share` computes the
experts this chip holds and :func:`..layers.moe.shared_expert` is added for
every token.

*A head share.* Both mixers hold ``heads_held = (first, count)`` of the
published heads and compute ``W_o`` over those alone (one chip of a
tensor-parallel group, without its all-reduce): KDA's per-head leaves are
the held heads' columns (``W_q, W_k, W_v, W_fb, W_gb, b_g, W_b``, the
convolutions, ``A_log``, ``dt_bias``) and rows (``W_o``), its ``W_fa``,
``W_ga`` and ``o_norm`` are whole on every chip; attention holds the query
heads and the key-value heads they read (whole groups). An expert share is
``experts_held``. What absent heads and experts would add is left out.

*Packed documents* (:mod:`..layers.decoder`: where they start is the batch's
numerical features). A document's first token resets the rule's state and the
convolution's window, and attention stays inside a document. The loss is
:func:`..layers.decoder.next_token_loss`, imported here as this model's own.

The plain products are :func:`..layers.dense.mxu_dot`; the router's is
float32 at ``highest`` (:func:`..layers.moe.route`); the rule itself stays at
``highest``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..layers.attention import (
    Causal,
    attention_path,
    attention_splash,
    attention_xla,
)
# `next_token_loss` is this model's loss: the benchmark's family makes its
# step with `models.solar_open2.next_token_loss`
from ..layers.decoder import document_segments, next_token_loss, rms_norm
from ..layers.dense import mxu_dot
from ..layers.gated_delta import causal_conv, chunk_kda_rule, l2_norm
from ..layers.moe import MoEShare, Router, moe_share, shared_expert
from ..layers.remat import KDA_LATENTS, checkpoint_layer
from ..telemetry import scopes

KDA, GQA = "kda", "gqa"


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
  """Widths as the published ``config.json`` names them
  (``linear_attn_config`` flattened to ``linear_*``), and the share of the
  model that lives here."""
  hidden_size: int = 4096
  moe_intermediate_size: int = 1280
  num_attention_heads: int = 64
  num_key_value_heads: int = 8
  head_dim: int = 128
  linear_num_heads: int = 64
  linear_head_dim: int = 128            # of keys and of values; the rank of
                                        # both low-rank pairs
  short_conv_kernel_size: int = 4
  kda_use_full_proj: bool = False
  kda_allow_neg_eigval: bool = True
  use_rope: bool = False
  use_gqa_gate: bool = True
  gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
  n_routed_experts: int = 320
  n_shared_experts: int = 1
  num_experts_per_tok: int = 8
  norm_topk_prob: bool = True
  routed_scaling_factor: float = 1.0
  first_k_dense_replace: int = 0
  num_hidden_layers: int = 48
  rms_norm_eps: float = 1e-5
  layers_here: Tuple[int, ...] = tuple(range(48))   # published numbers
  vocab_size: int = 196608              # rows of the head (a slice: fewer)
  heads_held: Tuple[int, int] = (0, 64)  # (first, count) of every mixer
  experts_held: Tuple[int, int] = (0, 320)
  seq_len: int = 8192
  mean_document_length: int = 4096
  chunk: int = 64                       # tokens a step of the chunked rule
  attention: str = "splash"             # splash: the TPU's kernel | xla: tests

  def __post_init__(self):
    for layer in self.layers_here:
      if not 0 <= layer < self.num_hidden_layers:
        raise ValueError(f"layers_here names layer {layer} of "
                         f"{self.num_hidden_layers}")
    if self.num_attention_heads != self.linear_num_heads:
      raise ValueError("heads_held is one range of both mixers' heads: "
                       f"{self.num_attention_heads} attention heads, "
                       f"{self.linear_num_heads} of the rule")
    first, count = self.heads_held
    if not 0 <= first < first + count <= self.num_attention_heads:
      raise ValueError(f"heads_held {self.heads_held} of "
                       f"{self.num_attention_heads} heads")
    if self.num_attention_heads % self.num_key_value_heads \
        or first % self.group or count % self.group:
      raise ValueError(f"heads_held {self.heads_held}: whole groups of "
                       f"{self.num_attention_heads} query heads over "
                       f"{self.num_key_value_heads} key-value heads")
    # what the equations above do not cover is refused, not guessed
    for key, want in (("kda_use_full_proj", False), ("use_rope", False),
                      ("use_gqa_gate", True), ("first_k_dense_replace", 0)):
      if getattr(self, key) != want:
        raise ValueError(f"{key}={getattr(self, key)!r}: this model is "
                         f"written for {want!r}")

  @property
  def group(self) -> int:
    """Query heads a key-value head."""
    return self.num_attention_heads // self.num_key_value_heads

  @property
  def kinds(self) -> Tuple[str, ...]:
    """The mixer of every layer that runs here."""
    return tuple(GQA if layer in self.gqa_layers else KDA
                 for layer in self.layers_here)

  @property
  def share(self) -> MoEShare:
    """This chip's share of every expert layer, and the layers' router."""
    return MoEShare(
        self.n_routed_experts, self.num_experts_per_tok,
        tuple(self.experts_held),
        Router("sigmoid", bool(self.norm_topk_prob),
               float(self.routed_scaling_factor), selection_bias=True))


def kda_mixer(cfg: SolarOpen2Config, p, u, seg):
  """The heads held here of one KDA mixer on its normalised input
  ``u [B, L, d]`` -> their part of ``o Wo``, ``[B, L, d]``."""
  b, length, _ = u.shape
  h, hd = cfg.heads_held[1], cfg.linear_head_dim

  def proj(x, w):
    with jax.named_scope(scopes.LINATTN_PROJ):
      return mxu_dot(x, p[w])

  def short(w, conv):
    y = proj(u, w)
    with jax.named_scope(scopes.LINATTN_CONV):
      return jax.nn.silu(causal_conv(y, p[conv], seg)).reshape(
          b, length, h, hd)

  def low_rank(first, second):
    """``(u W_a) W_b``; the plan keeps the 128 columns ``u W_a`` makes."""
    return mxu_dot(checkpoint_name(mxu_dot(u, p[first]), KDA_LATENTS),
                   p[second])

  q, k, v = short("wq", "conv_q"), short("wk", "conv_k"), short("wv", "conv_v")
  with jax.named_scope(scopes.LINATTN_GATE):
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        low_rank("w_fa", "w_fb") + p["dt_bias"]
    ).reshape(b, length, h, hd)
    beta = jax.nn.sigmoid(mxu_dot(u, p["wb"]))
    if cfg.kda_allow_neg_eigval:
      beta = 2.0 * beta
  o, _ = chunk_kda_rule(l2_norm(q) * hd ** -0.5, l2_norm(k), v, g, beta, seg,
                        cfg.chunk)
  with jax.named_scope(scopes.LINATTN_GATE):
    gate = jax.nn.sigmoid(low_rank("w_ga", "w_gb") + p["b_g"]).reshape(
        b, length, h, hd)
    o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps) * gate
  return proj(o.reshape(b, length, h * hd), "wo")


def gqa_mixer(cfg: SolarOpen2Config, p, u, seg):
  """The heads held here of one attention mixer on its normalised input
  ``u [B, L, d]`` -> their part of ``(gate * o) Wo``, ``[B, L, d]``."""
  b, length, _ = u.shape
  hd, group = cfg.head_dim, cfg.group
  hkv = cfg.heads_held[1] // group

  def proj(x, w):
    with jax.named_scope(scopes.ATTN_PROJ):
      return mxu_dot(x, p[w])

  q = proj(u, "wq").reshape(b, length, hkv, group, hd)
  with jax.named_scope(scopes.ATTN_QK):
    q = q * hd ** -0.5
  k = proj(u, "wk").reshape(b, length, hkv, hd)
  v = proj(u, "wv").reshape(b, length, hkv, hd)
  attend = attention_path(cfg.attention, attention_xla, attention_splash)
  with jax.named_scope(scopes.ATTN_CORE):
    o = attend(q, k, v, Causal(), seg)
  gate = jax.nn.sigmoid(proj(u, "wg"))
  return proj(gate * o.reshape(b, length, hkv * group * hd), "wo")


def decoder_layer(cfg: SolarOpen2Config, kind: str, p, x, seg):
  """One layer of mixer ``kind`` on ``x [B, L, d]`` with its parameters
  ``p`` -> (``x``, the expert layer's counters)."""
  b, length, d = x.shape
  mixer, scope = (kda_mixer, scopes.LINEAR_ATTENTION) if kind == KDA \
      else (gqa_mixer, scopes.ATTENTION)
  with jax.named_scope(scope):
    x = x + mixer(cfg, p, rms_norm(x, p["input_norm"], cfg.rms_norm_eps),
                  seg)
  with jax.named_scope(scopes.MOE):
    h = rms_norm(x, p["post_attention_norm"], cfg.rms_norm_eps).reshape(
        b * length, d)
  y, counters = moe_share(h, p["router"], p["w_gate"], p["w_up"],
                          p["w_down"], cfg.share, p["expert_bias"])
  y = y + shared_expert(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
  return x + y.reshape(b, length, d), counters


def layer_shapes(cfg: SolarOpen2Config, kind: str
                 ) -> Dict[str, Tuple[Any, str]]:
  """name -> (shape, kind of leaf) of one layer's parameters: ``matrix``,
  ``gain`` (starts at 1), ``bias`` (starts at 0), ``conv`` (taps x
  channels), ``a_log`` (a head), ``dt_bias`` (a channel)."""
  d, h = cfg.hidden_size, cfg.heads_held[1]
  f, held = cfg.moe_intermediate_size, cfg.experts_held[1]
  fs = cfg.n_shared_experts * f
  experts = {
      "post_attention_norm": ((d,), "gain"),
      "router": ((d, cfg.n_routed_experts), "matrix"),
      "expert_bias": ((cfg.n_routed_experts,), "bias"),
      "w_gate": ((held, d, f), "matrix"), "w_up": ((held, d, f), "matrix"),
      "w_down": ((held, f, d), "matrix"),
      "shared_gate": ((d, fs), "matrix"), "shared_up": ((d, fs), "matrix"),
      "shared_down": ((fs, d), "matrix")}
  if kind == GQA:
    cq, ckv = h * cfg.head_dim, h // cfg.group * cfg.head_dim
    return {"input_norm": ((d,), "gain"), "wq": ((d, cq), "matrix"),
            "wk": ((d, ckv), "matrix"), "wv": ((d, ckv), "matrix"),
            "wg": ((d, cq), "matrix"), "wo": ((cq, d), "matrix"), **experts}
  hd, taps = cfg.linear_head_dim, cfg.short_conv_kernel_size
  c = h * hd
  return {"input_norm": ((d,), "gain"), "wq": ((d, c), "matrix"),
          "wk": ((d, c), "matrix"), "wv": ((d, c), "matrix"),
          "conv_q": ((taps, c), "conv"), "conv_k": ((taps, c), "conv"),
          "conv_v": ((taps, c), "conv"),
          "w_fa": ((d, hd), "matrix"), "w_fb": ((hd, c), "matrix"),
          "a_log": ((h,), "a_log"), "dt_bias": ((c,), "dt_bias"),
          "wb": ((d, h), "matrix"),
          "w_ga": ((d, hd), "matrix"), "w_gb": ((hd, c), "matrix"),
          "b_g": ((c,), "bias"), "o_norm": ((hd,), "gain"),
          "wo": ((c, d), "matrix"), **experts}


def _uniform(lo: float, hi: float):
  return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
      key, shape, dtype, lo, hi)


# ``fla``'s ranges, as models/olmo_hybrid.py takes them: A in e^0 .. e^2, dt
# log-uniform in [0.001, 0.1] and dt_bias its inverse softplus
INITIALISERS = {
    "matrix": nn.initializers.normal(0.02), "gain": nn.initializers.ones,
    "bias": nn.initializers.zeros, "conv": _uniform(-0.5, 0.5),
    "a_log": _uniform(0.0, 2.0), "dt_bias": _uniform(-6.9, -2.3)}


class SolarOpen2(nn.Module):
  """``__call__(numerical, cats, emb_acts=[rows [B, L, d]])`` ->
  ``{"logits" [B, L, V], "weight" [B, L]}``: ``weight`` is 1 where the next
  token belongs to the same document, 0 at a document's last token (and
  ``"moe"``, the expert layers' counters stacked, where
  ``with_counters``)."""

  config: SolarOpen2Config
  with_counters: bool = False

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    del cats
    cfg = self.config
    if emb_acts is None or len(emb_acts) != 1:
      raise ValueError("SolarOpen2 takes its token rows as one sequence "
                       "input: emb_acts=[rows [B, L, hidden_size]]")
    (x,) = emb_acts
    layers = [{name: self.param(f"layer_{i}_{name}", INITIALISERS[leaf], shape)
               for name, (shape, leaf) in layer_shapes(cfg, kind).items()}
              for i, kind in enumerate(cfg.kinds)]
    norm = self.param("norm", nn.initializers.ones, (cfg.hidden_size,))
    head = self.param("head", INITIALISERS["matrix"],
                      (cfg.hidden_size, cfg.vocab_size))

    seg = document_segments(numerical, cfg.mean_document_length)
    counters = []
    # one layer's activations at a time, plus what layers/remat.py names:
    # the rest of the other layers is recomputed
    for kind, p in zip(cfg.kinds, layers):
      x, c = checkpoint_layer(functools.partial(decoder_layer, cfg, kind))(
          p, x, seg)
      counters.append(c)
    with jax.named_scope(scopes.LM_HEAD):
      logits = mxu_dot(rms_norm(x, norm, cfg.rms_norm_eps), head)
    same = jnp.pad(seg[:, 1:] == seg[:, :-1], ((0, 0), (0, 1)))
    out = {"logits": logits, "weight": same.astype(logits.dtype)}
    if self.with_counters:
      out["moe"] = jax.tree_util.tree_map(lambda *c: jnp.stack(c), *counters)
    return out
