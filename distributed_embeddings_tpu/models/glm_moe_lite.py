"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``): a decoder of latent
attention over sigmoid-routed experts with a multi-token-prediction module
beside it, on the training path over sequences of packed documents.

Per layer, pre-norm, on a residual stream ``x [B, L, d]``:
``x += attn(RMSNorm(x; input_norm))``, then
``x += ffn(RMSNorm(x; post_attention_norm))``; a final RMSNorm and an untied
head.

*Attention* is :func:`..layers.latent_attention.latent_attention`: queries
through a latent of ``q_lora_rank``, keys and values through one of
``kv_lora_rank``, a norm on each latent, heads of ``qk_nope_head_dim +
qk_rope_head_dim`` for ``q k^T`` and ``v_head_dim`` for ``P v``, RoPE on the
rotary part alone and that part of the key ONE head shared by all;
``attention="xla"`` names the path that runs without a TPU (tests).

*Dense MLP* (layers below ``first_k_dense_replace``):
``(SiLU(h W_gate) * (h W_up)) W_down`` at ``intermediate_size``.

*Experts* (the others): ``s = sigmoid(h W_r)`` over all experts in float32;
the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` are
chosen (``topk_method`` ``noaux_tc`` in one group); their weights are the
unbiased ``s``, renormalised to 1 and times ``routed_scaling_factor``;
:func:`..layers.moe.moe_share` computes the experts this chip holds, and a
shared expert of ``n_shared_experts x moe_intermediate_size``
(:func:`..layers.moe.shared_expert`) is added for every token. The bias
enters the choice alone: its gradient is zero and an optimizer step from
zero moments leaves it where it was.

*Multi-token prediction* (:func:`mtp_module`, ``num_nextn_predict_layers``
1; DeepSeek-V3's module, which the family inherits): with ``e_j`` the
table's row of token ``j`` and ``x_i`` the trunk's output BEFORE its final
norm, ``z_i = [RMSNorm(e_{i+1}; enorm) ; RMSNorm(x_i; hnorm)] W_eh``, then a
whole expert layer of the published shape with weights of its own over the
same documents and positions, then ``RMSNorm(z; mtp_norm) W_head`` with THE
TRUNK'S HEAD, the same leaf: position ``i`` predicts token ``i + 2``. The
token rows are the model's one sequence input, read as they are by the trunk
and shifted by one here, so a table row's gradient is the sum of both uses;
``e`` beyond the sequence's end is zeros at a position of weight 0.

Which of the published layers run here is ``layers_here`` (leaves
``layer_<i>_*`` for the ``i``-th of them); the module's leaves are ``mtp_*``.

*Packed documents* are :mod:`..layers.decoder`'s. The outputs are
``{"logits", "weight", "mtp_logits", "mtp_weight"}``: ``weight`` 1 where
position ``i + 1`` is of ``i``'s document, ``mtp_weight`` 1 where ``i + 1``
and ``i + 2`` both are. :func:`mtp_training_loss` is
``CE(logits, t_{i+1}) + weight * CE(mtp_logits, t_{i+2})``, each a mean over
its own weights, over ``labels = {"targets", "targets_2"}``.

The plain products are :func:`..layers.dense.mxu_dot`; the router's is
float32 at ``highest`` (:func:`..layers.moe.route`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..layers.attention import attention_path, attention_splash, attention_xla
from ..layers.decoder import document_segments, next_token_loss, rms_norm
from ..layers.dense import mxu_dot
from ..layers.latent_attention import LatentShapes, latent_attention
from ..layers.moe import MoEShare, Router, moe_share, shared_expert
from ..layers.remat import checkpoint_layer
from ..telemetry import scopes

DENSE, EXPERTS = "dense", "experts"
# the second term's weight: DeepSeek-V3's first-stage value (the published
# config names none)
MTP_LOSS_WEIGHT = 0.3


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
  """Widths as the published ``config.json`` names them, and the share of
  the model that lives here."""
  hidden_size: int = 2048
  intermediate_size: int = 10240
  moe_intermediate_size: int = 1536
  num_attention_heads: int = 20
  q_lora_rank: int = 768
  kv_lora_rank: int = 512
  qk_nope_head_dim: int = 192
  qk_rope_head_dim: int = 64
  v_head_dim: int = 256
  n_routed_experts: int = 64
  n_shared_experts: int = 1
  num_experts_per_tok: int = 4
  norm_topk_prob: bool = True
  routed_scaling_factor: float = 1.8
  first_k_dense_replace: int = 1
  num_hidden_layers: int = 47
  num_nextn_predict_layers: int = 1
  rms_norm_eps: float = 1e-5
  rope_theta: float = 1e6
  layers_here: Tuple[int, ...] = tuple(range(47))   # published numbers
  vocab_size: int = 154880              # rows of the head (a slice: fewer)
  experts_held: Tuple[int, int] = (0, 64)
  seq_len: int = 8192
  mean_document_length: int = 4096
  attention: str = "splash"             # splash: the TPU's kernel | xla: tests

  def __post_init__(self):
    for layer in self.layers_here:
      if not 0 <= layer < self.num_hidden_layers:
        raise ValueError(f"layers_here names layer {layer} of "
                         f"{self.num_hidden_layers}")
    if self.num_nextn_predict_layers != 1:
      raise ValueError(
          f"num_nextn_predict_layers={self.num_nextn_predict_layers}: one "
          "prediction module, neither none nor a chain of them")

  @property
  def kinds(self) -> Tuple[str, ...]:
    """The feed-forward of every trunk layer that runs here."""
    return tuple(DENSE if layer < self.first_k_dense_replace else EXPERTS
                 for layer in self.layers_here)

  @property
  def latent(self) -> LatentShapes:
    return LatentShapes(
        self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
        self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
        self.rms_norm_eps, self.rope_theta)

  @property
  def share(self) -> MoEShare:
    """This chip's share of every expert layer, and the layers' router."""
    return MoEShare(
        self.n_routed_experts, self.num_experts_per_tok,
        tuple(self.experts_held),
        Router("sigmoid", bool(self.norm_topk_prob),
               float(self.routed_scaling_factor), selection_bias=True))


def decoder_layer(cfg: GlmMoeLiteConfig, ffn: str, p, x, seg):
  """One layer of feed-forward ``ffn`` on ``x [B, L, d]`` with its
  parameters ``p`` -> (``x``, the expert layer's counters or ``None``)."""
  b, length, d = x.shape
  attend = attention_path(cfg.attention, attention_xla, attention_splash)
  with jax.named_scope(scopes.ATTENTION):
    h = rms_norm(x, p["input_norm"], cfg.rms_norm_eps)
    x = x + latent_attention(cfg.latent, p, h, jnp.arange(length), seg, attend)
  if ffn == DENSE:
    with jax.named_scope(scopes.MLP):
      h = rms_norm(x, p["post_attention_norm"], cfg.rms_norm_eps)
      y = mxu_dot(jax.nn.silu(mxu_dot(h, p["w_gate"]))
                  * mxu_dot(h, p["w_up"]), p["w_down"])
    return x + y, None
  with jax.named_scope(scopes.MOE):
    h = rms_norm(x, p["post_attention_norm"], cfg.rms_norm_eps).reshape(
        b * length, d)
  y, counters = moe_share(h, p["router"], p["w_gate"], p["w_up"],
                          p["w_down"], cfg.share, p["expert_bias"])
  y = y + shared_expert(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
  return x + y.reshape(b, length, d), counters


def mtp_module(cfg: GlmMoeLiteConfig, p, x, rows, seg):
  """The prediction module up to its final norm: the trunk's output ``x``
  (before the trunk's final norm) and the token rows ``rows``, both
  ``[B, L, d]`` -> (``z [B, L, d]``, its expert layer's counters). ``p``:
  ``enorm``, ``hnorm``, ``w_eh`` and the layer's leaves as ``layer_<name>``."""
  with jax.named_scope(scopes.MTP):
    following = jnp.pad(rows[:, 1:], ((0, 0), (0, 1), (0, 0)))   # e_{i+1}
    z = mxu_dot(jnp.concatenate(
        [rms_norm(following, p["enorm"], cfg.rms_norm_eps),
         rms_norm(x, p["hnorm"], cfg.rms_norm_eps)], axis=-1), p["w_eh"])
    layer = {n[len("layer_"):]: w for n, w in p.items()
             if n.startswith("layer_")}
    return decoder_layer(cfg, EXPERTS, layer, z, seg)


def layer_shapes(cfg: GlmMoeLiteConfig, ffn: str
                 ) -> Dict[str, Tuple[Any, str]]:
  """name -> (shape, kind of leaf) of one layer's parameters: ``matrix``,
  ``gain`` (starts at 1), ``bias`` (the selection bias, starts at 0)."""
  d = cfg.hidden_size
  shapes = {"input_norm": ((d,), "gain"), **cfg.latent.leaves(d),
            "post_attention_norm": ((d,), "gain")}
  if ffn == DENSE:
    f = cfg.intermediate_size
    return {**shapes, "w_gate": ((d, f), "matrix"),
            "w_up": ((d, f), "matrix"), "w_down": ((f, d), "matrix")}
  f, held = cfg.moe_intermediate_size, cfg.experts_held[1]
  fs = cfg.n_shared_experts * f
  return {**shapes, "router": ((d, cfg.n_routed_experts), "matrix"),
          "expert_bias": ((cfg.n_routed_experts,), "bias"),
          "w_gate": ((held, d, f), "matrix"),
          "w_up": ((held, d, f), "matrix"),
          "w_down": ((held, f, d), "matrix"),
          "shared_gate": ((d, fs), "matrix"),
          "shared_up": ((d, fs), "matrix"),
          "shared_down": ((fs, d), "matrix")}


def mtp_shapes(cfg: GlmMoeLiteConfig) -> Dict[str, Tuple[Any, str]]:
  """The module's parameters but for the head, which is the trunk's."""
  d = cfg.hidden_size
  return {"enorm": ((d,), "gain"), "hnorm": ((d,), "gain"),
          "w_eh": ((2 * d, d), "matrix"),
          **{f"layer_{n}": leaf
             for n, leaf in layer_shapes(cfg, EXPERTS).items()},
          "norm": ((d,), "gain")}


INITIALISERS = {"matrix": nn.initializers.normal(0.02),
                "gain": nn.initializers.ones, "bias": nn.initializers.zeros}


class GlmMoeLite(nn.Module):
  """``__call__(numerical, cats, emb_acts=[rows [B, L, d]])`` ->
  ``{"logits", "mtp_logits" [B, L, V], "weight", "mtp_weight" [B, L]}``
  (and ``"moe"``, the expert layers' counters stacked, the module's last,
  where ``with_counters``)."""

  config: GlmMoeLiteConfig
  with_counters: bool = False

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    del cats
    cfg = self.config
    if emb_acts is None or len(emb_acts) != 1:
      raise ValueError("GlmMoeLite takes its token rows as one sequence "
                       "input: emb_acts=[rows [B, L, hidden_size]]")
    (rows,) = emb_acts
    param = lambda name, shape, leaf: self.param(name, INITIALISERS[leaf],
                                                 shape)
    layers = [{name: param(f"layer_{i}_{name}", shape, leaf)
               for name, (shape, leaf) in layer_shapes(cfg, ffn).items()}
              for i, ffn in enumerate(cfg.kinds)]
    mtp = {name: param(f"mtp_{name}", shape, leaf)
           for name, (shape, leaf) in mtp_shapes(cfg).items()}
    norm = param("norm", (cfg.hidden_size,), "gain")
    head = param("head", (cfg.hidden_size, cfg.vocab_size), "matrix")

    seg = document_segments(numerical, cfg.mean_document_length)
    x, counters = rows, []
    # one layer's activations at a time, plus what layers/remat.py names:
    # the rest of the other layers, and of the module, is recomputed
    for ffn, p in zip(cfg.kinds, layers):
      x, c = checkpoint_layer(functools.partial(decoder_layer, cfg, ffn))(
          p, x, seg)
      if c is not None:
        counters.append(c)
    z, c = checkpoint_layer(functools.partial(mtp_module, cfg))(
        {n: w for n, w in mtp.items() if n != "norm"}, x, rows, seg)
    counters.append(c)
    with jax.named_scope(scopes.LM_HEAD):
      logits = mxu_dot(rms_norm(x, norm, cfg.rms_norm_eps), head)
    with jax.named_scope(scopes.MTP), jax.named_scope(scopes.LM_HEAD):
      mtp_logits = mxu_dot(rms_norm(z, mtp["norm"], cfg.rms_norm_eps), head)
    same = jnp.pad(seg[:, 1:] == seg[:, :-1], ((0, 0), (0, 1)))
    # i, i + 1 and i + 2 in one document
    both = same & jnp.pad(same[:, 1:], ((0, 0), (0, 1)))
    out = {"logits": logits, "weight": same.astype(logits.dtype),
           "mtp_logits": mtp_logits, "mtp_weight": both.astype(logits.dtype)}
    if self.with_counters:
      out["moe"] = jax.tree_util.tree_map(lambda *c: jnp.stack(c), *counters)
    return out


def mtp_training_loss(outputs, labels, weight: float = MTP_LOSS_WEIGHT):
  """-> (``next_token_loss + weight * mtp_loss``, the two terms by name):
  ``outputs`` as :class:`GlmMoeLite` returns them, ``labels = {"targets",
  "targets_2"}`` the ids shifted by one and by two. The step builders take
  the pair and report the terms in a guarded step's metrics
  (``training.py``); ``jax.value_and_grad(..., has_aux=True)`` does
  elsewhere."""
  first = next_token_loss(outputs, labels)
  with jax.named_scope(scopes.MTP):
    second = next_token_loss(
        {"logits": outputs["mtp_logits"], "weight": outputs["mtp_weight"]},
        {"targets": labels["targets_2"]})
  return first + weight * second, {"next_token_loss": first,
                                   "mtp_loss": second}
