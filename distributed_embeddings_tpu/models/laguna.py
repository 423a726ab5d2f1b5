"""Laguna (``model_type`` ``laguna``): a decoder whose layers differ in
SHAPE, on the training path over sequences of packed documents. Window and
full attention alternate 3 : 1 with different head counts and different
rotary tables, the attention output is gated, the first layer's MLP is dense
and every other layer is a mixture of experts beside a shared expert.

Per layer ``l``, pre-norm, on a residual stream ``x [B, L, d]``:
``x += attn_l(RMSNorm(x))``, then ``x += mlp_l(RMSNorm(x))``; a final RMSNorm
and an untied head.

*Attention.* ``H_l = num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` key-value heads of ``head_dim``, no bias, from the
normalised input ``h``: ``q = rope_l(h Wq)``, ``k = rope_l(h Wk)``,
``v = h Wv``; ``a = softmax(q k^T / sqrt(head_dim) + mask_l) v`` with
``H_l / num_key_value_heads`` query heads a key-value head;
``out = (sigmoid(h Wg) * a) Wo``, the gate one a channel of ``a``. So ``Wq``,
``Wg`` and ``Wo`` take their shape from the layer. ``mask_l`` is causal and
inside a document; on a ``sliding_attention`` layer also
``i - j < sliding_window`` (a query sees itself and the ``sliding_window - 1``
tokens before it). That is :mod:`..layers.attention`'s pair under ``Causal()``
or ``Window(sliding_window)``, the documents as segment ids: the kernel skips
the blocks the window empties, it does not mask them. ``attention="xla"``
names the path that runs without a TPU (tests).

*Two rotary tables* (:func:`rotary_table`, from ``rope_parameters`` by kind
of layer): ``default`` rotates ``partial_rotary_factor * head_dim`` leading
dimensions of a head at ``rope_theta``; ``yarn`` blends those frequencies
with the same divided by ``factor``, dimension by dimension between the
corrections of ``beta_fast`` and ``beta_slow`` turns in
``original_max_position_embeddings``, and multiplies cos and sin by
``attention_factor``. Positions count from the sequence's start.

*MLP.* ``mlp_layer_types[l]`` ``dense``: ``(SiLU(h Wgate) * (h Wup)) Wdown``
at ``intermediate_size``. ``sparse``: a sigmoid score an expert, the
``num_experts_per_tok`` largest, their scores renormalised to 1 and times
``moe_routed_scaling_factor``; ``y = sum_e w_e E_e(h) + E_shared(h)``, every
expert a SwiGLU (:mod:`..layers.moe`: :func:`moe_share` computes the routed
experts this chip holds, :func:`shared_expert` the one every chip computes
for its own tokens).

*Packed documents* and the loss (:func:`..layers.decoder.next_token_loss`)
are :mod:`..layers.decoder`'s; ``emb_acts`` is ``[rows [B, L, d]]``.

The projections', the dense MLP's and the head's products are
:func:`..layers.dense.mxu_dot`: on a TPU handed bfloat16 operands, float32
out of both passes.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Mapping, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..layers.attention import (
    Causal,
    Window,
    attention_path,
    attention_splash,
    attention_xla,
    rope,
    rope_frequencies,
)
from ..layers.decoder import document_segments, rms_norm
from ..layers.dense import mxu_dot
from ..layers.moe import MoEShare, Router, moe_share, shared_expert
from ..layers.remat import checkpoint_layer
from ..telemetry import scopes

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"

# the published rope_parameters of Laguna-XS.2, as hashable pairs
_XS2_ROPE = (
    (FULL, (("rope_theta", 500000.0), ("rope_type", "yarn"), ("factor", 64.0),
            ("original_max_position_embeddings", 4096), ("beta_slow", 1.0),
            ("beta_fast", 64.0), ("attention_factor", 1.4158883083359672),
            ("partial_rotary_factor", 0.5))),
    (SLIDING, (("rope_type", "default"), ("rope_theta", 10000.0),
               ("partial_rotary_factor", 1.0))))


def freeze_rope_parameters(published: Mapping[str, Any]):
  """``rope_parameters`` of a ``config.json`` -> the hashable form
  :class:`LagunaConfig` keeps (kind of layer -> its keys; other keys of the
  published group are not a layer's)."""
  return tuple((kind, tuple(published[kind].items()))
               for kind in (FULL, SLIDING) if kind in published)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
  """Widths as the published ``config.json`` names them, and the share of
  the model that lives here. The per-layer tuples are as long as the
  published model; ``num_hidden_layers`` says how many of them run."""
  hidden_size: int = 2048
  intermediate_size: int = 8192
  num_key_value_heads: int = 8
  head_dim: int = 128
  moe_intermediate_size: int = 512
  shared_expert_intermediate_size: int = 512
  num_experts: int = 256
  num_experts_per_tok: int = 8
  moe_routed_scaling_factor: float = 2.5
  sliding_window: int = 512
  rms_norm_eps: float = 1e-6
  num_hidden_layers: int = 40
  layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING) * 10
  mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
  num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
  rope_parameters: Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...] = \
      _XS2_ROPE
  vocab_size: int = 100352              # rows of the head (a slice: fewer)
  experts_held: Tuple[int, int] = (0, 256)
  seq_len: int = 8192
  mean_document_length: int = 4096
  attention: str = "splash"             # splash: the TPU's kernel | xla: tests

  def __post_init__(self):
    n = self.num_hidden_layers
    for name in ("layer_types", "mlp_layer_types",
                 "num_attention_heads_per_layer"):
      if len(getattr(self, name)) < n:
        raise ValueError(f"{name} names {len(getattr(self, name))} layers "
                         f"of {n}")
    stray = set(self.layer_types) - {SLIDING, FULL}
    if stray:
      raise ValueError(f"layer_types names {sorted(stray)}: "
                       f"{SLIDING} or {FULL}")
    stray = set(self.mlp_layer_types) - {DENSE, SPARSE}
    if stray:
      raise ValueError(f"mlp_layer_types names {sorted(stray)}: "
                       f"{DENSE} or {SPARSE}")
    for heads in self.num_attention_heads_per_layer[:n]:
      if heads % self.num_key_value_heads:
        raise ValueError(f"{heads} query heads over "
                         f"{self.num_key_value_heads} key-value heads")

  @property
  def share(self) -> MoEShare:
    """This chip's share of every expert layer, and the layers' router."""
    return MoEShare(
        self.num_experts, self.num_experts_per_tok, tuple(self.experts_held),
        Router("sigmoid", True, float(self.moe_routed_scaling_factor)))


def rotary_table(cfg: LagunaConfig, kind: str):
  """-> (``inv_freq [rotated width / 2]`` float32, the factor on cos and
  sin) of the layers of ``kind``, from ``rope_parameters[kind]``."""
  p = dict(dict(cfg.rope_parameters)[kind])
  rotary_dim = int(cfg.head_dim * float(p.get("partial_rotary_factor", 1.0)))
  theta = float(p["rope_theta"])
  inv_freq = rope_frequencies(theta, rotary_dim)
  rope_type = p.get("rope_type", "default")
  if rope_type == "default":
    return inv_freq, 1.0
  if rope_type != "yarn":
    raise ValueError(f"rope_type={rope_type!r}: default or yarn")
  factor = float(p["factor"])
  original = int(p["original_max_position_embeddings"])

  def correction_dim(turns: float) -> float:
    """The dimension whose wavelength makes ``turns`` turns in the
    original context."""
    return rotary_dim * math.log(original / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))

  low = max(math.floor(correction_dim(float(p["beta_fast"]))), 0)
  high = min(math.ceil(correction_dim(float(p["beta_slow"]))), rotary_dim - 1)
  if low == high:
    high += 0.001   # the published code's guard against a ramp of no width
  ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float32) - low)
                 / (high - low), 0.0, 1.0)
  # dimensions under `low` keep their frequency (extrapolation), those over
  # `high` have it divided by `factor` (interpolation), a ramp between
  inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
  scale = p.get("attention_factor")
  if scale is None:
    scale = 0.1 * math.log(factor) + 1.0
  return inv_freq.astype(np.float32), float(scale)


def attention_mixer(cfg: LagunaConfig, kind: str, p, h, seg):
  """One layer's attention on its normalised input ``h [B, L, d]`` ->
  ``[B, L, d]``; the head count is ``Wq``'s."""
  b, length, _ = h.shape
  hkv, hd = cfg.num_key_value_heads, cfg.head_dim
  group = p["wq"].shape[1] // (hkv * hd)
  inv_freq, factor = rotary_table(cfg, kind)
  positions = jnp.arange(length)

  def proj(x, w):
    with jax.named_scope(scopes.ATTN_PROJ):
      return mxu_dot(x, p[w])

  q = proj(h, "wq").reshape(b, length, hkv * group, hd)
  with jax.named_scope(scopes.ATTN_QK):
    q = rope(q, positions, inv_freq, factor) * hd ** -0.5
  k = proj(h, "wk").reshape(b, length, hkv, hd)
  with jax.named_scope(scopes.ATTN_QK):
    k = rope(k, positions, inv_freq, factor)
  v = proj(h, "wv").reshape(b, length, hkv, hd)
  mask = Window(cfg.sliding_window) if kind == SLIDING else Causal()
  attend = attention_path(cfg.attention, attention_xla, attention_splash)
  with jax.named_scope(scopes.ATTN_CORE):
    a = attend(q.reshape(b, length, hkv, group, hd), k, v, mask, seg)
  gate = jax.nn.sigmoid(proj(h, "wg"))
  return proj(gate * a.reshape(b, length, hkv * group * hd), "wo")


def decoder_layer(cfg: LagunaConfig, kind: str, mlp: str, p, x, seg):
  """One layer of attention ``kind`` and MLP ``mlp`` on ``x [B, L, d]`` with
  its parameters ``p`` -> (``x``, the expert layer's counters or ``None``)."""
  b, length, d = x.shape
  child = scopes.WINDOW_ATTENTION if kind == SLIDING \
      else scopes.FULL_ATTENTION
  with jax.named_scope(scopes.ATTENTION), jax.named_scope(child):
    h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    x = x + attention_mixer(cfg, kind, p, h, seg)
  if mlp == DENSE:
    with jax.named_scope(scopes.MLP):
      h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
      y = mxu_dot(jax.nn.silu(mxu_dot(h, p["w_gate"]))
                  * mxu_dot(h, p["w_up"]), p["w_down"])
    return x + y, None
  with jax.named_scope(scopes.MOE):
    h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps).reshape(b * length, d)
  y, counters = moe_share(h, p["router"], p["w_gate"], p["w_up"],
                          p["w_down"], cfg.share)
  y = y + shared_expert(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
  return x + y.reshape(b, length, d), counters


def layer_shapes(cfg: LagunaConfig, layer: int) -> Dict[str, Tuple[Any, str]]:
  """name -> (shape, ``matrix`` or ``gain``) of layer ``layer``'s
  parameters: the attention's from its head count, the MLP's from its
  kind."""
  d, hd = cfg.hidden_size, cfg.head_dim
  cq = cfg.num_attention_heads_per_layer[layer] * hd
  ckv = cfg.num_key_value_heads * hd
  shapes = {"attn_norm": ((d,), "gain"), "wq": ((d, cq), "matrix"),
            "wk": ((d, ckv), "matrix"), "wv": ((d, ckv), "matrix"),
            "wg": ((d, cq), "matrix"), "wo": ((cq, d), "matrix"),
            "mlp_norm": ((d,), "gain")}
  if cfg.mlp_layer_types[layer] == DENSE:
    f = cfg.intermediate_size
    return {**shapes, "w_gate": ((d, f), "matrix"),
            "w_up": ((d, f), "matrix"), "w_down": ((f, d), "matrix")}
  f, fs, held = cfg.moe_intermediate_size, \
      cfg.shared_expert_intermediate_size, cfg.experts_held[1]
  return {**shapes, "router": ((d, cfg.num_experts), "matrix"),
          "w_gate": ((held, d, f), "matrix"),
          "w_up": ((held, d, f), "matrix"),
          "w_down": ((held, f, d), "matrix"),
          "shared_gate": ((d, fs), "matrix"),
          "shared_up": ((d, fs), "matrix"),
          "shared_down": ((fs, d), "matrix")}


INITIALISERS = {"matrix": nn.initializers.normal(0.02),
                "gain": nn.initializers.ones}


class Laguna(nn.Module):
  """``__call__(numerical, cats, emb_acts=[rows [B, L, d]])`` ->
  ``{"logits" [B, L, V], "weight" [B, L]}``: ``weight`` is 1 where the next
  token belongs to the same document, 0 at a document's last token (and
  ``"moe"``, the expert layers' counters stacked, where
  ``with_counters``)."""

  config: LagunaConfig
  with_counters: bool = False

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    del cats
    cfg = self.config
    if emb_acts is None or len(emb_acts) != 1:
      raise ValueError("Laguna takes its token rows as one sequence input: "
                       "emb_acts=[rows [B, L, hidden_size]]")
    (x,) = emb_acts
    layers = [{name: self.param(f"layer_{i}_{name}", INITIALISERS[leaf], shape)
               for name, (shape, leaf) in layer_shapes(cfg, i).items()}
              for i in range(cfg.num_hidden_layers)]
    final_norm = self.param("final_norm", nn.initializers.ones,
                            (cfg.hidden_size,))
    head = self.param("head", INITIALISERS["matrix"],
                      (cfg.hidden_size, cfg.vocab_size))

    seg = document_segments(numerical, cfg.mean_document_length)
    counters = []
    # one layer's activations at a time, plus what layers/remat.py names:
    # the rest of the other layers is recomputed
    for i, p in enumerate(layers):
      x, c = checkpoint_layer(functools.partial(
          decoder_layer, cfg, cfg.layer_types[i], cfg.mlp_layer_types[i]))(
              p, x, seg)
      if c is not None:
        counters.append(c)
    with jax.named_scope(scopes.LM_HEAD):
      logits = mxu_dot(rms_norm(x, final_norm, cfg.rms_norm_eps), head)
    same = jnp.pad(seg[:, 1:] == seg[:, :-1], ((0, 0), (0, 1)))
    out = {"logits": logits, "weight": same.astype(logits.dtype)}
    if self.with_counters and counters:
      out["moe"] = jax.tree_util.tree_map(lambda *c: jnp.stack(c), *counters)
    return out
