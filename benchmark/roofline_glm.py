"""Least floating-point work of two parts of a GLM-4.7-Flash training step,
counted from the configuration and the traffic mix alone: what
``mla_proj_mxu_pct`` and ``splash_d256_mxu_pct`` divide by the MXU's peak
(``roofline.PEAKS``), each over a device time. Least as in
``roofline_lm.py``: what the equations need whatever implements them, no
recomputation, no masked-out pair of attention, so a share cannot pass 100%;
a multiply-add is 2, a backward pass twice its forward.

The latent products are counted in the EXPANDED form the configuration's
equations state (``W_dq``, ``W_uq``, ``W_dkv``, ``W_ukv``: down to a latent,
up to every head); ``W_o`` is not among them (``attn_proj_ms`` reads it).
The pairs an attention mask leaves are the EXPECTATION under the mix
(``roofline_laguna.expected_pairs``), the same for every seed. Every layer
that runs here has the attention: the trunk's and the prediction module's.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import roofline_laguna


def attention_layers(config: Dict[str, Any]) -> int:
  """The trunk's layers that run here and the prediction modules'."""
  return len(config["layers_here"]) + int(config["num_nextn_predict_layers"])


def latent_weights(config: Dict[str, Any]) -> int:
  """Weights of the four latent products of one layer."""
  d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
  q_rank, kv_rank = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
  nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
  return d * q_rank + q_rank * heads * (nope + rope) \
      + d * (kv_rank + rope) + kv_rank * heads * (nope + int(config["v_head_dim"]))


def mla_proj_flops(config, mix) -> float:
  """The four latent products of every layer, forward and backward: 6 per
  weight and token."""
  tokens = int(config["seq_len"]) * int(mix["global_batch"])
  return 6.0 * latent_weights(config) * tokens * attention_layers(config)


def splash_flops(config, mix) -> float:
  """QK and PV over the unmasked pairs only, forward and backward, of every
  layer: a pair and head costs ``6 x (qk_nope_head_dim + qk_rope_head_dim)``
  for the scores (2 forward; dQ and dK) and ``6 x v_head_dim`` for the values
  (2 forward; dP and dV)."""
  pairs = roofline_laguna.expected_pairs(
      int(config["seq_len"]), int(config["mean_document_length"]))
  head = 6.0 * (int(config["qk_nope_head_dim"])
                + int(config["qk_rope_head_dim"])) \
      + 6.0 * int(config["v_head_dim"])
  return head * int(config["num_attention_heads"]) * pairs \
      * int(mix["global_batch"]) * attention_layers(config)
