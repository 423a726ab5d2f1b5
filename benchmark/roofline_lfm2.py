"""Least work of three parts of an LFM2-MoE training step, counted from the
configuration and the traffic mix alone: what ``conv_gate_hbm_pct`` divides
by the HBM's peak, and ``moe_experts_w1536_mxu_pct`` and
``splash_d64_mxu_pct`` by the MXU's (``roofline.PEAKS``), each over a device
time. Least as in ``roofline_lm.py``: what the equations need whatever
implements them, no recomputation, no masked-out pair of attention, no padded
row of a grouped matmul, so a share cannot pass 100%; a multiply-add is 2, a
backward pass twice its forward.

The gate chain of a short-convolution mixer (``B * u``, three taps a channel
with a reset, ``C * c``) is elementwise but for the taps' two neighbours,
which a pass holds: bound by memory. Forward, one pass: ``B``, ``C`` and
``u`` read, the gated output written (4 arrays of ``[tokens, hidden]``
float32, the precision the configuration states for activations). Backward,
one pass: the output's cotangent and ``B``, ``C``, ``u`` read (the chain is
rebuilt on the fly), their three cotangents written (7). The taps, the
documents' numbers and the taps' gradient are thousandths of that.

The pairs an attention mask leaves are the EXPECTATION under the mix
(``roofline_laguna.expected_pairs``), the same for every seed.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmark import roofline_laguna

CONV, FULL = "conv", "full_attention"
GATE_ARRAYS = 4 + 7   # [tokens, hidden] float32 arrays moved, both passes


def kinds(config: Dict[str, Any]) -> List[Tuple[str, bool]]:
  """(mixer, has experts) of every layer that runs here."""
  dense = int(config["num_dense_layers"])
  return [(config["layer_types"][i], i >= dense)
          for i in config["layers_here"]]


def _tokens(config, mix) -> int:
  return int(config["seq_len"]) * int(mix["global_batch"])


def conv_gate_bytes(config, mix) -> float:
  """The gate chains of every convolution layer, forward and backward."""
  layers = sum(mixer == CONV for mixer, _ in kinds(config))
  return float(GATE_ARRAYS * 4 * _tokens(config, mix)
               * int(config["hidden_size"]) * layers)


def moe_experts_flops(config, mix) -> float:
  """The grouped matmuls of the held experts at the EXPECTED number of
  assignments on them (``tokens * top_k * held / experts`` an expert layer):
  6 per expert weight and assignment, three matrices of
  ``hidden x moe_intermediate_size`` an expert."""
  assignments = _tokens(config, mix) * int(config["num_experts_per_tok"]) \
      * int(config["experts_held"][1]) / int(config["num_experts"])
  layers = sum(has_experts for _, has_experts in kinds(config))
  return 6.0 * 3 * int(config["hidden_size"]) \
      * int(config["moe_intermediate_size"]) * assignments * layers


def splash_flops(config, mix) -> float:
  """QK and PV over the unmasked pairs only, forward and backward, of every
  attention layer: ``12 * head_dim`` a pair and query head (4 forward: two
  matmuls; 8 backward: dQ, dK, dP, dV)."""
  pairs = roofline_laguna.expected_pairs(
      int(config["seq_len"]), int(config["mean_document_length"]))
  layers = sum(mixer == FULL for mixer, _ in kinds(config))
  return 12.0 * int(config["head_dim"]) * int(config["num_attention_heads"]) \
      * pairs * int(mix["global_batch"]) * layers


def hbm_pct(n_bytes: float, ms: float, device_kind: str):
  """Share (%) of the HBM's peak that ``n_bytes`` in ``ms`` is."""
  from benchmark import roofline
  if not ms:
    return None
  return 100.0 * n_bytes / roofline.peaks(device_kind)["hbm_bytes_per_s"] \
      / (ms * 1e-3)
