"""Least floating-point work of three parts of a Laguna training step,
counted from the configuration and the traffic mix alone: what
``window_splash_mxu_pct``, ``full_splash_mxu_pct`` and
``moe_experts_w512_mxu_pct`` divide by the MXU's peak (``roofline.PEAKS``)
and by a device time. Least work as in ``roofline_lm.py``: no recomputation,
no masked-out pair of attention, no padded row of a grouped matmul, so a
share cannot pass 100%; a multiply-add is 2, a backward pass twice its
forward.

The pairs an attention mask leaves depend on where the mix's documents
start, which is drawn per batch: the count is the EXPECTATION under the
mix (a document starts at position ``i > 0`` with probability
``1 / mean_document_length``), the same for every seed; a traced window's
steps walk the pool's sixteen batches, whose own count the reference prints
(``reference batch:``). A query ``i`` reaches ``k`` positions back where no
document starts among them: probability ``(1 - 1/mean)^k``, for ``k <= i``
and, under a window, ``k < sliding_window``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

SLIDING, FULL = "sliding_attention", "full_attention"


def expected_pairs(length: int, mean_doc: int,
                   window: Optional[int] = None) -> float:
  """Expected (query, key) pairs of one sequence that are causal, inside one
  document and, with a window, at most ``window - 1`` apart: the sum over
  queries of the geometric series ``sum_{k < reach_i} (1 - 1/mean)^k``."""
  keep = 1.0 - 1.0 / mean_doc
  total = 0.0
  for i in range(length):
    reach = min(i, (window or length) - 1) + 1
    total += (1.0 - keep ** reach) * mean_doc
  return total


def _layers(config: Dict[str, Any]):
  n = int(config["num_hidden_layers_here"])
  return list(zip(config["layer_types"][:n], config["mlp_layer_types"][:n],
                  config["num_attention_heads_per_layer"][:n]))


def splash_flops(config: Dict[str, Any], mix: Dict[str, Any],
                 kind: str) -> float:
  """QK and PV over the unmasked pairs only, forward and backward, of every
  layer of ``kind``: ``12 * head_dim`` a pair and query head (4 forward: two
  matmuls; 8 backward: dQ, dK, dP, dV)."""
  window = int(config["sliding_window"]) if kind == SLIDING else None
  pairs = expected_pairs(int(config["seq_len"]),
                         int(config["mean_document_length"]), window)
  heads = sum(int(h) for k, _, h in _layers(config) if k == kind)
  return 12.0 * int(config["head_dim"]) * heads * pairs \
      * int(mix["global_batch"])


def window_splash_flops(config, mix) -> float:
  return splash_flops(config, mix, SLIDING)


def full_splash_flops(config, mix) -> float:
  return splash_flops(config, mix, FULL)


def moe_experts_flops(config, mix) -> float:
  """The grouped matmuls of the held routed experts at the EXPECTED number
  of assignments on them (``tokens * top_k * held / experts`` a sparse
  layer): 6 per expert weight and assignment, three matrices of
  ``hidden x moe_intermediate_size`` an expert. The shared expert is not in
  it (``de_moe_shared``)."""
  tokens = int(config["seq_len"]) * int(mix["global_batch"])
  assignments = tokens * int(config["num_experts_per_tok"]) \
      * int(config["experts_held"][1]) / int(config["num_experts"])
  sparse = sum(mlp == "sparse" for _, mlp, _ in _layers(config))
  return 6.0 * 3 * int(config["hidden_size"]) \
      * int(config["moe_intermediate_size"]) * assignments * sparse
