"""Least floating-point work of the two products a learned sparse-attention
layer is made of, counted from the configuration and the traffic mix alone:
what ``sparse_attn_mxu_pct`` and ``index_scores_mxu_pct`` divide by the MXU's
peak (``roofline.PEAKS``) and by a device time. Least work as in
``roofline_lm.py``: what the equations need whatever implements them, no
recomputation, no masked-out pair, so a share cannot pass 100%; a
multiply-add is 2, a backward pass twice its forward.

The pairs depend on where the mix's documents start, which is drawn per
batch: both counts are EXPECTATIONS under the mix (a document starts at
position ``i > 0`` with probability ``1 / mean_document_length``), the same
for every seed, and ``roofline_laguna.expected_pairs`` counts them: a query
that keeps ``min(visible, topk)`` keys keeps what a window of ``topk`` would
leave. The reader's ``ctx`` carries neither the pool nor the seed.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import roofline_laguna


def expected_pairs(length: int, mean_doc: int, topk: int) -> Dict[str, float]:
  """Of one sequence: ``visible`` (query, key) pairs (causal, inside one
  document), ``selected``: ``sum over queries of min(visible, topk)``, which
  is what a window of ``topk`` would leave, and ``active`` queries, those
  with more than ``topk`` visible keys (no document starts among the
  ``topk`` positions before them)."""
  keep = 1.0 - 1.0 / mean_doc
  return {"visible": roofline_laguna.expected_pairs(length, mean_doc),
          "selected": roofline_laguna.expected_pairs(length, mean_doc, topk),
          "active": max(0, length - topk) * keep ** topk}


def _pairs(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, float]:
  one = expected_pairs(int(config["seq_len"]),
                       int(config["mean_document_length"]),
                       int(config["sa_config"]["topk"]))
  return {k: v * int(mix["global_batch"]) for k, v in one.items()}


def sparse_attention_flops(config, mix) -> float:
  """QK and PV over the SELECTED pairs only, forward and backward, every
  layer: ``12 * head_dim`` a pair and query head (4 forward: two matmuls;
  8 backward: dQ, dK, dP, dV)."""
  return 12.0 * int(config["head_dim"]) * int(config["num_attention_heads"]) \
      * _pairs(config, mix)["selected"] * int(config["num_hidden_layers_here"])


def index_scores_flops(config, mix) -> float:
  """The indexer's score product over the VISIBLE pairs, forward and the two
  products of its gradient, every layer: ``6 * indexer_head_dim`` a pair and
  index head."""
  sa = config["sa_config"]
  return 6.0 * int(sa["indexer_head_dim"]) * int(sa["indexer_num_heads"]) \
      * _pairs(config, mix)["visible"] * int(config["num_hidden_layers_here"])
