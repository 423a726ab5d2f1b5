"""Least work of four parts of a Solar-Open2 training step, counted from the
configuration and the traffic mix alone: what ``kda_rule_mxu_pct``,
``moe_experts_w1280_mxu_pct`` and ``splash_gqa_d128_mxu_pct`` divide by the
MXU's peak and ``kda_gate_hbm_pct`` by the HBM's (``roofline.PEAKS``), each
over a device time. Least as in ``roofline_lm.py``: what the equations need
whatever implements them, no recomputation, no masked-out pair of attention,
no padded row of a grouped matmul, so a share cannot pass 100%; a
multiply-add is 2, a backward pass twice its forward.

*The rule with a decay a key channel*, chunked at ``C``: the products
``roofline_hybrid.delta_rule_chunk_flops`` counts for the scalar rule (``K
K^T`` and ``Q K^T`` over their triangles, the unit triangular solve, ``P U``
and ``P Wk``, the state's products), and what the per-channel decay adds to
them at the least: the decay no longer factors out of a pair's dot product,
so every row that enters one is first multiplied by its factor, a multiply a
channel: ``K`` as the rows and as the columns of ``K K^T`` (the columns serve
``Q K^T`` too), ``Q`` as the rows of ``Q K^T``, ``K`` and ``Q`` against the
incoming state, ``K`` towards the outgoing one: ``6 C d_k`` multiplies a
chunk and head. That the factors are taken sub-block by sub-block (so that
no exponent rises above 0) is the program's way, not the algorithm's: not
counted.

*The gate part* of a KDA mixer (``de_linattn_gate``: the two low-rank
chains, softplus and the decay, ``beta``, the sigmoid-gated output norm) is
bound by memory: the chains' first products read ``u [T, hidden]`` and make
128 columns. Arrays of float32 moved once, nothing rebuilt. Forward: ``u``
read once for ``W_fa``, ``W_ga`` and ``W_b`` (``T x hidden``), the two
latents written and read (``4 T x 128``), ``g`` written, the rule's ``o``
read and the gated output written (``3 T x c``, ``c`` the channels held),
``beta`` written (``T x heads``). Backward: ``u`` read again for the weight
gradients and the cotangent of ``u`` written (``2 T x hidden``), the
cotangents of the output and of ``g`` read, ``o`` read, the cotangent of
``o`` written (``4 T x c``), the latents' cotangents written and read (``4 T
x 128``), ``beta``'s read (``T x heads``, twice with its value). The weights
(4 MB a layer) are not counted.

The pairs an attention mask leaves are the EXPECTATION under the mix
(``roofline_laguna.expected_pairs``), the same for every seed.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark import roofline_hybrid, roofline_laguna

KDA, GQA = "kda", "gqa"


def kinds(config: Dict[str, Any]) -> List[str]:
  """The mixer of every layer that runs here."""
  gqa = set(int(i) for i in config["gqa_layers"])
  return [GQA if int(i) in gqa else KDA for i in config["layers_here"]]


def _tokens(config, mix) -> int:
  return int(config["seq_len"]) * int(mix["global_batch"])


def kda_rule_chunk_flops(chunk: int, dk: int, dv: int) -> float:
  """Forward flops of one chunk of one head: the scalar rule's products and
  the six per-channel factors' multiplies."""
  return roofline_hybrid.delta_rule_chunk_flops(chunk, dk, dv) \
      + 6.0 * chunk * dk


def kda_rule_flops(config, mix) -> float:
  """Forward and backward, every chunk, held head, KDA layer and sequence
  of a step."""
  chunk, length = int(config["chunk"]), int(config["seq_len"])
  hd = int(config["linear_attn_config"]["head_dim"])
  calls = (-(-length // chunk) * int(config["heads_held"][1])
           * sum(kind == KDA for kind in kinds(config))
           * int(mix["global_batch"]))
  return 3.0 * calls * kda_rule_chunk_flops(chunk, hd, hd)


def kda_gate_bytes(config, mix) -> float:
  """The gate part of every KDA layer, forward and backward."""
  d, heads = int(config["hidden_size"]), int(config["heads_held"][1])
  rank = int(config["linear_attn_config"]["head_dim"])
  c = heads * rank
  a_token = (1 + 2) * d + (3 + 4) * c + (4 + 4) * rank + (1 + 2) * heads
  return float(4 * a_token * _tokens(config, mix)
               * sum(kind == KDA for kind in kinds(config)))


def moe_experts_flops(config, mix) -> float:
  """The grouped matmuls of the held experts at the EXPECTED number of
  assignments on them (``tokens * top_k * held / experts`` a layer, every
  layer an expert layer): 6 per expert weight and assignment, three matrices
  of ``hidden x moe_intermediate_size`` an expert. The shared expert is not
  in it (``de_moe_shared``)."""
  assignments = _tokens(config, mix) * int(config["num_experts_per_tok"]) \
      * int(config["experts_held"][1]) / int(config["n_routed_experts"])
  return 6.0 * 3 * int(config["hidden_size"]) \
      * int(config["moe_intermediate_size"]) * assignments \
      * len(config["layers_here"])


def splash_flops(config, mix) -> float:
  """QK and PV over the unmasked pairs only, forward and backward, of every
  attention layer: ``12 * head_dim`` a pair and query head HELD (4 forward:
  two matmuls; 8 backward: dQ, dK, dP, dV)."""
  pairs = roofline_laguna.expected_pairs(
      int(config["seq_len"]), int(config["mean_document_length"]))
  return 12.0 * int(config["head_dim"]) * int(config["heads_held"][1]) \
      * pairs * int(mix["global_batch"]) \
      * sum(kind == GQA for kind in kinds(config))
