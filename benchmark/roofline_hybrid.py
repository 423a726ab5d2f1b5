"""Least floating-point work of the chunked gated delta rule in a training
step, counted from the configuration and the traffic mix alone: what
``delta_rule_mxu_pct`` divides by the MXU's peak (``roofline.PEAKS``) and by
the device time under ``de_delta_rule``. The yardstick a later kernel for the
rule is read against.

Least work of the chunked algorithm at chunk ``C`` (Yang et al., "Gated Delta
Networks"; the program's `layers/gated_delta.py` says which products these
are), per chunk and head, a multiply-add as 2, triangular products over their
triangle only, nothing recomputed, a backward pass as twice its forward:

- ``K K^T`` below the diagonal and ``Q K^T`` on and below it: ``d_k`` a pair;
- the unit triangular solve ``(I + A) [U | Wk] = [beta V | beta e^gamma K]``
  by forward substitution: ``d_v + d_k`` a pair below the diagonal;
- ``P U`` and ``P Wk`` on and below the diagonal: ``d_v + d_k`` a pair;
- ``Kd^T Wk`` (``C d_k d_k``), ``Kd^T U`` and ``(e^gamma Q - P Wk) S``
  (``C d_k d_v`` each), and the chunk-to-chunk step ``M S`` (``d_k d_k d_v``).

What the rule does besides multiply (decays, masks, the running sums) is not
counted: this is a share of the MXU's peak, not of the layer's time.
"""

from __future__ import annotations

from typing import Any, Dict


def delta_rule_chunk_flops(chunk: int, dk: int, dv: int) -> float:
  """Forward flops of one chunk of one head."""
  below, upto = chunk * (chunk - 1) // 2, chunk * (chunk + 1) // 2
  pairs = below * dk + upto * dk            # K K^T, Q K^T
  pairs += below * (dv + dk)                # the triangular solve
  pairs += upto * (dv + dk)                 # P U, P Wk
  full = chunk * dk * dk + 2 * chunk * dk * dv + dk * dk * dv
  return 2.0 * (pairs + full)


def delta_rule_flops(config: Dict[str, Any], mix: Dict[str, Any]) -> float:
  """Forward and backward, every chunk, held head, gated-delta-rule layer
  and sequence of a step."""
  chunk, length = int(config["chunk"]), int(config["seq_len"])
  layers = config["layer_types"][:int(config["num_hidden_layers_here"])]
  calls = (-(-length // chunk) * int(config["heads_held"][1])
           * sum(kind == "linear_attention" for kind in layers)
           * int(mix["global_batch"]))
  return 3.0 * calls * delta_rule_chunk_flops(
      chunk, int(config["linear_key_head_dim"]),
      int(config["linear_value_head_dim"]))
