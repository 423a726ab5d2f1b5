"""Least floating-point work of a language-model training step, counted from
the configuration and the traffic mix alone: what the ``*_mxu_pct`` metrics
of ``layer_metrics/`` divide by the MXU's peak (``roofline.PEAKS``) and by a
scope's device time. Least work means no recomputation (a rematerialised
forward is the program's choice, not the algorithm's), no masked-out pair of
attention and no padded row of a grouped matmul, so a share cannot pass 100%.
A multiply-add is 2; a backward pass is twice its forward.
"""

from __future__ import annotations

from typing import Any, Dict


def _sizes(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
  return dict(
      batch=int(mix["global_batch"]), length=int(config["seq_len"]),
      block=int(config["block_length"]),
      layers=int(config["num_hidden_layers_here"]),
      d=int(config["hidden_size"]), hq=int(config["num_attention_heads"]),
      hkv=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
      f=int(config["moe_intermediate_size"]),
      experts=int(config["num_experts"]), held=int(config["experts_held"][1]),
      top_k=int(config["num_experts_per_tok"]))


def block_diffusion_pairs(length: int, block: int) -> int:
  """(query, key) pairs the block-diffusion mask leaves, over ``[xt ; x0]``
  of one sequence. A noisy query of block b sees its block's ``block`` noisy
  keys and ``b * block`` clean keys; a clean query of block b sees
  ``(b + 1) * block`` clean keys. Summed: ``length * (length + block)``."""
  n = length // block
  noisy = block * block * (n + n * (n - 1) // 2)
  clean = block * block * (n * (n + 1) // 2)
  return noisy + clean


def attention_core_flops(config, mix) -> float:
  """QK and PV over the unmasked pairs only, forward and backward, every
  layer: ``12 * head_dim`` a pair and query head (4 forward: two matmuls;
  8 backward: dQ, dK, dP, dV)."""
  s = _sizes(config, mix)
  pairs = block_diffusion_pairs(s["length"], s["block"])
  return 12.0 * s["hd"] * s["hq"] * pairs * s["batch"] * s["layers"]


def attention_flops(config, mix) -> float:
  """Everything under ``de_attention``: the q, k, v and o projections of
  every position (6 per weight and position: forward and backward) and
  :func:`attention_core_flops`."""
  s = _sizes(config, mix)
  weights = s["d"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"])
  positions = 2 * s["length"] * s["batch"]
  return 6.0 * weights * positions * s["layers"] \
      + attention_core_flops(config, mix)


def moe_experts_flops(config, mix) -> float:
  """The grouped matmuls of the held experts at the EXPECTED number of
  assignments on them (``positions * top_k * held / experts``; the router of
  seeded weights is near uniform): 6 per expert weight and assignment."""
  s = _sizes(config, mix)
  positions = 2 * s["length"] * s["batch"]
  assignments = positions * s["top_k"] * s["held"] / s["experts"]
  return 6.0 * 3 * s["d"] * s["f"] * assignments * s["layers"]


def mxu_pct(flops: float, ms: float, device_kind: str):
  """Share (%) of the MXU's bf16 peak that ``flops`` in ``ms`` is."""
  from benchmark import roofline
  if not ms:
    return None
  peak = roofline.peaks(device_kind)["bf16_flops_per_s"]
  return 100.0 * flops / peak / (ms * 1e-3)
