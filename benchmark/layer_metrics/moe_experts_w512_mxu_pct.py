"""moe_experts_w512_mxu_pct: what it measures is in ``moe_experts_w512_mxu_pct.json``; the counts are
``benchmark/roofline_laguna.py``."""

from benchmark import (
    roofline_laguna,
    roofline_lm,
    scope_children,
    scope_children_laguna,
)


def read(red, ctx):
  # a program without the shared expert's scope is not this model's
  if scope_children_laguna.scope_ms(red, ctx, "de_moe_shared") is None:
    return None
  ms = scope_children.scope_ms(red, ctx, "de_moe_experts")
  if ms is None:
    return None
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_laguna.moe_experts_flops(cell.config, cell.traffic), ms,
      ctx["device_kind"])
