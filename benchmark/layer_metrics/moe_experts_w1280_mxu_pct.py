"""moe_experts_w1280_mxu_pct: what it measures is in ``moe_experts_w1280_mxu_pct.json``; the
counts are ``benchmark/roofline_solar.py``, the time is ``moe_experts_ms``'s."""

from benchmark import roofline_lm, roofline_solar, scope_children


def read(red, ctx):
  ms = scope_children.scope_ms(red, ctx, "de_moe_experts")
  if ms is None:
    return None
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_solar.moe_experts_flops(cell.config, cell.traffic), ms,
      ctx["device_kind"])
