"""delta_rule_mxu_pct: what it measures is in ``delta_rule_mxu_pct.json``; the
counts are ``benchmark/roofline_hybrid.py``."""

from benchmark import roofline_hybrid, roofline_lm, scope_children_hybrid


def read(red, ctx):
  ms = scope_children_hybrid.scope_ms(red, ctx, "de_delta_rule")
  if ms is None:
    return None
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_hybrid.delta_rule_flops(cell.config, cell.traffic), ms,
      ctx["device_kind"])
