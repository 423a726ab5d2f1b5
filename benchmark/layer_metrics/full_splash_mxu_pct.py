"""full_splash_mxu_pct: what it measures is in ``full_splash_mxu_pct.json``; the counts are
``benchmark/roofline_laguna.py``."""

from benchmark import roofline_laguna, roofline_lm, scope_children_laguna


def read(red, ctx):
  ms = scope_children_laguna.scope_ms(red, ctx, "de_full_attention:splash")
  if ms is None:
    return None
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_laguna.full_splash_flops(cell.config, cell.traffic), ms,
      ctx["device_kind"])
