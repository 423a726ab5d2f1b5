"""attn_mxu_pct: what it measures is in ``attn_mxu_pct.json``; the counts are
``benchmark/roofline_lm.py``."""

from benchmark import roofline_lm, scope_children


def read(red, ctx):
  ms = scope_children.scope_ms(red, ctx, "de_attention")
  if ms is None:
    return None
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_lm.attention_flops(cell.config, cell.traffic), ms, ctx["device_kind"])
