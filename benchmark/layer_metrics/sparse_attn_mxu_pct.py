"""sparse_attn_mxu_pct: what it measures is in ``sparse_attn_mxu_pct.json``; the counts are
``benchmark/roofline_keye.py``, the time is ``attn_core_ms``'s."""

import os

from benchmark import roofline_keye, roofline_lm, scope_parts

_ms = scope_parts.reader(os.path.join(os.path.dirname(__file__), "attn_core_ms.py"))


def read(red, ctx):
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_keye.sparse_attention_flops(cell.config, cell.traffic), _ms(red, ctx),
      ctx["device_kind"])
