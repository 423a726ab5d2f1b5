"""kda_rule_mxu_pct: what it measures is in ``kda_rule_mxu_pct.json``; the counts
are ``benchmark/roofline_solar.py``, the time is ``kda_rule_ms``'s."""

import os

from benchmark import roofline_lm, roofline_solar, scope_parts

_ms = scope_parts.reader(os.path.join(os.path.dirname(__file__), "kda_rule_ms.py"))


def read(red, ctx):
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_solar.kda_rule_flops(cell.config, cell.traffic), _ms(red, ctx),
      ctx["device_kind"])
