"""kda_gate_hbm_pct: what it measures is in ``kda_gate_hbm_pct.json``; the count is
``benchmark/roofline_solar.py``, the time is ``kda_gate_ms``'s."""

import os

from benchmark import roofline_lfm2, roofline_solar, scope_parts

_ms = scope_parts.reader(os.path.join(os.path.dirname(__file__), "kda_gate_ms.py"))


def read(red, ctx):
  cell = ctx["cell"]
  return roofline_lfm2.hbm_pct(
      roofline_solar.kda_gate_bytes(cell.config, cell.traffic), _ms(red, ctx),
      ctx["device_kind"])
