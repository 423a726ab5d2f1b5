"""conv_gate_hbm_pct: what it measures is in ``conv_gate_hbm_pct.json``; the count is
``benchmark/roofline_lfm2.py``, the time is ``conv_gate_ms``'s."""

import os

from benchmark import roofline_lfm2, scope_parts

_ms = scope_parts.reader(os.path.join(os.path.dirname(__file__), "conv_gate_ms.py"))


def read(red, ctx):
  cell = ctx["cell"]
  return roofline_lfm2.hbm_pct(
      roofline_lfm2.conv_gate_bytes(cell.config, cell.traffic), _ms(red, ctx),
      ctx["device_kind"])
