"""mla_proj_mxu_pct: what it measures is in ``mla_proj_mxu_pct.json``; the count is
``benchmark/roofline_glm.py``, the time is ``mla_down_ms`` + ``mla_up_ms``."""

import os

from benchmark import roofline_glm, roofline_lm, scope_parts

_HERE = os.path.dirname(__file__)
_down = scope_parts.reader(os.path.join(_HERE, "mla_down_ms.py"))
_up = scope_parts.reader(os.path.join(_HERE, "mla_up_ms.py"))


def read(red, ctx):
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_glm.mla_proj_flops(cell.config, cell.traffic),
      _down(red, ctx) + _up(red, ctx), ctx["device_kind"])
