"""splash_attention_roofline: what it measures is in ``splash_attention_roofline.json``; the counts are
``benchmark/roofline_lm.py``."""

import re

from benchmark import roofline_lm
from benchmark.trace_reduce import op_name

_KERNEL = re.compile(r"^splash_")


def read(red, ctx):
  ms = red.per_step_ms(lambda name: _KERNEL.search(op_name(name)) is not None)
  if ms is None:
    return None
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_lm.attention_core_flops(cell.config, cell.traffic), ms, ctx["device_kind"])
