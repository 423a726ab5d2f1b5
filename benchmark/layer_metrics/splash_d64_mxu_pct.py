"""splash_d64_mxu_pct: what it measures is in ``splash_d64_mxu_pct.json``; the counts are
``benchmark/roofline_lfm2.py``, the time is the splash kernels' under
``de_attention`` (what ``attn_layout_ms`` takes out)."""

from benchmark import roofline_lfm2, roofline_lm, scope_parts


def _kernel(key):
  chain, _, op = key
  return "de_attention" in chain and op.startswith("splash_")


def read(red, ctx):
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_lfm2.splash_flops(cell.config, cell.traffic),
      scope_parts.parts(red, ctx).ms(_kernel), ctx["device_kind"])
