"""splash_gqa_d128_mxu_pct: what it measures is in ``splash_gqa_d128_mxu_pct.json``; the
counts are ``benchmark/roofline_solar.py``, the time is the splash kernels' under
``de_attention`` (what ``attn_layout_ms`` takes out)."""

from benchmark import roofline_lm, roofline_solar, scope_parts


def _kernel(key):
  chain, _, op = key
  return "de_attention" in chain and op.startswith("splash_")


def read(red, ctx):
  cell = ctx["cell"]
  return roofline_lm.mxu_pct(
      roofline_solar.splash_flops(cell.config, cell.traffic),
      scope_parts.parts(red, ctx).ms(_kernel), ctx["device_kind"])
