"""moe_experts_ms: what it measures is in ``moe_experts_ms.json``; the reduction is
``benchmark/scope_children.py``."""

from benchmark import scope_children

SCOPES = ('de_moe_experts',)


def read(red, ctx):
  return scope_children.scope_ms(red, ctx, *SCOPES)
