"""moe_route_ms: what it measures is in ``moe_route_ms.json``; the reduction is
``benchmark/scope_children.py``."""

from benchmark import scope_children

SCOPES = ('de_moe_route',)


def read(red, ctx):
  return scope_children.scope_ms(red, ctx, *SCOPES)
