"""moe_shared_ms: what it measures is in ``moe_shared_ms.json``; the reduction is
``benchmark/scope_children_laguna.py``."""

from benchmark import scope_children_laguna

SCOPES = ('de_moe_shared',)


def read(red, ctx):
  return scope_children_laguna.scope_ms(red, ctx, *SCOPES)
