"""delta_rule_ms: what it measures is in ``delta_rule_ms.json``; the reduction is
``benchmark/scope_children_hybrid.py``."""

from benchmark import scope_children_hybrid

SCOPES = ('de_delta_rule',)


def read(red, ctx):
  return scope_children_hybrid.scope_ms(red, ctx, *SCOPES)
