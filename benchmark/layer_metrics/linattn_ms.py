"""linattn_ms: what it measures is in ``linattn_ms.json``; the reduction is
``benchmark/scope_children_hybrid.py``."""

from benchmark import scope_children_hybrid

SCOPES = ('de_linear_attention',)


def read(red, ctx):
  return scope_children_hybrid.scope_ms(red, ctx, *SCOPES)
