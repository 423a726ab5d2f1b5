"""full_attn_ms: what it measures is in ``full_attn_ms.json``; the reduction is
``benchmark/scope_children_laguna.py``."""

from benchmark import scope_children_laguna

SCOPES = ('de_full_attention',)


def read(red, ctx):
  return scope_children_laguna.scope_ms(red, ctx, *SCOPES)
