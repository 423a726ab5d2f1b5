"""lm_head_ms: what it measures is in ``lm_head_ms.json``; the reduction is
``benchmark/scope_children.py``."""

from benchmark import scope_children

SCOPES = ('de_lm_head', 'de_loss')


def read(red, ctx):
  return scope_children.scope_ms(red, ctx, *SCOPES)
