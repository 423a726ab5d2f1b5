"""interact_ms: what it measures is in ``interact_ms.json``; the reduction is
``benchmark/scope_reduce.py``."""

from benchmark import scope_reduce

SCOPES = ("de_interact",)


def read(red, ctx):
  return scope_reduce.scoped(red, ctx).child_ms(SCOPES[0])
