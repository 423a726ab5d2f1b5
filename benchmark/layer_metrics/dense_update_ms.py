"""dense_update_ms: what it measures is in ``dense_update_ms.json``; the reduction is
``benchmark/scope_reduce.py``."""

from benchmark import scope_reduce

SCOPES = ("de_dense_update",)


def read(red, ctx):
  return scope_reduce.scoped(red, ctx).scope_ms(*SCOPES)
