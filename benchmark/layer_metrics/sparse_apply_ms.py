"""sparse_apply_ms: what it measures is in ``sparse_apply_ms.json``; the reduction is
``benchmark/scope_reduce.py``."""

from benchmark import scope_reduce

SCOPES = ("de_apply",)


def read(red, ctx):
  return scope_reduce.scoped(red, ctx).scope_ms(*SCOPES)
