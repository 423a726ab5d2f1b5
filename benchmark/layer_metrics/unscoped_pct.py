"""unscoped_pct: what it measures is in ``unscoped_pct.json``; the reduction is
``benchmark/scope_reduce.py``."""

from benchmark import scope_reduce

SCOPES = ()


def read(red, ctx):
  return scope_reduce.scoped(red, ctx).unscoped_pct()
