"""dense_model_ms: what it measures is in ``dense_model_ms.json``; the reduction is
``benchmark/scope_reduce.py``."""

from benchmark import scope_reduce

SCOPES = ("de_model", "de_loss")


def read(red, ctx):
  return scope_reduce.scoped(red, ctx).scope_ms(*SCOPES)
