"""Device time under the child scopes a hybrid linear-attention model adds
inside ``de_model``: ``de_linear_attention`` (a gated-delta-rule mixer whole),
``de_delta_rule`` inside it (the chunked rule alone) and ``de_mlp``.
``scope_children.NAMES`` is a tuple in a file this cannot edit (as
``scope_reduce.CHILDREN`` was for that file), so these three are attributed
here, the third attribution of one trace, with the same ingredients:
``scope_reduce.read_op_names`` (an op's name stack from the trace's own
metadata) and ``trace_reduce.nesting`` (self time; what runs inside a
``while``, as the rule's chunk-to-chunk scan does).

An op goes to every one of these names that its name stack holds as a whole
component. A fusion the compiler left without a name stack of its own goes
where most of its instructions lie; an op without any inside a ``while`` goes
where its holder went. A program that has none of these scopes (the parent
of the PR that added them) gives ``None``: the metric is then left out of
the line.
"""

from __future__ import annotations

import collections
import glob
import os
import statistics
from typing import Any, Dict, FrozenSet, List, Optional

from benchmark import scope_reduce
from benchmark.trace_reduce import nesting, op_name

NAMES = ("de_linear_attention", "de_delta_rule", "de_mlp")


def names_in(name_stack: str) -> FrozenSet[str]:
  return frozenset(inner for _, inner in scope_reduce._parts(name_stack)
                   if inner in NAMES)


def op_scopes(names: scope_reduce.OpNames, op: str
              ) -> Optional[FrozenSet[str]]:
  """The names of ``NAMES`` an op lies under; ``None`` where the trace knows
  no top-level scope for it."""
  own = names.own.get(op, "")
  if scope_reduce.layer_of(own)[0] is not None:
    return names_in(own)
  votes = collections.Counter(
      names_in(s) for s in names.inside.get(op, ())
      if scope_reduce.layer_of(s)[0] is not None)
  return votes.most_common(1)[0][0] if votes else None


def per_step_ns(red, names: scope_reduce.OpNames
                ) -> List[Dict[str, List[float]]]:
  """Per device, per name of ``NAMES``: self ns of each traced step."""
  cache: Dict[str, Optional[FrozenSet[str]]] = {}
  out = []
  for steps, ops in zip(red.steps, red.ops):
    by_name = {n: [0.0] * len(steps) for n in NAMES}
    self_ns, parent, order = nesting(ops)
    placed: List[Optional[FrozenSet[str]]] = [None] * len(ops)
    for i in order:  # holders first
      op = op_name(ops[i][0])
      if op not in cache:
        cache[op] = op_scopes(names, op)
      placed[i] = cache[op]
      if placed[i] is None and parent[i] >= 0:
        placed[i] = placed[parent[i]]
    for i, (_, _, _, k) in enumerate(ops):
      if k >= 0:
        for n in placed[i] or ():
          by_name[n][k] += self_ns[i]
    out.append(by_name)
  return out


def children(red, ctx: Dict[str, Any]) -> List[Dict[str, List[float]]]:
  """The run's trace attributed to ``NAMES``, once per run (kept in
  ``ctx``)."""
  if "scope_children_hybrid" not in ctx:
    cell = ctx["cell"]
    files = glob.glob(os.path.join(cell.root, ".bench_trace", cell.name,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
      raise RuntimeError(f"expected one .xplane.pb of {cell.name}, "
                         f"found {files}")
    names = scope_reduce.read_op_names(files[0], red.steps[0][0][0])
    ctx["scope_children_hybrid"] = per_step_ns(red, names)
    print("hybrid child scopes (self time, ms a step): " + " ".join(
        f"{n}={scope_ms(red, ctx, n) or 0.0:.3f}" for n in NAMES), flush=True)
  return ctx["scope_children_hybrid"]


def scope_ms(red, ctx: Dict[str, Any], *wanted: str) -> Optional[float]:
  """Per-step sum of self time under the names given, median over steps,
  mean over devices; ``None`` where no op of the trace lies under them."""
  per_dev = []
  for by_name in children(red, ctx):
    sums = [sum(v) for v in zip(*(by_name[n] for n in wanted))]
    if any(sums):
      per_dev.append(statistics.median(sums))
  if not per_dev:
    return None
  return statistics.fmean(per_dev) * 1e-6
