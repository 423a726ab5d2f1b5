"""Device time under the child scopes a model of window and full attention
beside a shared expert adds inside ``de_model``: ``de_window_attention`` and
``de_full_attention`` (inside ``de_attention``: a mixer of that kind whole)
and ``de_moe_shared`` (inside ``de_moe``: the expert every token passes);
``de_mlp`` beside them, for the line it prints; and, under each of the two
attention scopes, the time of the Mosaic kernels
named ``splash_*`` alone (``<scope>:splash``), which the two kernels' shares
of the MXU divide by. ``scope_children.NAMES`` and
``scope_children_hybrid.NAMES`` are tuples in files this cannot edit, so
these are attributed here, the FOURTH attribution of one trace (PERF.md
section 7 asks the next ``benchmark`` issue for one reader that takes its
names from the metric's ``.json``), with the same ingredients:
``scope_reduce.read_op_names`` (an op's name stack from the trace's own
metadata) and ``trace_reduce.nesting`` (self time).

An op goes to every one of these names that its name stack holds as a whole
component. A fusion the compiler left without a name stack of its own goes
where most of its instructions lie; an op without any inside a ``while``
goes where its holder went. A program that has none of these scopes (the
parent of the PR that added them) gives ``None``: the metric is then left
out of the line.
"""

from __future__ import annotations

import collections
import glob
import os
import statistics
from typing import Any, Dict, FrozenSet, List, Optional

from benchmark import scope_reduce
from benchmark.trace_reduce import nesting, op_name

# `de_mlp` (the leading dense layer's MLP) is attributed for the printed
# line alone: `mlp_ms` reads it in the cells its own list names
NAMES = ("de_window_attention", "de_full_attention", "de_moe_shared",
         "de_mlp")
KERNEL = "splash_"          # JAX's splash-attention kernels, by op name
KERNEL_UNDER = NAMES[:2]    # the scopes whose kernels are summed apart
KEYS = NAMES + tuple(f"{n}:splash" for n in KERNEL_UNDER)


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
  """Per device, per key of ``KEYS``: self ns of each traced step."""
  cache: Dict[str, Optional[FrozenSet[str]]] = {}
  out = []
  for steps, ops in zip(red.steps, red.ops):
    by_key = {k: [0.0] * len(steps) for k in KEYS}
    self_ns, parent, order = nesting(ops)
    placed: List[Optional[FrozenSet[str]]] = [None] * len(ops)
    for i in order:  # holders first
      op = op_name(ops[i][0])
      if op not in cache:
        cache[op] = op_scopes(names, op)
      placed[i] = cache[op]
      if placed[i] is None and parent[i] >= 0:
        placed[i] = placed[parent[i]]
    for i, (name, _, _, k) in enumerate(ops):
      if k >= 0:
        kernel = op_name(name).startswith(KERNEL)
        for n in placed[i] or ():
          by_key[n][k] += self_ns[i]
          if kernel and n in KERNEL_UNDER:
            by_key[f"{n}:splash"][k] += self_ns[i]
    out.append(by_key)
  return out


def children(red, ctx: Dict[str, Any]) -> List[Dict[str, List[float]]]:
  """The run's trace attributed to ``KEYS``, once per run (kept in
  ``ctx``)."""
  if "scope_children_laguna" not in ctx:
    cell = ctx["cell"]
    files = glob.glob(os.path.join(cell.root, ".bench_trace", cell.name,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
      raise RuntimeError(f"expected one .xplane.pb of {cell.name}, "
                         f"found {files}")
    names = scope_reduce.read_op_names(files[0], red.steps[0][0][0])
    ctx["scope_children_laguna"] = per_step_ns(red, names)
    print("window/full/shared child scopes (self time, ms a step): "
          + " ".join(f"{k}={scope_ms(red, ctx, k) or 0.0:.3f}" for k in KEYS),
          flush=True)
  return ctx["scope_children_laguna"]


def scope_ms(red, ctx: Dict[str, Any], *wanted: str) -> Optional[float]:
  """Per-step sum of self time under the keys given, median over steps,
  mean over devices; ``None`` where no op of the trace lies under them."""
  per_dev = []
  for by_key in children(red, ctx):
    sums = [sum(v) for v in zip(*(by_key[k] for k in wanted))]
    if any(sums):
      per_dev.append(statistics.median(sums))
  if not per_dev:
    return None
  return statistics.fmean(per_dev) * 1e-6
