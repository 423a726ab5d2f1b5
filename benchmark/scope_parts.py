"""Device time by part and by pass, read from the metric's own ``.json``.

A language-model step marks, inside each layer's scope, the parts the work
falls into (``telemetry/scopes.py::PARTS``: the projections of an attention
mixer, what happens to ``q`` and ``k``, the kernel's call; the router, the
sort, the dispatch and the return of an expert route; the projections and
the convolutions of a recurrent mixer), and JAX marks the ops a
rematerialised layer runs again: their name stack holds the component
``rematted_computation``. This reads both from a traced run, with the
ingredients of the four attributions that stand
(``scope_reduce.read_op_names``: an op's name stack from the trace's own
metadata; ``trace_reduce.nesting``: self time), and holds no name of the
program: a metric's ``layer_metrics/<metric>.json`` says what it reads,

  ``"scopes"``        the names an op's stack must hold as whole components,
                      all of them (``["de_attention", "de_attn_core"]``);
                      none: every op of the step
  ``"less_kernels"``  prefixes of op names whose self time is taken out
                      (``["splash_"]``: the layout's time without the
                      kernels')
  ``"pass"``          absent: all three passes; ``"remat"``: only the ops
                      of the rebuilt forward

and its ``.py`` is ``read = scope_parts.reader(__file__)``. A later family
adds metric files and no attribution file.

The three passes, by an op's name stack: ``rematted_computation`` among its
components: the rebuilt forward; else a component inside ``transpose(``: the
backward; else the forward. A scope of the program is a component that
starts with ``de_`` (``tests/test_scopes.py`` holds the vocabulary to that);
an op's place is the chain of them, outermost first. A fusion the compiler
left without a name stack of its own goes where most of its instructions
lie, scope by scope from the outermost; an op without any, inside a
``while``, goes where its holder went; one with none at all counts as
forward under no scope. XLA fuses across a part's line, so a part is exact
to a fusion: ``straddle`` is the share of a layer's self time in fusions
whose place holds under 80% of their instructions.

A part with no op reads 0.0, as a scope of ``scope_reduce`` does: a program
older than the parts (the parent of the PR that added them) reads 0.0 under
each of them and its rebuilt forward as it is. (``result_line.validate``
refuses a traced line that leaves a declared metric out, so a reader of a
metric on an older cell's list may not return ``None``.)
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import statistics
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmark import scope_reduce
from benchmark.trace_reduce import nesting, op_name

REMAT = "rematted_computation"     # jax.checkpoint's rebuilt forward
PASSES = ("forward", "remat", "backward")
SCOPE_PREFIX = "de_"
AGREE = 0.8     # a fusion under this share of agreeing instructions straddles

Chain = Tuple[str, ...]


def pass_of(name_stack: str) -> str:
  """Which of ``PASSES`` an op of this name stack belongs to."""
  parts = scope_reduce._parts(name_stack)
  if any(inner == REMAT for _, inner in parts):
    return "remat"
  if any("transpose(" in part for part, _ in parts):
    return "backward"
  return "forward"


def chain_of(name_stack: str) -> Chain:
  """The program's scopes in a name stack, outermost first, whole
  components only; a scope entered again inside itself (the backward's
  ``transpose(jvp(de_model))/jvp(de_model)``) once."""
  chain: List[str] = []
  for _, inner in scope_reduce._parts(name_stack):
    if inner.startswith(SCOPE_PREFIX) and chain[-1:] != [inner]:
      chain.append(inner)
  return tuple(chain)


@dataclasses.dataclass(frozen=True)
class Place:
  """Where an op's self time goes. ``agree``: the share of a fusion's
  instructions (those under some scope) that lie at exactly this chain;
  ``beside``: the commonest other chain among them."""
  chain: Chain
  which: str
  agree: float = 1.0
  beside: Optional[Chain] = None


def _majority(chains: Sequence[Chain]) -> Chain:
  """Scope by scope from the outermost: the commonest next scope among the
  chains that agree so far, until most of them end."""
  chain: Chain = ()
  while True:
    n = len(chain)
    votes = collections.Counter(c[n] if len(c) > n else None for c in chains)
    best = votes.most_common(1)[0][0]
    if best is None:
      return chain
    chain += (best,)
    chains = [c for c in chains if c[:n + 1] == chain]


def place_of(names: scope_reduce.OpNames, op: str) -> Optional[Place]:
  """The op's own name stack where it names a top-level scope, else the
  vote of the instructions inside it; ``None`` where the trace knows no
  scope for it."""
  scoped = lambda s: scope_reduce.layer_of(s)[0] is not None
  own = names.own.get(op, "")
  inside = [s for s in names.inside.get(op, ()) if scoped(s)]
  if scoped(own):
    chain, which = chain_of(own), pass_of(own)
  elif inside:
    chain = _majority([chain_of(s) for s in inside])
    which = collections.Counter(
        pass_of(s) for s in inside
        if chain_of(s)[:len(chain)] == chain).most_common(1)[0][0]
  else:
    return None
  if not inside:
    return Place(chain, which)
  others = collections.Counter(chain_of(s) for s in inside)
  here = others.pop(chain, 0)
  return Place(chain, which, here / len(inside),
               others.most_common(1)[0][0] if others else None)


Key = Tuple[Chain, str, str]   # an op's chain, its pass, its name


@dataclasses.dataclass
class Parts:
  """One trace attributed once: ``per_step[device][(chain, pass, op)]`` is a
  list of self ns, one entry a traced step; ``places``: every op's."""
  per_step: List[Dict[Key, List[float]]]
  places: Dict[str, Optional[Place]]

  def ms(self, want: Callable[[Key], bool]) -> float:
    """Per-step sum of self time over the keys wanted, median over steps,
    mean over devices; 0.0 where no op of the trace is wanted."""
    per_dev = []
    for by_key in self.per_step:
      rows = [v for k, v in by_key.items() if want(k)]
      per_dev.append(statistics.median(sum(v) for v in zip(*rows))
                     if rows else 0.0)
    return statistics.fmean(per_dev) * 1e-6

  def straddles(self, op: str) -> bool:
    place = self.places.get(op)
    return place is not None and place.agree < AGREE


def attribute(red, names: scope_reduce.OpNames) -> Parts:
  """Every op event of ``red`` (a ``trace_reduce.Reduced``) goes to one
  (chain of scopes, pass) by its self time."""
  places: Dict[str, Optional[Place]] = {}
  nowhere = Place((), "forward")
  per_step = []
  for steps, ops in zip(red.steps, red.ops):
    by_key: Dict[Key, List[float]] = {}
    self_ns, parent, order = nesting(ops)
    placed: List[Optional[Place]] = [None] * len(ops)
    for i in order:  # holders first
      op = op_name(ops[i][0])
      if op not in places:
        places[op] = place_of(names, op)
      placed[i] = places[op]
      if placed[i] is None and parent[i] >= 0:
        placed[i] = placed[parent[i]]
    for i, (name, _, _, k) in enumerate(ops):
      if k >= 0:
        place = placed[i] or nowhere
        key = (place.chain, place.which, op_name(name))
        by_key.setdefault(key, [0.0] * len(steps))[k] += self_ns[i]
    per_step.append(by_key)
  return Parts(per_step, places)


# ---- what a metric's file asks for -----------------------------------------
def _selector(spec: Dict[str, Any]) -> Callable[[Key], bool]:
  scopes = tuple(spec.get("scopes", ()))
  kernels = tuple(spec.get("less_kernels", ()))
  which = spec.get("pass")
  if which is not None and which not in PASSES:
    raise ValueError(f"{spec.get('name')}: pass {which!r} is none of {PASSES}")

  def want(key: Key) -> bool:
    chain, pass_, op = key
    return all(s in chain for s in scopes) \
        and (which is None or pass_ == which) \
        and not op.startswith(kernels)
  return want


def metric_specs(cell) -> List[Dict[str, Any]]:
  """The files of the cell's metrics that this reader reads (those that say
  ``scopes``)."""
  out = []
  for m in cell.per_layer:
    with open(os.path.join(cell.root, "benchmark", "layer_metrics",
                           f"{m['name']}.json")) as f:
      spec = json.load(f)
    if "scopes" in spec:
      out.append(spec)
  return out


def parts(red, ctx: Dict[str, Any]) -> Parts:
  """The run's trace attributed, once per run (kept in ``ctx``): the first
  reader that asks opens the ``.xplane.pb`` and prints the tables on the
  lines for people: a table for each layer that the cell's metric files
  name first in their ``scopes``, its kernels by their ``less_kernels``."""
  if "scope_parts" not in ctx:
    cell = ctx["cell"]
    files = glob.glob(os.path.join(cell.root, ".bench_trace", cell.name,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
      raise RuntimeError(f"expected one .xplane.pb of {cell.name}, "
                         f"found {files}")
    names = scope_reduce.read_op_names(files[0], red.steps[0][0][0])
    ctx["scope_parts"] = attribute(red, names)
    print(table(ctx["scope_parts"], layers_of(metric_specs(cell))),
          flush=True)
  return ctx["scope_parts"]


def layers_of(specs_: List[Dict[str, Any]]) -> Dict[str, Tuple[str, ...]]:
  """layer -> the prefixes of its kernels' names, from the metric files."""
  layers: Dict[str, set] = {}
  for s in specs_:
    if s["scopes"]:
      layers.setdefault(s["scopes"][0], set()).update(
          s.get("less_kernels", ()))
  return {k: tuple(sorted(v)) for k, v in layers.items()}


def reader(metric_py: str):
  """``read(reduced, ctx)`` of the metric whose ``.json`` lies beside
  ``metric_py``."""
  with open(os.path.splitext(metric_py)[0] + ".json") as f:
    want = _selector(json.load(f))
  return lambda red, ctx: parts(red, ctx).ms(want)


# ---- for people --------------------------------------------------------------
def _row(label: str, got: Parts, want: Callable[[Key], bool]) -> str:
  cells = [got.ms(lambda k, p=p: k[1] == p and want(k))
           for p in PASSES] + [got.ms(want)]
  return f"    {label:<44}" + "".join(f"{c:11.3f}" for c in cells)


def table(got: Parts, layers: Dict[str, Tuple[str, ...]]) -> str:
  """A table a layer (``layers``: layer -> the prefixes of its kernels'
  names): rows the layer's parts, its kernels and what is left of every
  scope that holds parts; columns forward, rebuilt forward, backward, sum;
  ``straddle`` under it. Last, every op of the step by pass."""
  head = "".join(f"{c:>11}" for c in ("forward", "rebuilt", "backward", "sum"))
  lines = ["parts by pass (self time, ms a step: median over steps, mean "
           "over devices)"]
  keys = {k for dev in got.per_step for k in dev}
  for layer, kernels in layers.items():
    inside = {k for k in keys if layer in k[0]}
    if not inside:
      continue
    below = lambda k: k[0][k[0].index(layer) + 1:]
    is_kernel = lambda k: k[2].startswith(kernels)
    rows = sorted({(below(k), is_kernel(k)) for k in inside})
    lines.append(f"  {layer:<46}{head}")
    for sub, kernel in rows:
      holds_more = any(s[:len(sub)] == sub and len(s) > len(sub)
                       for s, _ in rows)
      label = "/".join(sub) if sub else "(the layer's own)"
      if kernel:
        label += ": " + ",".join(f"{p}*" for p in kernels)
      elif sub and holds_more:
        label += " (its own)"
      lines.append(_row(label, got, lambda k, sub=sub, kernel=kernel: (
          k in inside and below(k) == sub and is_kernel(k) == kernel)))
    lines.append(_row("all", got, lambda k: k in inside))
    whole = got.ms(lambda k: k in inside)
    smeared = got.ms(lambda k: k in inside and got.straddles(k[2]))
    worst = sorted(
        ((got.ms(lambda k, op=op: k in inside and k[2] == op), op)
         for op in {k[2] for k in inside if got.straddles(k[2])}),
        reverse=True)
    shown = []
    for ms, op in worst[:4]:
      place = got.places[op]
      shown.append(
          f"{op} {ms:.3f} {'/'.join(place.chain[-2:])}"
          f"|{'/'.join((place.beside or ())[-2:]) or '-'}"
          f" {100 * place.agree:.0f}%")
    lines.append(
        f"    straddle {100 * smeared / whole if whole else 0.0:.2f}% of the "
        f"layer in fusions whose place holds under {100 * AGREE:.0f}% of "
        "their instructions" + (": " + "; ".join(shown) if shown else ""))
  lines.append(f"  {'every op of the step':<46}{head}")
  lines.append(_row("under a scope or none", got, lambda k: True))
  lines.append(_row("of them under no scope", got, lambda k: not k[0]))
  return "\n".join(lines)
