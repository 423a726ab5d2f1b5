"""From a profiler trace to per-layer metrics.

The reducer works on a plain structure, so that it can be checked on a small
recorded fixture (`tests/benchmark/data/trace_fixture.json`):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

:func:`load_xplane` fills it from the ``.xplane.pb`` the JAX profiler writes,
with ``jax.profiler.ProfileData`` (nothing else is installed). What a TPU
trace holds, as read on this installation (PERF.md, PR 25): one plane per
chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one
event per HLO op, named by its HLO text ``%<op> = <shape> <opcode>(...``), and
a plane ``/host:CPU`` whose main-thread line carries the benchmark's
``jax.profiler.TraceAnnotation`` spans. That line is named after the process
(``python``, or ``python3`` when started so: PR 25's proof run from the
archive found that out); every other thread's line is ``<name>/<tid>``. All
planes share one clock.
"""

from __future__ import annotations

import bisect
import re
import statistics
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench_"
COLLECTIVE_OPCODES = ("all-to-all", "all-reduce", "all-gather",
                      "reduce-scatter", "collective-permute")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
OUTSIDE = "outside the benchmark's spans"

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def is_main_thread(line_name: str) -> bool:
  """The host line of the process's main thread: the only one without a
  ``/<tid>`` suffix, whatever the interpreter was started as."""
  return "/" not in line_name


def op_name(event_name: str) -> str:
  """``%fusion.6 = f32[8]{0} fusion(...)`` -> ``fusion.6``."""
  return event_name.split(" = ", 1)[0].lstrip("%").strip()


def opcode(event_name: str) -> str:
  """The HLO opcode of an ``XLA Ops`` event, '' where the name has none."""
  parts = event_name.split(" = ", 1)
  if len(parts) < 2:
    return ""
  m = _OPCODE.search(parts[1])
  return m.group(1) if m else ""


def is_collective(event_name: str) -> bool:
  code = opcode(event_name)
  return any(code.startswith(c) for c in COLLECTIVE_OPCODES)


def load_xplane(path: str) -> Dict[str, Any]:
  """The planes and lines the reducer reads, out of an ``.xplane.pb``."""
  import jax
  data = jax.profiler.ProfileData.from_file(path)
  planes = []
  for plane in data.planes:
    if DEVICE_PLANE.match(plane.name):
      keep = lambda line: line.name in ("XLA Modules", "XLA Ops")
      want = lambda name: True
    elif plane.name == HOST_PLANE:
      keep = lambda line: is_main_thread(line.name)
      want = lambda name: name.startswith(SPAN_PREFIX)
    else:
      continue
    lines = []
    for line in plane.lines:
      if keep(line):
        lines.append({"name": line.name, "events": [
            [e.name, float(e.start_ns), float(e.duration_ns)]
            for e in line.events if want(e.name)]})
    planes.append({"name": plane.name, "lines": lines})
  return {"planes": planes}


def _line(plane: Dict[str, Any], name: str) -> List[Event]:
  for line in plane["lines"]:
    if line["name"] == name:
      return sorted((tuple(e) for e in line["events"]), key=lambda e: e[1])
  return []


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
  merged: List[List[float]] = []
  for a, b in sorted(intervals):
    if merged and a <= merged[-1][1]:
      merged[-1][1] = max(merged[-1][1], b)
    else:
      merged.append([a, b])
  return merged


def nesting(ops) -> Tuple[List[float], List[int], List[int]]:
  """Per event of one device's ``XLA Ops`` line, in the order given: its
  self time (its duration less what the events nested in it cover) and the
  index of the event that holds it (-1 at the top); and the indices in
  timeline order, holders before what they hold. A ``while`` or a
  conditional holds its body's ops; an event's children are those that
  start before it ends."""
  order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
  self_ns = [0.0] * len(ops)
  parent = [-1] * len(ops)
  open_: List[Tuple[int, float]] = []  # (index, end) of the enclosing events
  for i in order:
    _, start, dur, _ = ops[i]
    while open_ and open_[-1][1] <= start:
      open_.pop()
    self_ns[i] = dur
    if open_:
      parent[i], parent_end = open_[-1]
      self_ns[parent[i]] -= min(dur, parent_end - start)
    open_.append((i, start + dur))
  return self_ns, parent, order


class Reduced:
  """One trace, reduced once; the metric readers pick from it."""

  def __init__(self, trace: Dict[str, Any], module_pattern: str):
    self.module_pattern = re.compile(module_pattern)
    self.devices = [p for p in trace["planes"]
                    if DEVICE_PLANE.match(p["name"])]
    if not self.devices:
      raise ValueError("the trace has no /device:TPU:<n> plane")
    host = [p for p in trace["planes"] if p["name"] == HOST_PLANE]
    self.spans: List[Event] = []
    for p in host:
      for line in p["lines"]:
        if is_main_thread(line["name"]):
          self.spans += [tuple(e) for e in line["events"]
                         if e[0].startswith(SPAN_PREFIX)]
    self.spans.sort(key=lambda e: e[1])
    # per device: the step programs, and every op with the step it ran in
    self.steps: List[List[Event]] = []
    self.ops: List[List[Tuple[str, float, float, int]]] = []
    self.busy: List[List[List[float]]] = []
    for plane in self.devices:
      steps = [e for e in _line(plane, "XLA Modules")
               if self.module_pattern.search(e[0])]
      if not steps:
        raise ValueError(f"{plane['name']}: no module matches "
                         f"{module_pattern!r}")
      starts = [s[1] for s in steps]
      lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
      ops, spans = [], []
      for name, start, dur in _line(plane, "XLA Ops"):
        if start < lo or start >= hi:
          continue
        k = bisect.bisect_right(starts, start) - 1
        inside = start < steps[k][1] + steps[k][2]
        ops.append((name, start, dur, k if inside else -1))
        spans.append((start, min(start + dur, hi)))
      self.steps.append(steps)
      self.ops.append(ops)
      self.busy.append(_union(spans))

  # ---- the device line of the result --------------------------------------
  def window_s(self) -> float:
    return max(s[-1][1] + s[-1][2] - s[0][1] for s in self.steps) * 1e-9

  def busy_s(self) -> float:
    """Mean over devices of each device's union of op intervals inside its
    own window: it cannot exceed the window."""
    per_dev = [sum(b - a for a, b in u) for u in self.busy]
    return statistics.fmean(per_dev) * 1e-9

  def n_steps(self) -> int:
    return min(len(s) for s in self.steps)

  # ---- reductions ----------------------------------------------------------
  def module_ms(self) -> float:
    """Median device duration of the step program, mean over devices."""
    return statistics.fmean(
        statistics.median(e[2] for e in steps) for steps in self.steps) * 1e-6

  def per_step_ms(self, select: Callable[[str], bool]) -> Optional[float]:
    """Sum over the selected ops inside each step, median over steps, mean
    over devices. None where no device ran such an op."""
    per_dev = []
    for steps, ops in zip(self.steps, self.ops):
      sums = [0.0] * len(steps)
      hit = False
      for name, _, dur, k in ops:
        if k >= 0 and select(name):
          sums[k] += dur
          hit = True
      if hit:
        per_dev.append(statistics.median(sums))
    if not per_dev:
      return None
    return statistics.fmean(per_dev) * 1e-6

  def span_ms(self, name: str) -> Optional[float]:
    durs = [e[2] for e in self.spans if e[0] == name]
    return statistics.median(durs) * 1e-6 if durs else None

  def idle_pct(self) -> float:
    return 100.0 * (1.0 - self.busy_s() / self.window_s())

  # ---- breakdown -----------------------------------------------------------
  def top_ops(self, n: int = 10) -> List[List[Any]]:
    """Seconds of self time per op name over the window, mean over devices:
    a ``while`` counts once, less its body's ops, which count as
    themselves."""
    total: Dict[str, float] = {}
    for ops in self.ops:
      for (name, _, _, _), own in zip(ops, nesting(ops)[0]):
        key = op_name(name)
        total[key] = total.get(key, 0.0) + own
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9 / len(self.ops)] for k, v in rows]

  def idle_gaps(self, n: int = 10) -> List[List[Any]]:
    """Idle seconds of the first device, by the benchmark span the host was
    in at the middle of each gap."""
    starts = [e[1] for e in self.spans]
    total: Dict[str, float] = {}
    steps = self.steps[0]
    lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
    union = [[lo, lo]] + self.busy[0] + [[hi, hi]]
    for (_, b), (a2, _) in zip(union, union[1:]):
      if a2 <= b:
        continue
      mid = 0.5 * (b + a2)
      k = bisect.bisect_right(starts, mid) - 1
      label = OUTSIDE
      # spans do not nest and do not overlap: the one that began last
      if k >= 0 and mid < self.spans[k][1] + self.spans[k][2]:
        label = self.spans[k][0]
      total[f"host: {label}"] = total.get(f"host: {label}", 0.0) + (a2 - b)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in rows]


def reader_from_spec(spec: Dict[str, Any]):
  """A reader for a ``layer_metrics/<metric>.json``: a selector and one of a
  small fixed set of reductions. ``reader(reduced, cell) -> float | None``."""
  kind = spec["reduction"]
  sel = spec.get("selector", {})

  def select(name: str) -> bool:
    if sel.get("collective"):
      return is_collective(name)
    return re.search(sel["op_pattern"], op_name(name)) is not None

  if kind == "span_median_ms":
    return lambda red, cell: red.span_ms(sel["span"])
  if kind == "module_median_ms":
    return lambda red, cell: red.module_ms()
  if kind == "per_step_sum_ms":
    return lambda red, cell: red.per_step_ms(select)
  if kind == "idle_share_pct":
    return lambda red, cell: red.idle_pct()
  if kind == "roofline_pct":
    def roofline(red, cell):
      from benchmark import roofline as rf
      ms = red.per_step_ms(select)
      if ms is None:
        return None
      least_ms = rf.least_ms(spec["bytes_function"], cell)
      return None if least_ms is None else 100.0 * least_ms / ms
    return roofline
  raise ValueError(f"unknown reduction {kind!r}")
