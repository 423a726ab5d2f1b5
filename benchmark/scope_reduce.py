"""From the program's scopes to per-layer device time.

The program marks its stages with ``jax.named_scope`` (the vocabulary is
``distributed_embeddings_tpu/telemetry/scopes.py``; the names are repeated
here as text, because this file also has to read the trace of a program
that has no such module). A scope lands in the ``op_name`` metadata of every
HLO op traced under it, e.g.

    jit(step_fn)/jit(local_step)/transpose(jvp(de_combine))/de_onehot/dot_general

``trace_reduce.load_xplane`` keeps ``(event name, start, duration)`` only:
``jax.profiler.ProfileData`` yields an event's own stats and not those of
its ``XEventMetadata``, which is where a device trace keeps what is the same
for every execution of an op. So this file opens the ``.xplane.pb`` itself,
with a small decoder of the protobuf wire format that skips the ``lines``
(the bulk of the file) and decodes the metadata tables.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import statistics
import struct
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmark.trace_reduce import DEVICE_PLANE, nesting, op_name

# ---- protobuf wire format ---------------------------------------------------
# xplane.proto field numbers (tensorflow/tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 3, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
LINE_NAME = 2
EVENT_METADATA_ID, EVENT_METADATA_NAME, EVENT_METADATA_DISPLAY_NAME, \
    EVENT_METADATA_STATS = 1, 2, 4, 5
STAT_METADATA_ID, STAT_METADATA_NAME = 1, 2
STAT_METADATA_REF, STAT_DOUBLE, STAT_UINT64, STAT_INT64, STAT_STR, \
    STAT_BYTES, STAT_REF = 1, 2, 3, 4, 5, 6, 7

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
  value, shift = 0, 0
  while True:
    b = buf[at]
    at += 1
    value |= (b & 0x7F) << shift
    if not b & 0x80:
      return value, at
    shift += 7


def fields(buf: bytes, begin: int = 0, end: int = -1
           ) -> Iterator[Tuple[int, int, Any]]:
  """(field number, wire type, value) of one message lying in
  ``buf[begin:end]``. A length-delimited value comes as ``(begin, end)``
  offsets into ``buf``, so a field that is skipped is never copied."""
  at = begin
  end = len(buf) if end < 0 else end
  while at < end:
    tag, at = _varint(buf, at)
    number, kind = tag >> 3, tag & 7
    if kind == VARINT:
      value, at = _varint(buf, at)
    elif kind == BYTES:
      size, at = _varint(buf, at)
      value = (at, at + size)
      at += size
    elif kind == FIXED64:
      value = int.from_bytes(buf[at:at + 8], "little")
      at += 8
    elif kind == FIXED32:
      value = int.from_bytes(buf[at:at + 4], "little")
      at += 4
    else:
      raise ValueError(f"wire type {kind} at byte {at}: not a protobuf of "
                       "this schema")
    yield number, kind, value
  if at != end:
    raise ValueError("a field runs past the end of its message")


def _text(buf: bytes, span: Tuple[int, int]) -> str:
  return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, span: Tuple[int, int]):
  """One ``map<int64, Message>`` entry -> (key, span of the value)."""
  key, value = 0, None
  for number, _, v in fields(buf, *span):
    if number == MAP_KEY:
      key = v
    elif number == MAP_VALUE:
      value = v
  return key, value


def read_planes(buf: bytes) -> List[Dict[str, Any]]:
  """Per plane: its name, the names of its lines, its stat names by id and
  its event metadata by id (``name``, ``display_name`` and ``stats``: stat
  name -> text, number, or the span of a bytes value). Events are skipped."""
  planes = []
  for number, _, span in fields(buf):
    if number != SPACE_PLANES:
      continue
    plane = {"name": "", "lines": [], "stat_names": {}, "events": {}}
    raw_events = []
    for n, _, v in fields(buf, *span):
      if n == PLANE_NAME:
        plane["name"] = _text(buf, v)
      elif n == PLANE_LINES:
        for ln, _, lv in fields(buf, *v):
          if ln == LINE_NAME:
            plane["lines"].append(_text(buf, lv))
            break
      elif n == PLANE_STAT_METADATA:
        _, value = _map_entries(buf, v)
        sid, name = 0, ""
        for sn, _, sv in fields(buf, *value):
          if sn == STAT_METADATA_ID:
            sid = sv
          elif sn == STAT_METADATA_NAME:
            name = _text(buf, sv)
        plane["stat_names"][sid] = name
      elif n == PLANE_EVENT_METADATA:
        raw_events.append(_map_entries(buf, v))
    for key, value in raw_events:  # after the loop: stat names are complete
      if value is None:
        continue
      meta = {"name": "", "display_name": "", "stats": {}}
      mid = key
      for en, _, ev in fields(buf, *value):
        if en == EVENT_METADATA_ID:
          mid = ev
        elif en == EVENT_METADATA_NAME:
          meta["name"] = _text(buf, ev)
        elif en == EVENT_METADATA_DISPLAY_NAME:
          meta["display_name"] = _text(buf, ev)
        elif en == EVENT_METADATA_STATS:
          name, val = _stat(buf, ev, plane["stat_names"])
          meta["stats"][name] = val
      plane["events"][mid] = meta
    planes.append(plane)
  return planes


def _stat(buf: bytes, span: Tuple[int, int], stat_names: Dict[int, str]):
  name, value = "", None
  for n, _, v in fields(buf, *span):
    if n == STAT_METADATA_REF:
      name = stat_names.get(v, f"#{v}")
    elif n == STAT_STR:
      value = _text(buf, v)
    elif n == STAT_REF:
      value = stat_names.get(v, f"#{v}")
    elif n == STAT_BYTES:
      value = v  # a span: the caller slices what it wants
    elif n in (STAT_UINT64, STAT_INT64):
      value = v
    elif n == STAT_DOUBLE:
      value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
  return name, value


# ---- the vocabulary, and an op's place in it --------------------------------
TOP_LEVEL = ("de_route", "de_gather", "de_combine", "de_model", "de_loss",
             "de_dense_update", "de_apply")
CHILDREN = ("de_onehot", "de_exchange", "de_interact")
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")


def _parts(name_stack: str) -> List[Tuple[str, str]]:
  """(path component as written, the name inside its ``jvp(``/``transpose(``
  wrappers) of the first of the ``;``-joined names an op may carry."""
  out = []
  for part in name_stack.split(";")[0].split("/"):
    inner = _WRAPPED.match(part)
    out.append((part, inner.group(1) if inner else part))
  return out


def layer_of(name_stack: str) -> Tuple[Optional[str], bool]:
  """(top-level scope, backward) of a name stack; (None, False) without
  one. The outermost top-level scope wins; it is backward where its
  component sits inside ``transpose(``."""
  for part, inner in _parts(name_stack):
    if inner in TOP_LEVEL:
      return inner, "transpose(" in part
  return None, False


def scope_chain(name_stack: str) -> Tuple[str, ...]:
  """The registry's names in the name stack, outermost first, whole
  components only."""
  return tuple(inner for _, inner in _parts(name_stack)
               if inner in TOP_LEVEL or inner in CHILDREN)


# ---- where the trace keeps an op's name stack -------------------------------
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_PROGRAM_ID = re.compile(r"\((\d+)\)")
HLO_PROTO_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
NAME_STAT = "tf_op"


class NoNameStacks(RuntimeError):
  """The trace has neither source of an op's name stack."""


@dataclasses.dataclass
class OpNames:
  """Per op of the step's program (by the name the trace shows, ``fusion.7``):
  its own name stack, and the name stacks of the instructions inside the
  computation it calls where it is a fusion."""
  own: Dict[str, str]
  inside: Dict[str, List[str]]

  def name_stack(self, op: str) -> str:
    """The op's own name stack. The TPU compiler leaves many fusions (and
    every copy it makes) without one, while the instructions inside keep
    theirs: such a fusion goes to the top-level scope that most of its
    instructions lie under (the first of them in the registry's order on a
    tie), and the name returned is one of theirs."""
    own = self.own.get(op, "")
    if layer_of(own)[0] is not None:
      return own
    votes: Dict[Tuple[str, bool], List[str]] = {}
    for stack in self.inside.get(op, ()):
      scope, backward = layer_of(stack)
      if scope is not None:
        votes.setdefault((scope, backward), []).append(stack)
    if not votes:
      return own
    best = max(votes, key=lambda k: (len(votes[k]), -TOP_LEVEL.index(k[0]),
                                     not k[1]))
    # among the winners, the commonest chain of scopes: the children count too
    chains = collections.Counter(scope_chain(s) for s in votes[best])
    chain = chains.most_common(1)[0][0]
    return next(s for s in votes[best] if scope_chain(s) == chain)


def names_from_hlo_text(text: str) -> OpNames:
  """Own and inner name stacks out of an HLO module's text."""
  own: Dict[str, str] = {}
  calls: Dict[str, str] = {}
  body: Dict[str, List[str]] = {}
  current: Optional[str] = None
  for line in text.splitlines():
    head = _COMPUTATION.match(line)
    if head:
      current = head.group(1)
      body[current] = []
      continue
    if line.startswith("}"):
      current = None
      continue
    inst = _INSTRUCTION.match(line)
    if inst is None or current is None:
      continue
    name = _OP_NAME.search(line)
    if name:
      own[inst.group(1)] = name.group(1)
      body[current].append(name.group(1))
    called = _CALLS.search(line)
    if called and " fusion(" in line:
      calls[inst.group(1)] = called.group(1)
  inside = {op: body.get(comp, []) for op, comp in calls.items()}
  return OpNames(own, inside)


def _hlo_text(proto: bytes) -> Optional[str]:
  """The text of the ``HloProto``'s module, None where this jaxlib cannot
  give it."""
  module = None
  for number, kind, span in fields(proto):
    if number == 1 and kind == BYTES:  # HloProto.hlo_module
      module = proto[span[0]:span[1]]
  if module is None:
    return None
  try:
    from jax._src.lib import xla_client
    return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module).to_string()
  except (ImportError, AttributeError):
    return None


def read_op_names(path: str, step_module: str) -> OpNames:
  """The name stacks of the step program's ops, out of an ``.xplane.pb``.

  Two sources, as found on a v5e trace of this installation (PERF.md, PR
  26), both used: (a) the ``tf_op`` stat on an op's ``XEventMetadata``
  (``<op_name>:<op_type>``), which only the ops with an ``op_name`` of
  their own have; (b) the program's ``HloProto`` in the ``/host:metadata``
  plane, which also names the instructions inside each fusion. Where two
  programs share an op name the step's is kept, by ``program_id``.
  ``step_module`` is the name of the step's event on ``XLA Modules``:
  ``jit_step_fn(<program id>)``."""
  with open(path, "rb") as f:
    buf = f.read()
  planes = read_planes(buf)
  pid = _PROGRAM_ID.search(step_module)
  pid = int(pid.group(1)) if pid else None
  names = OpNames({}, {})
  found = False
  for plane in planes:  # (b) first, (a) over it: the op's own stat is final
    if plane["name"] != HLO_PROTO_PLANE:
      continue
    for mid, meta in plane["events"].items():
      span = meta["stats"].get(HLO_PROTO_STAT)
      if not isinstance(span, tuple):
        continue
      if pid is not None and mid != pid and f"({pid})" not in meta["name"]:
        continue
      text = _hlo_text(buf[span[0]:span[1]])
      if text is not None:
        names = names_from_hlo_text(text)
        found = True
  for plane in planes:
    if not DEVICE_PLANE.match(plane["name"]):
      continue
    for meta in plane["events"].values():
      stats = meta["stats"]
      if NAME_STAT not in stats:
        continue
      found = True
      if pid is not None and stats.get("program_id", pid) != pid:
        continue
      op = meta["display_name"] or op_name(meta["name"])
      names.own[op] = str(stats[NAME_STAT]).rsplit(":", 1)[0]
  if not found:
    raise NoNameStacks(
        f"{path}: no op carries a {NAME_STAT!r} stat and the "
        f"{HLO_PROTO_PLANE} plane holds no readable {HLO_PROTO_STAT!r}: "
        "this trace cannot say which scope an op ran under")
  return names


# ---- self time per scope ----------------------------------------------------
UNSCOPED = "(no scope)"


@dataclasses.dataclass
class Scoped:
  """One trace attributed once. ``per_step[device][(scope, backward)]`` and
  ``child_step[device][child]`` are lists of ns, one entry per traced step;
  ``op_ms`` is the first device's mean self ms per step, by scope and op."""
  per_step: List[Dict[Tuple[str, bool], List[float]]]
  child_step: List[Dict[str, List[float]]]
  op_ms: Dict[str, Dict[str, float]]

  def _median_ms(self, pick) -> float:
    per_dev = []
    for dev in pick:
      per_dev.append(statistics.median(dev) if dev else 0.0)
    return statistics.fmean(per_dev) * 1e-6 if per_dev else 0.0

  def scope_ms(self, *scopes_: str, backward: Optional[bool] = None) -> float:
    """Per-step sum of self time under the top-level scopes named, median
    over steps, mean over devices; 0.0 where no op lies under them."""
    rows = []
    for dev in self.per_step:
      keys = [k for k in dev if k[0] in scopes_
              and (backward is None or k[1] == backward)]
      rows.append([sum(dev[k][i] for k in keys)
                   for i in range(self._n(dev))] if keys else [])
    return self._median_ms(rows)

  def child_ms(self, child: str) -> float:
    return self._median_ms([dev.get(child, []) for dev in self.child_step])

  def unscoped_pct(self) -> float:
    """Share of a step's self time under no top-level scope: median over
    steps, mean over devices; 100.0 where nothing has a scope."""
    per_dev = []
    for dev in self.per_step:
      shares = []
      for i in range(self._n(dev)):
        total = sum(v[i] for v in dev.values())
        bare = sum(v[i] for k, v in dev.items() if k[0] == UNSCOPED)
        if total > 0:
          shares.append(100.0 * bare / total)
      if shares:
        per_dev.append(statistics.median(shares))
    return statistics.fmean(per_dev) if per_dev else 100.0

  @staticmethod
  def _n(dev) -> int:
    return len(next(iter(dev.values()))) if dev else 0

  def table(self, longest: int = 5) -> str:
    """For people: per top-level scope forward and backward ms a step, and
    the longest ops beneath it by the names the trace shows."""
    lines = ["scopes (self time, ms a step: median over steps, mean over "
             "devices; ops: first device, mean over steps)"]
    for scope in TOP_LEVEL + (UNSCOPED,):
      fwd = self.scope_ms(scope, backward=False)
      bwd = self.scope_ms(scope, backward=True)
      ops = sorted(self.op_ms.get(scope, {}).items(), key=lambda kv: -kv[1])
      shown = " ".join(f"{k}={v:.3f}" for k, v in ops[:longest])
      lines.append(f"  {scope:<16} fwd {fwd:9.3f}  bwd {bwd:9.3f}  | {shown}")
    for child in CHILDREN:
      lines.append(f"  {'  ' + child:<16} all {self.child_ms(child):9.3f}")
    lines.append(f"  unscoped_pct {self.unscoped_pct():.3f}")
    return "\n".join(lines)

  def scope_of(self, op: str) -> str:
    """The scope most of an op's self time went to (first device)."""
    held = {s: ops[op] for s, ops in self.op_ms.items() if op in ops}
    return max(held, key=held.get) if held else UNSCOPED


def attribute(red, names: OpNames) -> Scoped:
  """Every op event of ``red`` (a ``trace_reduce.Reduced``) goes to one
  (top-level scope, direction) by its self time. An op that has no scope of
  its own and runs inside another event (the body of a ``while``: the
  compiler names few of its ops) is its holder's; an op with no scope at
  all is data, under ``UNSCOPED``."""
  Place = Tuple[Tuple[str, bool], Tuple[str, ...]]
  own: Dict[str, Optional[Place]] = {}

  def own_place(op: str) -> Optional[Place]:
    if op not in own:
      stack = names.name_stack(op)
      scope, backward = layer_of(stack)
      own[op] = None if scope is None else (
          (scope, backward),
          tuple(c for c in scope_chain(stack) if c in CHILDREN))
    return own[op]

  per_step, child_step = [], []
  op_ns: Dict[str, Dict[str, float]] = {}
  for d, (steps, ops) in enumerate(zip(red.steps, red.ops)):
    n = len(steps)
    by_scope: Dict[Tuple[str, bool], List[float]] = {}
    by_child: Dict[str, List[float]] = {}
    self_ns, parent, order = nesting(ops)
    placed: List[Optional[Place]] = [None] * len(ops)
    for i in order:  # holders first
      placed[i] = own_place(op_name(ops[i][0]))
      if placed[i] is None and parent[i] >= 0:
        placed[i] = placed[parent[i]]
    for i, (name, _, _, k) in enumerate(ops):
      if k < 0:
        continue
      key, children = placed[i] or ((UNSCOPED, False), ())
      by_scope.setdefault(key, [0.0] * n)[k] += self_ns[i]
      for c in children:
        by_child.setdefault(c, [0.0] * n)[k] += self_ns[i]
      if d == 0:
        ops_of = op_ns.setdefault(key[0], {})
        op = op_name(name)
        ops_of[op] = ops_of.get(op, 0.0) + self_ns[i]
    per_step.append(by_scope)
    child_step.append(by_child)
  n0 = max(1, len(red.steps[0]))
  op_ms = {s: {op: ns * 1e-6 / n0 for op, ns in ops.items()}
           for s, ops in op_ns.items()}
  return Scoped(per_step, child_step, op_ms)


def scoped(red, ctx: Dict[str, Any]) -> Scoped:
  """The run's trace attributed, once per run: the first reader that asks
  opens the ``.xplane.pb`` (still on disk while readers run), prints the
  table on the lines for people, and leaves the result in ``ctx``."""
  if "scoped" not in ctx:
    cell = ctx["cell"]
    files = glob.glob(os.path.join(cell.root, ".bench_trace", cell.name,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
      raise RuntimeError(f"expected one .xplane.pb of {cell.name}, "
                         f"found {files}")
    names = read_op_names(files[0], red.steps[0][0][0])
    ctx["scoped"] = attribute(red, names)
    print(ctx["scoped"].table(), flush=True)
    # the ops of breakdown.device_ops (longest by summed duration, a while
    # with its body), each with the scope it was charged to
    print("  longest ops -> scope: " + " ".join(
        f"{op}->{ctx['scoped'].scope_of(op)}" for op, _ in red.top_ops()),
        flush=True)
  return ctx["scoped"]
