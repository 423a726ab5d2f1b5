"""AST lint pass enforcing repo invariants (stdlib-only, no jax import).

Rules live in a registry (:data:`RULES`); each carries a stable ID, a
severity (``error`` fails the lint, ``warning`` reports), and a one-line
contract. A finding on a line carrying ``# graftlint: disable=<ID>``
(comma-separated IDs, or ``all``) is suppressed — the comment is the
reviewed-and-intentional marker, so every suppression should say why on
the same line or the one above.

The rule catalog (see `docs/ARCHITECTURE.md` §12 for the long form):

==========  =========  =====================================================
ID          severity   invariant
==========  =========  =====================================================
GL101       error      no host sync (``jax.device_get`` /
                       ``.block_until_ready()`` / ``.item()``) inside
                       trace-reachable step-builder code
GL102       error      no ``np.*`` / ``numpy.*`` calls on traced values
                       inside trace-reachable step-builder code
GL103       error      no bare ``except:`` anywhere
GL104       error      durable paths never ``os.rename``/``os.replace``
                       without an fsync earlier in the same function
GL105       error      no wall clock / RNG in durable (checkpoint /
                       manifest) modules — manifests must be deterministic
GL106       error      int32 casts of index ARITHMETIC (overflow at vocab
                       scale) — widen to int64, bound, then narrow a value
GL107       error      every ``pytest.mark.<name>`` is registered in
                       ``pyproject.toml`` (a typo'd marker silently
                       deselects)
GL108       error      fault-injection site literals must be registered in
                       ``resilience.faultinject.SITES``
GL109       error      no raw ``lax.all_to_all`` or ``lax.ppermute`` outside
                       ``parallel/wire.py`` (library-package modules:
                       everywhere; elsewhere: trace-reachable step-builder
                       code) — a raw exchange bypasses the plan's wire
                       contract and the audit's pinned round counts
GL110       error      no ``jax.process_count()``/``process_index()``
                       compared against hardcoded world constants (!= 0/1)
                       in durable modules — elastic pods resize the world
                       between runs; derive shapes from the plan/manifest
GL111       error      train-only surfaces (optax / ``resilience.guards``
                       imports; the step builders, scatter emitters, and
                       guard helpers by name) are unreachable from
                       ``serving/`` modules — the inference path must stay
                       free of optimizer state and commit gates
GL112       error      dynamic-vocabulary translation state mutates only in
                       ``dynvocab/`` host paths — the translator surface
                       (``translate_batch`` / ``translate_dynamic_ids`` /
                       the table/sketch/recycler constructors) never
                       appears in trace-reachable step code
GL113       error      no raw ``time.perf_counter``/``time.monotonic``
                       timing in library modules outside ``telemetry/`` —
                       spans (and ``telemetry.timed`` / the histogram
                       type) are the sanctioned form, so every stage is
                       on one trace and one metrics schema
GL114       error      train-only surfaces (the GL111 list) are
                       unreachable from ``fleet/`` modules — the fleet
                       tier is the serving engine spread over processes,
                       same inference-only contract at fleet scope
GL115       error      trace ids / clock epochs are minted only inside
                       ``telemetry/``: raw ``uuid.*`` / ``secrets`` /
                       ``os.urandom`` / ``time.time_ns`` minting in the
                       request/delta-path packages (``serving/``,
                       ``fleet/``, ``streaming/``) is flagged — ids
                       minted elsewhere never land on one trace, and a
                       second clock-epoch source cannot be correlated
GL116       error      process signaling (``signal.signal`` /
                       ``os.kill`` / ``os.killpg``) only inside
                       ``resilience/`` — preemption handling (SIGTERM
                       drain, SIGKILL chaos, pid liveness probes) is a
                       resilience contract; a second handler elsewhere
                       silently replaces the drain path's disposition
GL117       error      fleet mutation surfaces (``fleet.reshard``,
                       ``apply_fleet``/``set_fleet`` replica-set edits,
                       ``compact_once``/``gc_deltas``/``compact_chain``
                       folds) are unreachable from library modules
                       outside ``control/`` and the surfaces' home
                       packages — mutations route through decision-
                       logged control daemons or operator tools
GL118       error      every multi-controller refusal branch
                       (``jax.process_count() > 1`` raising
                       ``NotImplementedError``) must name a literal
                       reason string AND appear in the checked
                       :data:`REFUSAL_INVENTORY` — closing a refusal
                       without pruning the inventory, or adding one
                       without inventorying it, fails the lint
GL119       error      no raw ``threading.Thread`` / executor
                       construction in the step-adjacent training
                       packages (``tiering/``, ``dynvocab/``,
                       ``resilience/``, ``streaming/``, ``training.py``)
                       outside ``pipeline.py`` — ``HostWorker`` is the
                       one sanctioned host/device overlap surface, so
                       overlap stays bit-exact, joined before
                       accounting, and on one trace
GL126       error      hand-written TPU kernel entry points
                       (``pl.pallas_call`` / ``pltpu.
                       make_async_remote_copy``) live only in
                       ``ops/pallas_*.py`` modules, and every
                       ``DE_TPU_PALLAS_*`` env gate read in the library
                       package must match a :data:`PALLAS_GATE_REGISTRY`
                       entry whose ``_use_pallas_*`` predicate is
                       defined in that file — BOTH ways: an
                       unregistered gate fails at its line, a registry
                       entry whose file no longer reads the env (or
                       lost its predicate) fails as stale
GL124       error      every ``# graftlint: disable=<ID>`` comment must
                       suppress a finding that actually fires on its
                       line, and name a known rule id — stale or typo'd
                       suppressions rot the swept baseline silently
                       (ids owned by the threadlint pass are judged
                       there; see ``EXTERNAL_RULE_IDS``)
==========  =========  =====================================================

The concurrency rules GL120–GL123 and the thread-root registry check
GL125 live in the sibling :mod:`.threadlint` pass (lock discipline,
lock-graph cycles, multi-root mutation, condvar misuse) — same
``Finding`` type, same suppression comment, run side by side by
``tools/graftlint.py``.

Trace-reachable scope (GL101/GL102) is structural: any function nested —
at any depth — inside a module-level builder whose name matches
``make_*step*`` / ``make_*eval*``, or inside one of the private factories
the builders share their pieces through (``_make_*step*``:
``training._make_step_forward``, ``training._make_train_step_pieces``) —
``local_step``, ``body``, ``forward_backward``, ``loss_with``, ``commit``,
... — is traced by ``jax.jit`` / ``shard_map`` when the built step runs. Host syncs there either silently
serialize the device pipeline or break tracing outright; host-side code
(trainers, checkpoint I/O, the builders' own plan-time setup) is
unrestricted. The lookup engine's methods are not statically reachable
this way — the jaxpr audit (:mod:`.jaxpr_audit`) covers them dynamically
end to end.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

STEP_BUILDER_RE = re.compile(r"^_?make_\w*(step|eval)\w*$")
DURABLE_PATH_RE = re.compile(r"(checkpoint|durable)")
SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\s]+)")

# Rule ids owned by the threadlint pass (analysis.threadlint). GL124's
# staleness judgment skips them here — a suppression for a concurrency
# rule only looks stale to astlint because astlint never runs that rule
# — and threadlint judges them in its own pass. A literal set (not an
# import) keeps astlint importable standalone, the property the CLI's
# --ast-only mode depends on.
EXTERNAL_RULE_IDS = frozenset({"GL120", "GL121", "GL122", "GL123", "GL125"})

# pytest's own marks — always registered
BUILTIN_MARKS = frozenset({
    "parametrize", "skip", "skipif", "xfail", "usefixtures",
    "filterwarnings", "tryfirst", "trylast",
})

HOST_SYNC_ATTRS = frozenset({"block_until_ready", "item"})
HOST_SYNC_JAX_FUNCS = frozenset({"device_get"})
WALLCLOCK_CALLS = frozenset({
    ("time", "time"), ("time", "time_ns"),
    ("datetime", "now"), ("datetime", "utcnow"), ("datetime", "today"),
})
RENAME_FUNCS = frozenset({("os", "rename"), ("os", "replace"),
                          ("shutil", "move")})
INT32_NAMES = frozenset({"int32", "uint32"})
FAULT_RULE_METHODS = frozenset({"crash_after", "fail_first"})


@dataclass(frozen=True)
class Finding:
  rule: str
  severity: str
  path: str
  line: int
  message: str

  def render(self) -> str:
    return (f"{self.path}:{self.line}: {self.severity} {self.rule}: "
            f"{self.message}")


@dataclass
class Rule:
  id: str
  severity: str
  title: str
  check: Callable[["ParsedModule"], List[Finding]]


RULES: Dict[str, Rule] = {}


def _rule(rule_id: str, severity: str, title: str):
  def deco(fn):
    RULES[rule_id] = Rule(rule_id, severity, title, fn)
    return fn
  return deco


@dataclass
class LintContext:
  """Repo-level facts rules consult (parsed once per lint run)."""
  registered_markers: frozenset = frozenset()
  fault_sites: Optional[frozenset] = None  # None: registry not found

  @classmethod
  def for_repo(cls, root: str) -> "LintContext":
    return cls(registered_markers=_parse_markers(root),
               fault_sites=_parse_fault_sites(root))


@dataclass
class ParsedModule:
  path: str
  source: str
  tree: ast.Module
  ctx: LintContext
  lines: List[str] = field(init=False)

  def __post_init__(self):
    self.lines = self.source.splitlines()

  def finding(self, rule_id: str, node: ast.AST, msg: str) -> Finding:
    return Finding(rule_id, RULES[rule_id].severity, self.path,
                   getattr(node, "lineno", 0), msg)

  def suppressed(self, f: Finding) -> bool:
    if not (1 <= f.line <= len(self.lines)):
      return False
    m = SUPPRESS_RE.search(self.lines[f.line - 1])
    if not m:
      return False
    ids = {s.strip() for s in m.group(1).split(",")}
    return f.rule in ids or "all" in ids


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
  """``a.b.c`` attribute/name chain as a string, else None."""
  parts = []
  while isinstance(node, ast.Attribute):
    parts.append(node.attr)
    node = node.value
  if isinstance(node, ast.Name):
    parts.append(node.id)
    return ".".join(reversed(parts))
  return None


def _call_pair(call: ast.Call):
  """(module_root, func_name) of a call. The name is the final attribute
  (``x.y.astype`` -> ``astype``, even when the chain roots in another
  call); the root is the leading Name when the chain has one."""
  d = _dotted(call.func)
  if d and "." in d:
    parts = d.split(".")
    return parts[0], parts[-1]
  if isinstance(call.func, ast.Attribute):
    return None, call.func.attr
  return None, d


def _traced_functions(tree: ast.Module) -> List[ast.AST]:
  """Function bodies that are traced when a built step runs: every
  function nested inside a ``make_*step*``/``make_*eval*`` builder or a
  ``_make_*step*`` factory of shared step pieces."""
  out = []

  class V(ast.NodeVisitor):
    def _visit_fn(self, node):
      if STEP_BUILDER_RE.match(node.name):
        for sub in ast.walk(node):
          if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)) and sub is not node:
            out.append(sub)
      else:
        self.generic_visit(node)

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

  V().visit(tree)
  return out


def _is_const_expr(node: ast.AST) -> bool:
  if isinstance(node, ast.Constant):
    return True
  if isinstance(node, ast.BinOp):
    return _is_const_expr(node.left) and _is_const_expr(node.right)
  if isinstance(node, ast.UnaryOp):
    return _is_const_expr(node.operand)
  return False


def _is_durable_module(path: str) -> bool:
  """GL104/GL105 scope: library modules on the checkpoint/durable write
  path. Test files are exempt (they corrupt files and draw RNG batches
  on purpose)."""
  base = os.path.basename(path)
  return bool(DURABLE_PATH_RE.search(base)) and not base.startswith("test_")


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


@_rule("GL101", "error",
       "no host sync inside trace-reachable step-builder code")
def _check_host_sync(mod: ParsedModule) -> List[Finding]:
  out = []
  for fn in _traced_functions(mod.tree):
    for node in ast.walk(fn):
      if not isinstance(node, ast.Call):
        continue
      root, name = _call_pair(node)
      if name in HOST_SYNC_ATTRS and isinstance(node.func, ast.Attribute):
        out.append(mod.finding(
            "GL101", node,
            f".{name}() inside trace-reachable step code: a host sync "
            "here serializes the device pipeline (or breaks tracing). "
            "Sync on the host side of the step boundary instead."))
      elif name in HOST_SYNC_JAX_FUNCS and root in ("jax", None):
        out.append(mod.finding(
            "GL101", node,
            f"jax.{name}() inside trace-reachable step code — fetch "
            "values on the host after the step returns."))
  return out


@_rule("GL102", "error",
       "no numpy calls on traced values inside step-builder code")
def _check_numpy_in_trace(mod: ParsedModule) -> List[Finding]:
  out = []
  for fn in _traced_functions(mod.tree):
    for node in ast.walk(fn):
      if isinstance(node, ast.Call):
        root, name = _call_pair(node)
        if root in ("np", "numpy"):
          out.append(mod.finding(
              "GL102", node,
              f"{root}.{name}(...) inside trace-reachable step code: "
              "numpy forces concretization of traced values (silent "
              "host round-trip or a TracerError). Use jnp, or hoist the "
              "constant computation to build time."))
  return out


@_rule("GL103", "error", "no bare except")
def _check_bare_except(mod: ParsedModule) -> List[Finding]:
  out = []
  for node in ast.walk(mod.tree):
    if isinstance(node, ast.ExceptHandler) and node.type is None:
      out.append(mod.finding(
          "GL103", node,
          "bare 'except:' swallows KeyboardInterrupt/SystemExit and every "
          "injected fault — name the exception types (the resilience "
          "layer depends on faults propagating)."))
  return out


@_rule("GL104", "error",
       "durable paths must fsync before rename/replace")
def _check_unfsynced_rename(mod: ParsedModule) -> List[Finding]:
  if not _is_durable_module(mod.path):
    return []
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
      continue
    renames, fsync_lines = [], []
    for sub in ast.walk(node):
      if isinstance(sub, ast.Call):
        root, name = _call_pair(sub)
        if (root, name) in RENAME_FUNCS:
          renames.append(sub)
        elif name and "fsync" in name:
          fsync_lines.append(sub.lineno)
    for rn in renames:
      if not any(line < rn.lineno for line in fsync_lines):
        out.append(mod.finding(
            "GL104", rn,
            f"{_dotted(rn.func)}() with no fsync earlier in "
            f"'{node.name}': a rename published before the data is "
            "synced can survive a crash as a complete-looking, "
            "torn checkpoint. fsync every written file (and the tmp "
            "dir) first."))
  return out


@_rule("GL105", "error",
       "no wall clock / RNG in durable (manifest-writing) modules")
def _check_wallclock_in_durable(mod: ParsedModule) -> List[Finding]:
  if not _is_durable_module(mod.path):
    return []
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Call):
      continue
    root, name = _call_pair(node)
    dotted = _dotted(node.func) or ""
    if (root, name) in WALLCLOCK_CALLS or dotted.startswith("np.random.") \
        or dotted.startswith("numpy.random.") \
        or dotted.startswith("random."):
      out.append(mod.finding(
          "GL105", node,
          f"{dotted}() in a durable module: checkpoint contents and "
          "manifests must be deterministic functions of the train state "
          "(bit-exact resume, content-addressed verification). Derive "
          "ordering/ids from the step counter or file contents."))
  return out


@_rule("GL106", "error",
       "int32 casts of index arithmetic (vocab-scale overflow)")
def _check_int32_narrowing(mod: ParsedModule) -> List[Finding]:
  out = []

  # The arithmetic must be on the VALUE path of the cast: a `*`/`+` in an
  # opaque call's arguments (an RNG bound, a shape) is not index math
  # being narrowed. Element-wise value-propagating calls are followed.
  value_prop = frozenset({
      "minimum", "maximum", "clip", "where", "concatenate", "stack",
      "reshape", "ravel", "cumsum", "sum", "prod", "mod", "abs",
      "floor_divide", "add", "multiply", "subtract",
  })

  def is_zero_mult(node: ast.BinOp) -> bool:
    # `x * 0` — the varying-zero dependency idiom; the value is 0
    return isinstance(node.op, ast.Mult) and any(
        isinstance(s, ast.Constant) and s.value == 0
        for s in (node.left, node.right))

  def has_arith(node: ast.AST) -> bool:
    if isinstance(node, ast.BinOp):
      if isinstance(node.op, (ast.Mult, ast.Add, ast.LShift, ast.Pow)) \
          and not _is_const_expr(node) and not is_zero_mult(node):
        return True
      return has_arith(node.left) or has_arith(node.right)
    if isinstance(node, ast.Call):
      _, name = _call_pair(node)
      if name in value_prop:
        return any(has_arith(a) for a in node.args)
      return False
    if isinstance(node, (ast.Tuple, ast.List)):
      return any(has_arith(e) for e in node.elts)
    if isinstance(node, ast.UnaryOp):
      return has_arith(node.operand)
    return False

  def is_int32_ref(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value in INT32_NAMES:
      return True
    d = _dotted(node)
    return bool(d) and d.split(".")[-1] in INT32_NAMES

  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Call):
      continue
    root, name = _call_pair(node)
    target = None
    if name in INT32_NAMES and node.args:           # np.int32(expr)
      target = node.args[0]
    elif name == "astype" and isinstance(node.func, ast.Attribute) \
        and node.args and is_int32_ref(node.args[0]):
      target = node.func.value                       # expr.astype(int32)
    elif name in ("asarray", "array") and len(node.args) >= 2 \
        and is_int32_ref(node.args[1]):
      target = node.args[0]                          # asarray(expr, int32)
    elif name in ("asarray", "array") and node.args:
      for kw in node.keywords:
        if kw.arg == "dtype" and is_int32_ref(kw.value):
          target = node.args[0]
    if target is not None and has_arith(target):
      out.append(mod.finding(
          "GL106", node,
          "int32 cast of an arithmetic expression: products/sums of "
          "vocab-sized ints overflow 2^31 at the scales the planner "
          "targets. Compute in int64 (numpy's default), bound the "
          "result, then narrow the VALUE — or suppress with a comment "
          "stating the proven bound."))
  return out


@_rule("GL107", "error", "every pytest.mark must be registered")
def _check_markers(mod: ParsedModule) -> List[Finding]:
  out = []
  registered = mod.ctx.registered_markers | BUILTIN_MARKS
  for node in ast.walk(mod.tree):
    if isinstance(node, ast.Attribute):
      d = _dotted(node)
      if d and d.startswith("pytest.mark."):
        mark = d.split(".")[2]
        if mark not in registered:
          out.append(mod.finding(
              "GL107", node,
              f"pytest.mark.{mark} is not registered in pyproject.toml "
              "[tool.pytest.ini_options].markers — under "
              "--strict-markers collection fails; without it a typo'd "
              "marker silently deselects the test."))
  return out


@_rule("GL109", "error",
       "no raw all_to_all / ppermute outside the sanctioned wire module")
def _check_raw_all_to_all(mod: ParsedModule) -> List[Finding]:
  # parallel/wire.py (that exact path — not any file named wire.py) is
  # the one sanctioned home of the exchange primitives; the rule exists
  # so a new exchange cannot silently bypass the plan's wire knobs (bf16
  # /fp8 narrowing, dedup'd payloads, the chunked ppermute pipeline).
  # ppermute joined the guarded set with the pipelined wire: a raw
  # ppermute round in step code would fly f32 outside the audit's
  # (world-1) x chunks round pins exactly like a raw all_to_all. Scope:
  # trace-reachable step-builder closures ANYWHERE, plus every function
  # of library-package modules — the lookup engine's methods are where
  # the real exchanges live and are not statically
  # step-builder-reachable; tests/tools stay free to build raw audit
  # fixtures.
  norm = mod.path.replace(os.sep, "/")
  if norm.endswith("parallel/wire.py"):
    return []
  if "distributed_embeddings_tpu/" in norm:
    nodes = ast.walk(mod.tree)
  else:
    nodes = (n for fn in _traced_functions(mod.tree)
             for n in ast.walk(fn))
  out = []
  seen = set()
  for node in nodes:
    if not isinstance(node, ast.Call):
      continue
    _, name = _call_pair(node)
    if name in ("all_to_all", "ppermute") and node.lineno not in seen:
      seen.add(node.lineno)  # nested traced fns overlap in their walks
      out.append(mod.finding(
          "GL109", node,
          f"raw lax.{name} outside parallel/wire.py: exchanges "
          "must ride the wire module (wire.exchange_ids / "
          "wire.pipelined_exchange_ids for integer payloads, "
          "wire.float_all_to_all / wire.pipelined_float_exchange for "
          "activations/cotangents) so the plan's wire_dtype / "
          "dedup_exchange / overlap contract holds — a raw exchange "
          "ships f32 payloads outside the round counts the audit "
          "layer pins."))
  return out


@_rule("GL110", "error",
       "no hardcoded world constants vs process_count/index in durable code")
def _check_world_constants(mod: ParsedModule) -> List[Finding]:
  # Elastic pods resize the world between runs: a checkpoint written at
  # world N restores at world M, so durable (checkpoint/manifest) code
  # comparing jax.process_count() / jax.process_index() against a baked-in
  # integer encodes one world shape into exactly the layer that must
  # survive a resize. 0 and 1 are exempt — `process_index() == 0` (the
  # controller check) and `process_count() > 1` (the multi-controller
  # check) are world-shape-free idioms.
  if not _is_durable_module(mod.path):
    return []
  proc_calls = frozenset({"process_count", "process_index"})
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Compare):
      continue
    sides = [node.left] + list(node.comparators)
    if not any(isinstance(s, ast.Call) and _call_pair(s)[1] in proc_calls
               for s in sides):
      continue
    for s in sides:
      if isinstance(s, ast.Constant) and isinstance(s.value, int) \
          and not isinstance(s.value, bool) and s.value not in (0, 1):
        out.append(mod.finding(
            "GL110", node,
            f"jax.process_count()/process_index() compared against the "
            f"hardcoded constant {s.value}: durable code must stay "
            "world-shape-portable (a checkpoint written at world N "
            "restores at world M). Derive world facts from the plan "
            "(plan.world_size) or the manifest's 'world' section; only "
            "0/1 (controller / multi-controller idioms) are "
            "shape-free."))
        break
  return out


# Train-only surfaces a serving module may not reference by name: the
# step builders and state constructors (they build/consume optimizer
# state), the scatter-add emitters (serving never writes), and the
# guard/commit-gate helpers (nothing to gate without a commit).
_TRAIN_ONLY_NAMES = frozenset({
    "make_train_step", "make_sparse_train_step", "make_tiered_train_step",
    "init_sparse_state", "init_sparse_state_direct", "init_tiered_state",
    "apply_sparse", "apply_sparse_streams", "sparse_delta_streams",
    "scatter_add_fused", "DistributedOptimizer", "_make_guard_helpers",
    "_make_train_step_pieces", "select_tree", "check_oov",
})


def _train_surface_findings(mod: ParsedModule, rule_id: str,
                            pkg: str, where: str) -> List[Finding]:
  """Shared body of GL111/GL114: train-only surfaces referenced inside
  one inference-side package (``pkg`` is the directory name)."""
  norm = mod.path.replace(os.sep, "/")
  if f"/{pkg}/" not in norm and not norm.startswith(f"{pkg}/"):
    return []
  out = []
  for node in ast.walk(mod.tree):
    if isinstance(node, ast.Import):
      for alias in node.names:
        root = alias.name.split(".")[0]
        if root == "optax" or alias.name.endswith("resilience.guards"):
          out.append(mod.finding(
              rule_id, node,
              f"import of {alias.name!r} in a {where} module: the "
              "inference path carries no optimizer state or commit "
              "gate — strip at export instead."))
    elif isinstance(node, ast.ImportFrom):
      module = node.module or ""
      names = [a.name for a in node.names]
      if module.split(".")[0] == "optax" or module.endswith("guards") \
          or ("resilience" in module and "guards" in names):
        out.append(mod.finding(
            rule_id, node,
            f"import from {module or '.'!r} of {names} in a {where} "
            "module: optax / resilience.guards are train-only surfaces "
            "— the serve step has nothing to optimize or gate."))
      bad = sorted(set(names) & _TRAIN_ONLY_NAMES)
      if bad:
        out.append(mod.finding(
            rule_id, node,
            f"train-only name(s) {bad} imported into a {where} module: "
            "the step builders, scatter emitters, and guard helpers "
            "must stay unreachable from the inference path."))
    elif isinstance(node, (ast.Name, ast.Attribute)):
      name = node.id if isinstance(node, ast.Name) else node.attr
      if name in _TRAIN_ONLY_NAMES or name == "optax":
        out.append(mod.finding(
            rule_id, node,
            f"reference to train-only surface {name!r} in a {where} "
            "module: serve buffers have no aux lanes to update and no "
            "commit to gate — route the need through export/eval "
            "instead."))
  # nested attribute chains repeat line numbers; report each line once
  seen = set()
  uniq = []
  for f in out:
    if f.line not in seen:
      seen.add(f.line)
      uniq.append(f)
  return uniq


@_rule("GL111", "error",
       "train-only surfaces are unreachable from serving/ modules")
def _check_serving_train_surfaces(mod: ParsedModule) -> List[Finding]:
  # The serving subsystem's whole point is an inference image with the
  # optimizer lanes stripped and no write path: an optax import, a
  # guard/commit-gate helper, or a scatter-add emitter reappearing
  # there means training plumbing leaked back into the serve step (the
  # jaxpr audit pins the traced program; this rule catches the leak at
  # review time, before anything traces). faultinject/retry are NOT
  # banned — the export path legitimately rides the durable-checkpoint
  # machinery.
  return _train_surface_findings(mod, "GL111", "serving", "serving")


@_rule("GL114", "error",
       "train-only surfaces are unreachable from fleet/ modules")
def _check_fleet_train_surfaces(mod: ParsedModule) -> List[Finding]:
  # The fleet tier is the serving engine spread over processes — the
  # same inference-only contract at fleet scope: a router or owner that
  # imports optax, a step builder, a scatter-add emitter, or a guard
  # helper has train plumbing on the request path (GL111's invariant,
  # one package over). faultinject/retry stay legal — the fleet rides
  # the durable/retry machinery by design.
  return _train_surface_findings(mod, "GL114", "fleet", "fleet")


# The fleet MUTATION surface: the operations that change what the fleet
# IS — re-cut the published artifact (``fleet.reshard``), edit the
# replica set the router routes through (``apply_fleet``/``set_fleet``),
# fold or garbage-collect the delta chain (``compact_once``/
# ``gc_deltas``/``compact_chain``). Each maps to its sanctioned home
# package (the module that DEFINES it); everywhere else in the library
# the only legitimate callers are ``control/`` daemons — operator tools
# and tests live outside the library package and stay unrestricted.
_FLEET_MUTATION_NAMES = {
    "reshard": "fleet",
    "apply_fleet": "fleet",
    "set_fleet": "fleet",
    "compact_once": "streaming",
    "gc_deltas": "streaming",
    "compact_chain": "streaming",
}


@_rule("GL117", "error",
       "fleet mutation surfaces are reachable only from control/ daemons")
def _check_fleet_mutation_surfaces(mod: ParsedModule) -> List[Finding]:
  # The control plane's authority boundary: a data-path module (router
  # gather, subscriber fold, batcher flush) that can trigger a reshard,
  # a replica-set edit, or a chain compaction can wedge the fleet from
  # a request handler — exactly the accidental-operator bug class the
  # autonomous control plane exists to absorb. Mutations route through
  # control/ (decision-logged, hysteresis-guarded) or the operator
  # tools; the home packages keep their own definitions and internal
  # plumbing.
  norm = mod.path.replace(os.sep, "/")
  if "distributed_embeddings_tpu/" not in norm or "/control/" in norm:
    return []
  out = []
  for node in ast.walk(mod.tree):
    hits = []
    if isinstance(node, ast.Import):
      hits = [last for alias in node.names
              for last in [alias.name.split(".")[-1]]
              if last in _FLEET_MUTATION_NAMES]
    elif isinstance(node, ast.ImportFrom):
      hits = [a.name for a in node.names
              if a.name in _FLEET_MUTATION_NAMES]
    elif isinstance(node, (ast.Name, ast.Attribute)):
      name = node.id if isinstance(node, ast.Name) else node.attr
      if name in _FLEET_MUTATION_NAMES:
        hits = [name]
    for name in hits:
      if f"/{_FLEET_MUTATION_NAMES[name]}/" in norm:
        continue  # the surface's own home package
      out.append(mod.finding(
          "GL117", node,
          f"fleet mutation surface {name!r} referenced from a library "
          "module outside control/: resharding, replica-set edits, and "
          "compactor folds are control-plane actuations — route the "
          "need through a control/ daemon (decision-logged, "
          "hysteresis-guarded) or an operator tool."))
  seen = set()
  uniq = []
  for f in out:
    if f.line not in seen:
      seen.add(f.line)
      uniq.append(f)
  return uniq


# The dynamic-vocabulary translation surface: every entry point that
# reads or mutates the host-side id space (open-addressing table,
# admission sketch, TTL recycler). Distinctively-named on purpose —
# generic method names (insert/remove/update) stay lintable-free.
_DYNVOCAB_SURFACE = frozenset({
    "translate_batch", "translate_readonly", "translate_dynamic_ids",
    "DynVocabTranslator", "IdTranslationTable", "CountMinSketch",
    "RowRecycler", "apply_zero_work",
})


@_rule("GL112", "error",
       "dynvocab translation state mutates only in dynvocab/ host paths")
def _check_dynvocab_in_trace(mod: ParsedModule) -> List[Finding]:
  # The allocation protocol's core claim is that the id space is HOST
  # state mutated between steps (the TieredPrefetcher pattern): the
  # traced step sees only translated in-range ids, so its jaxpr is
  # byte-identical to a static-vocab plan's. A translator call inside a
  # trace-reachable step closure would either fail tracing outright
  # (numpy on tracers) or — worse — run once at trace time and silently
  # freeze the id space into the compiled step. The dynvocab package
  # itself is exempt (it IS the sanctioned home); host-side trainer /
  # test / tool code is unrestricted.
  norm = mod.path.replace(os.sep, "/")
  if "/dynvocab/" in norm or norm.startswith("dynvocab/"):
    return []
  out = []
  seen = set()
  for fn in _traced_functions(mod.tree):
    for node in ast.walk(fn):
      if isinstance(node, ast.Name):
        name = node.id
      elif isinstance(node, ast.Attribute):
        name = node.attr
      else:
        continue
      if name in _DYNVOCAB_SURFACE and node.lineno not in seen:
        seen.add(node.lineno)  # nested traced fns overlap in their walks
        out.append(mod.finding(
            "GL112", node,
            f"dynvocab translation surface {name!r} inside "
            "trace-reachable step code: the id space is host state "
            "mutated BETWEEN steps (the prefetcher pattern) — inside a "
            "traced closure it would either break tracing or freeze "
            "one translation into the compiled step. Translate on the "
            "host side of the step boundary "
            "(DistributedLookup.translate_dynamic_ids / "
            "DynVocabTrainer)."))
  return out


_RAW_TIMING_CALLS = frozenset({
    "perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns",
})


@_rule("GL113", "error",
       "no raw perf_counter/monotonic timing outside telemetry/")
def _check_raw_timing(mod: ParsedModule) -> List[Finding]:
  # Pre-telemetry, ~30 tools and several library modules each hand-rolled
  # perf_counter timing, so "where did step k's time go?" had no one
  # answer. telemetry/ is the sanctioned home of raw clock reads in the
  # LIBRARY package: a library module that wants a duration opens a
  # span (one trace, per-thread tracks) or observes a telemetry
  # histogram (one registry, bounded-error percentiles). Scope is the
  # library package only — tests and tools/ drive their own harnesses
  # (and the bench utilities consolidate on the histogram type anyway).
  # Deadline arithmetic that is not timing (the batcher's flush clock,
  # checkpoint barrier visibility polls) suppresses with the reason.
  norm = mod.path.replace(os.sep, "/")
  if "distributed_embeddings_tpu/" not in norm \
      or "/telemetry/" in norm:
    return []
  # both spellings are timing: `time.monotonic()` through any alias of
  # the module, and bare `perf_counter()` imported (possibly renamed)
  # from it — a from-import must not be a lint bypass
  time_aliases = {"time"}
  from_names: Dict[str, str] = {}  # local alias -> original clock name
  for node in ast.walk(mod.tree):
    if isinstance(node, ast.Import):
      for a in node.names:
        if a.name == "time":
          time_aliases.add(a.asname or "time")
    elif isinstance(node, ast.ImportFrom) and node.module == "time":
      for a in node.names:
        if a.name in _RAW_TIMING_CALLS:
          from_names[a.asname or a.name] = a.name
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Call):
      continue
    root, name = _call_pair(node)
    clock = None
    if root in time_aliases and name in _RAW_TIMING_CALLS:
      clock = name
    elif root is None and isinstance(node.func, ast.Name) \
        and node.func.id in from_names:
      clock = from_names[node.func.id]
    if clock is not None:
      out.append(mod.finding(
          "GL113", node,
          f"raw time.{clock}() in a library module: timing belongs to "
          "the telemetry layer — wrap the stage in telemetry.span(...) "
          "(or telemetry.timed(...) for histogram aggregation) so it "
          "lands on the shared trace and registry; suppress with the "
          "reason stated if this is deadline arithmetic, not timing."))
  return out


# GL119 guards: thread/executor CONSTRUCTION (not use) in the training
# packages that sit next to the step loop. Scope mirrors where a stray
# thread can race device dispatch, write-back, guard rollback, or a
# snapshot; serving/fleet/control run their own audited thread pools.
_GL119_PKGS = ("tiering", "dynvocab", "resilience", "streaming")
_GL119_EXECUTORS = frozenset({"ThreadPoolExecutor", "ProcessPoolExecutor"})


@_rule("GL119", "error",
       "step-adjacent training modules spawn threads only via "
       "pipeline.HostWorker")
def _check_raw_threads(mod: ParsedModule) -> List[Finding]:
  # The overlap schedulers' bit-exactness rests on ONE worker with ONE
  # join discipline: jobs sequenced in submission order, results joined
  # BEFORE accounting (so a guard rollback never races an in-flight
  # gather/translate), failures re-raised as step failures, and job time
  # on the shared trace/registry. A raw Thread or executor next to the
  # step loop re-creates exactly the hazard classes pipeline.py exists
  # to absorb — write-back tears, snapshot-over-mutation, silent
  # swallowed worker exceptions. pipeline.py is the sanctioned home;
  # long-lived service threads that predate it (the SIGTERM watchdog,
  # the async checkpoint writer, the subscriber poll loop) suppress with
  # their reason — each holds no step-loop state and joins on its own
  # shutdown path. Tools and tests stay unrestricted.
  norm = mod.path.replace(os.sep, "/")
  if "distributed_embeddings_tpu/" not in norm \
      or norm.endswith("distributed_embeddings_tpu/pipeline.py"):
    return []
  if not (any(f"/{pkg}/" in norm for pkg in _GL119_PKGS)
          or norm.endswith("distributed_embeddings_tpu/training.py")):
    return []
  # both import spellings, either surface — a rename or a from-import
  # must not be a lint bypass (the GL113 alias discipline)
  thread_aliases = {"threading"}
  cf_aliases = {"concurrent"}
  from_names: Dict[str, str] = {}  # local alias -> flagged surface
  for node in ast.walk(mod.tree):
    if isinstance(node, ast.Import):
      for a in node.names:
        if a.name == "threading":
          thread_aliases.add(a.asname or "threading")
        elif a.name in ("concurrent", "concurrent.futures"):
          cf_aliases.add(a.asname or "concurrent")
    elif isinstance(node, ast.ImportFrom):
      if node.module == "threading":
        for a in node.names:
          if a.name == "Thread":
            from_names[a.asname or a.name] = "threading.Thread"
      elif node.module == "concurrent.futures":
        for a in node.names:
          if a.name in _GL119_EXECUTORS:
            from_names[a.asname or a.name] = f"concurrent.futures.{a.name}"
      elif node.module == "concurrent":
        for a in node.names:
          if a.name == "futures":
            cf_aliases.add(a.asname or "futures")
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Call):
      continue
    root, name = _call_pair(node)
    surface = None
    if root in thread_aliases and name == "Thread":
      surface = "threading.Thread"
    elif root in cf_aliases and name in _GL119_EXECUTORS:
      surface = f"concurrent.futures.{name}"
    elif root is None and isinstance(node.func, ast.Name) \
        and node.func.id in from_names:
      surface = from_names[node.func.id]
    if surface is not None:
      out.append(mod.finding(
          "GL119", node,
          f"raw {surface}(...) in a step-adjacent training module: "
          "host/device overlap routes through pipeline.HostWorker (one "
          "worker, jobs joined before accounting, failures re-raised, "
          "spans on the shared trace) — submit a job there instead, or "
          "suppress with the reason if this is a long-lived service "
          "thread that holds no step-loop state."))
  return out


# id/epoch mints GL115 guards: uuid (any version), the secrets module,
# raw urandom, and wall-epoch reads in ns (perf_counter/monotonic are
# GL113's; time_ns is the remaining epoch-mint spelling)
_MINT_UUID = frozenset({"uuid1", "uuid3", "uuid4", "uuid5"})
_MINT_SECRETS = frozenset({"token_hex", "token_bytes", "token_urlsafe"})
_MINT_EPOCH = frozenset({"time_ns"})
_GL115_PKGS = ("serving", "fleet", "streaming")


@_rule("GL115", "error",
       "trace ids / clock epochs are minted only inside telemetry/")
def _check_raw_minting(mod: ParsedModule) -> List[Finding]:
  # The distributed-tracing contract: every id that might need to be
  # followed across a process boundary (trace ids, span ids,
  # subscriber ids) comes from telemetry.trace.mint_id/mint_context,
  # and every clock-epoch exchange rides
  # telemetry.estimate_clock_offset — so one merge pass can assemble
  # the fleet's buffers into one timeline. A raw uuid/urandom mint in
  # the request/delta-path packages creates an id namespace the trace
  # layer has never heard of; a raw time_ns epoch read there is a
  # second clock domain nothing can correlate. Scope: library modules
  # of serving/, fleet/, streaming/ only — trainers, tools, and tests
  # mint freely (nothing follows their ids across processes).
  norm = mod.path.replace(os.sep, "/")
  if "distributed_embeddings_tpu/" not in norm:
    return []
  if not any(f"/{pkg}/" in norm for pkg in _GL115_PKGS):
    return []
  # track BOTH import spellings so neither is a bypass: `from uuid
  # import uuid4 [as u4]` / `from time import time_ns`, and module
  # aliases `import uuid as u; u.uuid4()`
  from_names: Dict[str, str] = {}
  mod_alias = {"uuid": {"uuid"}, "secrets": {"secrets"},
               "os": {"os"}, "time": {"time"}}
  for node in ast.walk(mod.tree):
    if isinstance(node, ast.Import):
      for a in node.names:
        if a.name in mod_alias:
          mod_alias[a.name].add(a.asname or a.name)
    elif isinstance(node, ast.ImportFrom):
      if node.module == "uuid":
        for a in node.names:
          if a.name in _MINT_UUID:
            from_names[a.asname or a.name] = f"uuid.{a.name}"
      elif node.module == "secrets":
        for a in node.names:
          if a.name in _MINT_SECRETS:
            from_names[a.asname or a.name] = f"secrets.{a.name}"
      elif node.module == "time":
        for a in node.names:
          if a.name in _MINT_EPOCH:
            from_names[a.asname or a.name] = f"time.{a.name}"
      elif node.module == "os":
        for a in node.names:
          if a.name == "urandom":
            from_names[a.asname or a.name] = "os.urandom"
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Call):
      continue
    root, name = _call_pair(node)
    minted = None
    if root in mod_alias["uuid"] and name in _MINT_UUID:
      minted = f"uuid.{name}"
    elif root in mod_alias["secrets"] and name in _MINT_SECRETS:
      minted = f"secrets.{name}"
    elif root in mod_alias["os"] and name == "urandom":
      minted = "os.urandom"
    elif root in mod_alias["time"] and name in _MINT_EPOCH:
      minted = f"time.{name}"
    elif root is None and isinstance(node.func, ast.Name) \
        and node.func.id in from_names:
      minted = from_names[node.func.id]
    if minted is not None:
      out.append(mod.finding(
          "GL115", node,
          f"raw {minted}() in a request/delta-path module: trace ids "
          "and clock epochs are minted only inside telemetry/ — use "
          "telemetry.trace.mint_id()/mint_context() for ids and "
          "telemetry.estimate_clock_offset(...) for clock handshakes, "
          "so ids land on one trace and clock domains stay "
          "correlated."))
  return out


# GL116 guards: handler installation and real signal delivery (os.kill
# with a live signal is a kill OR the pid-liveness probe — both are
# membership/preemption machinery; signal.getsignal is a read and fine)
_GL116_OS_KILLS = frozenset({"kill", "killpg"})


@_rule("GL116", "error",
       "process signaling (signal.signal / os.kill) only in resilience/")
def _check_raw_signaling(mod: ParsedModule) -> List[Finding]:
  # Preemption handling is a resilience contract: the SIGTERM graceful
  # drain installs the ONE handler (ResilientTrainer.install_sigterm_
  # drain), the chaos harness's kill_at rule delivers the ONE in-library
  # SIGKILL (faultinject), and pod-membership liveness probes
  # (elastic.alive_members) own os.kill(pid, 0). A second
  # signal.signal(SIGTERM, ...) in any other library module silently
  # REPLACES the drain disposition — the notice arrives, nothing
  # snapshots, and the follow-up SIGKILL lands on an undrained step.
  # Scope: the library package outside resilience/; tools and tests
  # drive their own processes (the chaos drivers kill real workers).
  norm = mod.path.replace(os.sep, "/")
  if "distributed_embeddings_tpu/" not in norm or "/resilience/" in norm:
    return []
  # both import spellings, so neither is a lint bypass: module aliases
  # (`import signal as sg; sg.signal(...)`) and from-imports
  # (`from os import kill [as k]`)
  mod_alias = {"signal": {"signal"}, "os": {"os"}}
  from_names: Dict[str, str] = {}
  for node in ast.walk(mod.tree):
    if isinstance(node, ast.Import):
      for a in node.names:
        if a.name in mod_alias:
          mod_alias[a.name].add(a.asname or a.name)
    elif isinstance(node, ast.ImportFrom):
      if node.module == "signal":
        for a in node.names:
          if a.name == "signal":
            from_names[a.asname or a.name] = "signal.signal"
      elif node.module == "os":
        for a in node.names:
          if a.name in _GL116_OS_KILLS:
            from_names[a.asname or a.name] = f"os.{a.name}"
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Call):
      continue
    root, name = _call_pair(node)
    hit = None
    if root in mod_alias["signal"] and name == "signal":
      hit = "signal.signal"
    elif root in mod_alias["os"] and name in _GL116_OS_KILLS:
      hit = f"os.{name}"
    elif root is None and isinstance(node.func, ast.Name) \
        and node.func.id in from_names:
      hit = from_names[node.func.id]
    if hit is not None:
      out.append(mod.finding(
          "GL116", node,
          f"raw {hit}() in a library module: process signal "
          "dispositions and kills belong to resilience/ — install the "
          "SIGTERM drain via ResilientTrainer.install_sigterm_drain, "
          "probe liveness via resilience.elastic.alive_members, and "
          "leave chaos kills to faultinject.kill_at; suppress with the "
          "reason stated if this genuinely is not preemption "
          "handling."))
  return out


@_rule("GL108", "error", "fault-injection sites must be registered")
def _check_fault_sites(mod: ParsedModule) -> List[Finding]:
  # the registry module itself defines the sites
  if os.path.basename(mod.path) == "faultinject.py":
    return []
  sites = mod.ctx.fault_sites
  out = []
  for node in ast.walk(mod.tree):
    if not isinstance(node, ast.Call):
      continue
    _, name = _call_pair(node)
    if name == "fire" or name in FAULT_RULE_METHODS:
      if not node.args or not isinstance(node.args[0], ast.Constant) \
          or not isinstance(node.args[0].value, str):
        continue
      site = node.args[0].value
      if sites is None:
        out.append(mod.finding(
            "GL108", node,
            "faultinject.SITES registry not found — cannot validate "
            f"site {site!r} (was the registry removed?)."))
      elif site not in sites:
        out.append(mod.finding(
            "GL108", node,
            f"unknown fault-injection site {site!r}: not in "
            f"faultinject.SITES {sorted(sites)}. A typo'd site never "
            "fires, so the test silently stops testing the fault."))
  return out


# The multi-controller refusal inventory: every `jax.process_count() > 1`
# branch in the LIBRARY package that raises NotImplementedError must match
# one `(path_suffix, reason_snippet)` entry here. The inventory is checked
# BOTH ways: a refusal branch matching no entry fails GL118 at its line
# (adding a refusal silently is impossible), and an entry whose file is in
# the linted set but whose snippet matches no branch there fails GL118 as
# a stale-inventory finding (closing a refusal forces this list to shrink
# with it — the doc's refusal matrix and the code cannot drift). Remaining
# by design after the multi-controller pod work (round 21):
# - export/delta publication are single-controller by contract (the chain
#   fingerprint protocol has exactly one writer);
# - async snapshots need every process's main thread in the save barriers.
REFUSAL_INVENTORY = (
    ("serving/export.py", "export is a single-controller operation"),
    ("resilience/trainer.py", "snapshot(async_=True) under multi-controller"),
    ("streaming/publish.py", "delta publication is a single-controller"),
)


def _const_str(node: ast.AST) -> Optional[str]:
  """The literal text of a string expression: a Constant, an f-string's
  constant parts, or a `+`/implicit concatenation of those. None when
  any part is non-literal beyond f-string interpolations."""
  if isinstance(node, ast.Constant) and isinstance(node.value, str):
    return node.value
  if isinstance(node, ast.JoinedStr):
    return "".join(v.value for v in node.values
                   if isinstance(v, ast.Constant) and isinstance(v.value, str))
  if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
    left, right = _const_str(node.left), _const_str(node.right)
    if left is not None and right is not None:
      return left + right
  return None


def multicontroller_refusals(tree: ast.Module):
  """``(if_node, reason_or_None)`` for every multi-controller refusal:
  an ``if`` comparing ``process_count()`` against 1 (``> 1`` / ``1 <``)
  whose body raises ``NotImplementedError``. The reason is the raise's
  literal message (None when the message is not extractable)."""
  out = []
  for node in ast.walk(tree):
    if not isinstance(node, ast.If) or not isinstance(node.test, ast.Compare):
      continue
    sides = [node.test.left] + list(node.test.comparators)
    if not any(isinstance(s, ast.Call)
               and _call_pair(s)[1] == "process_count" for s in sides):
      continue
    if not any(isinstance(s, ast.Constant) and s.value == 1 for s in sides):
      continue
    for stmt in node.body:
      if isinstance(stmt, ast.Raise) and stmt.exc is not None:
        exc = stmt.exc
        name = _dotted(exc.func) if isinstance(exc, ast.Call) else _dotted(exc)
        if name and name.split(".")[-1] == "NotImplementedError":
          reason = None
          if isinstance(exc, ast.Call) and exc.args:
            reason = _const_str(exc.args[0])
          out.append((node, reason))
  return out


@_rule("GL118", "error",
       "multi-controller refusals must name a reason and be inventoried")
def _check_refusal_inventory(mod: ParsedModule) -> List[Finding]:
  # The multi-controller pod work (round 21) closed the elastic-resize,
  # prefetcher-write-back, and barrier-validation refusals; the ones that
  # REMAIN are design decisions, and this rule pins them as such: every
  # `process_count() > 1 -> raise NotImplementedError` branch in the
  # library package must carry an extractable literal reason and match
  # the REFUSAL_INVENTORY. A new refusal added without inventorying it
  # (the easy way out of a hard multi-controller path) fails review
  # here; lint_paths' staleness pass fails the OTHER direction.
  norm = mod.path.replace(os.sep, "/")
  if "distributed_embeddings_tpu/" not in norm:
    return []
  out = []
  for node, reason in multicontroller_refusals(mod.tree):
    if not reason:
      out.append(mod.finding(
          "GL118", node,
          "multi-controller refusal branch raises NotImplementedError "
          "without an extractable literal reason string: the refusal "
          "matrix (ARCHITECTURE §24) is built from these messages — "
          "name what is refused and why in a string literal."))
      continue
    if not any(norm.endswith(sfx) and snippet in reason
               for sfx, snippet in REFUSAL_INVENTORY):
      out.append(mod.finding(
          "GL118", node,
          f"multi-controller refusal {reason[:80]!r}... is not in "
          "analysis.astlint.REFUSAL_INVENTORY: refusing under "
          "process_count() > 1 is a design decision that must be "
          "inventoried (add a (path_suffix, reason_snippet) entry and "
          "the ARCHITECTURE §24 matrix row) — or implement the "
          "multi-controller path."))
  return out


# The sanctioned Pallas gates, BOTH directions checked by GL126. Each env
# knob that can route a step onto a hand-written TPU kernel flows through
# exactly one predicate in one file: the predicate is what tests force
# (and what the CPU tier proves stays False when the env is set), so a
# gate read outside its predicate's home file — or a second read of the
# same knob — would let the kernel engage on a path tier-1 never guards.
# An env read matching no entry fails at its line; an entry whose file is
# linted but no longer reads the env, or no longer defines the predicate,
# fails as a stale-registry finding at the file.
PALLAS_GATE_REGISTRY = (
    ("ops/packed_table.py", "DE_TPU_PALLAS_APPLY", "_use_pallas_apply"),
    ("ops/pallas_interact.py", "DE_TPU_PALLAS_INTERACT",
     "use_pallas_interact"),
    ("parallel/lookup_engine.py", "DE_TPU_PALLAS_DELTA", "_use_pallas_delta"),
    ("ops/pallas_exchange.py", "DE_TPU_PALLAS_EXCHANGE",
     "_use_pallas_exchange"),
)

PALLAS_ENV_PREFIX = "DE_TPU_PALLAS_"
PALLAS_KERNEL_CALLS = ("pallas_call", "make_async_remote_copy")
_PALLAS_HOME_RE = re.compile(r"ops/pallas_[^/]*\.py$")


def _pallas_env_reads(tree: ast.Module) -> List[Tuple[ast.AST, str]]:
  """``(node, env_name)`` for every ``DE_TPU_PALLAS_*`` env access:
  ``environ.get(...)`` / ``os.getenv(...)`` calls and ``environ[...]``
  subscripts. Docstrings/comments mentioning a gate never match — only
  actual access expressions do."""
  out = []
  for node in ast.walk(tree):
    if isinstance(node, ast.Call):
      _, name = _call_pair(node)
      if name in ("get", "getenv") and node.args:
        a0 = node.args[0]
        if isinstance(a0, ast.Constant) and isinstance(a0.value, str) \
            and a0.value.startswith(PALLAS_ENV_PREFIX):
          out.append((node, a0.value))
    elif isinstance(node, ast.Subscript):
      sl = node.slice
      if isinstance(sl, ast.Constant) and isinstance(sl.value, str) \
          and sl.value.startswith(PALLAS_ENV_PREFIX):
        d = _dotted(node.value)
        if d and d.split(".")[-1] == "environ":
          out.append((node, sl.value))
  return out


@_rule("GL126", "error",
       "Pallas kernel calls and env gates are registered and homed")
def _check_pallas_gates(mod: ParsedModule) -> List[Finding]:
  # Two invariants, scoped to the library package (tests/tools stay free
  # to force gates and build kernel fixtures):
  # 1. `pl.pallas_call` / `pltpu.make_async_remote_copy` appear only in
  #    `ops/pallas_*.py` — the kernel modules with interpret-mode twins
  #    and TPU smoke coverage. A kernel call elsewhere has neither.
  # 2. Every `DE_TPU_PALLAS_*` env read matches a PALLAS_GATE_REGISTRY
  #    entry for this file, and each entry for this file still holds
  #    (env read present, predicate defined) — the stale direction, so
  #    renaming or removing a gate forces the registry (and the
  #    ARCHITECTURE gate table) to move with it.
  norm = mod.path.replace(os.sep, "/")
  if "distributed_embeddings_tpu/" not in norm:
    return []
  out = []
  in_kernel_home = bool(_PALLAS_HOME_RE.search(norm))
  if not in_kernel_home:
    for node in ast.walk(mod.tree):
      if isinstance(node, ast.Call):
        _, name = _call_pair(node)
        if name in PALLAS_KERNEL_CALLS:
          out.append(mod.finding(
              "GL126", node,
              f"{name} outside ops/pallas_*.py: hand-written kernel "
              "entry points live in the kernel modules (with their "
              "interpret-mode twins and TPU smoke coverage) and are "
              "reached through a registered _use_pallas_* gate — a "
              "kernel call here has neither a sim twin nor a gate "
              "tier-1 can prove off."))
  entries = [e for e in PALLAS_GATE_REGISTRY if norm.endswith(e[0])]
  reads = _pallas_env_reads(mod.tree)
  for node, env in reads:
    if not any(env == e[1] for e in entries):
      out.append(mod.finding(
          "GL126", node,
          f"unregistered Pallas gate {env!r}: every DE_TPU_PALLAS_* "
          "env knob must have a (file, env, predicate) entry in "
          "analysis.astlint.PALLAS_GATE_REGISTRY homing it to ONE "
          "_use_pallas_* predicate in ONE file — a second read of a "
          "gate (or a gate without a predicate) can engage a kernel "
          "on a path tier-1 never guards."))
  if entries:
    defined = {n.name for n in ast.walk(mod.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    read_envs = {env for _, env in reads}
    for sfx, env, pred in entries:
      if env not in read_envs:
        out.append(Finding(
            "GL126", "error", mod.path, 0,
            f"stale PALLAS_GATE_REGISTRY entry ({sfx!r}, {env!r}): "
            "this file no longer reads the env gate — the gate moved "
            "or was removed, so prune/update the registry entry (and "
            "the ARCHITECTURE gate table) to match."))
      if pred not in defined:
        out.append(Finding(
            "GL126", "error", mod.path, 0,
            f"stale PALLAS_GATE_REGISTRY entry ({sfx!r}, {pred!r}): "
            "this file does not define the registered predicate — "
            "the gate's decision point moved, so update the registry "
            "entry to the predicate that actually guards the kernel."))
  return out


# ---------------------------------------------------------------------------
# repo-context parsing (no imports of the target package)
# ---------------------------------------------------------------------------


def _parse_markers(root: str) -> frozenset:
  """Marker names from pyproject [tool.pytest.ini_options].markers."""
  pyproject = os.path.join(root, "pyproject.toml")
  if not os.path.exists(pyproject):
    return frozenset()
  with open(pyproject) as f:
    text = f.read()
  try:
    import tomllib
    data = tomllib.loads(text)
    markers = (data.get("tool", {}).get("pytest", {})
               .get("ini_options", {}).get("markers", []))
  except ModuleNotFoundError:  # py3.10: no tomllib; scrape the list
    m = re.search(r"markers\s*=\s*\[(.*?)\]", text, re.S)
    markers = re.findall(r"[\"']([^\"':]+):?[^\"']*[\"']",
                         m.group(1)) if m else []
  return frozenset(m.split(":")[0].strip() for m in markers)


_REGISTER_SITE_RE = re.compile(
    r"register_site\(\s*[\"']([A-Za-z0-9_]+)[\"']")


def _parse_fault_sites(root: str) -> Optional[frozenset]:
  """The known fault-site set: the ``SITES`` literal from
  resilience/faultinject.py (by AST) plus every string-literal
  ``register_site`` call in the library package and tools/ (the
  sanctioned extension mechanism — a registered site is known by
  definition, so rules installed on it must lint clean)."""
  path = os.path.join(root, "distributed_embeddings_tpu", "resilience",
                      "faultinject.py")
  if not os.path.exists(path):
    return None
  with open(path) as f:
    tree = ast.parse(f.read())
  sites = None
  for node in ast.walk(tree):
    if isinstance(node, ast.Assign) and any(
        isinstance(t, ast.Name) and t.id == "SITES" for t in node.targets):
      consts = [s.value for s in ast.walk(node.value)
                if isinstance(s, ast.Constant) and isinstance(s.value, str)]
      if consts:
        sites = set(consts)
  if sites is None:
    return None
  for base in ("distributed_embeddings_tpu", "tools"):
    top = os.path.join(root, base)
    if not os.path.isdir(top):
      continue
    for dirpath, dirnames, filenames in os.walk(top):
      dirnames[:] = [d for d in dirnames if d != "__pycache__"]
      for fn in sorted(filenames):
        if fn.endswith(".py"):
          with open(os.path.join(dirpath, fn)) as f:
            sites.update(_REGISTER_SITE_RE.findall(f.read()))
  return frozenset(sites)


# ---------------------------------------------------------------------------
# GL124: stale-suppression detection
# ---------------------------------------------------------------------------


def _suppression_comments(source: str) -> List[Tuple[int, List[str]]]:
  """``(line, [rule ids])`` for every REAL ``# graftlint: disable``
  comment. Scans tokenize COMMENT tokens, not raw lines: disable text
  inside string literals (this repo's own lint-test fixtures) is not a
  live suppression and must not be judged as one."""
  out = []
  try:
    for tok in tokenize.generate_tokens(io.StringIO(source).readline):
      if tok.type == tokenize.COMMENT:
        m = SUPPRESS_RE.search(tok.string)
        if m:
          ids = [s.strip() for s in m.group(1).split(",") if s.strip()]
          out.append((tok.start[0], ids))
  except (tokenize.TokenError, IndentationError):
    pass
  return out


@_rule("GL124", "error",
       "suppression comments must suppress something (no stale or "
       "unknown-id disables)")
def _check_stale_suppression(mod: ParsedModule) -> List[Finding]:
  # Registered for the catalog and --list-rules; the real judgment is
  # aggregate over the run's raw findings (a rule check cannot see the
  # other rules' findings), so it lives in lint_source below.
  return []


def _stale_suppressions(mod: ParsedModule, raw: List[Finding],
                        run_ids: Set[str]) -> List[Finding]:
  """GL124 findings: disable comments whose ids fire nothing on their
  line. Only ids whose rule actually RAN are judged (a partial-rules
  lint must not call the others' suppressions stale), and threadlint's
  ids (:data:`EXTERNAL_RULE_IDS`) are left to that pass."""
  fired: Dict[int, Set[str]] = {}
  for f in raw:
    fired.setdefault(f.line, set()).add(f.rule)
  out = []
  for line, ids in _suppression_comments(mod.source):
    for rid in ids:
      if rid in ("all", "GL124") or rid in EXTERNAL_RULE_IDS:
        continue
      if rid not in RULES:
        out.append(Finding(
            "GL124", "error", mod.path, line,
            f"unknown rule id {rid!r} in graftlint suppression — a "
            "typo'd id suppresses nothing while looking reviewed; fix "
            "the id (known: GL101..GL125) or delete the comment."))
        continue
      if rid not in run_ids:
        continue
      if rid not in fired.get(line, set()):
        out.append(Finding(
            "GL124", "error", mod.path, line,
            f"suppression for {rid} suppresses nothing: no {rid} "
            "finding fires on this line — the violation moved or was "
            "fixed; delete the stale comment so the swept baseline "
            "cannot rot."))
  return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def lint_source(source: str, path: str,
                ctx: Optional[LintContext] = None,
                rules: Optional[Iterable[str]] = None) -> List[Finding]:
  """Lint one source string; returns unsuppressed findings."""
  mod = ParsedModule(path, source, ast.parse(source), ctx or LintContext())
  run_ids = set(rules) if rules is not None else set(RULES)
  raw = []
  for rule_id in sorted(rules or RULES):
    raw.extend(RULES[rule_id].check(mod))
  if "GL124" in run_ids:
    raw.extend(_stale_suppressions(mod, raw, run_ids))
  out = [f for f in raw if not mod.suppressed(f)]
  return sorted(out, key=lambda f: (f.path, f.line, f.rule))


def _iter_py_files(paths: Sequence[str]):
  for p in paths:
    if os.path.isfile(p):
      if p.endswith(".py"):
        yield p
    else:
      for dirpath, dirnames, filenames in os.walk(p):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git", "dist")]
        for fn in sorted(filenames):
          if fn.endswith(".py"):
            yield os.path.join(dirpath, fn)


def lint_paths(paths: Sequence[str],
               root: Optional[str] = None,
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
  """Lint files/directories; ``root`` anchors the repo-context parse
  (pyproject markers, fault-site registry). Defaults to the common
  parent of ``paths``."""
  if root is None:
    root = os.path.commonpath([os.path.abspath(p) for p in paths]) \
        if paths else os.getcwd()
    while root != os.path.dirname(root) and not os.path.exists(
        os.path.join(root, "pyproject.toml")):
      root = os.path.dirname(root)
  ctx = LintContext.for_repo(root)
  out = []
  # GL118 staleness (the aggregate direction): inventory entries whose
  # file IS in the linted set but whose snippet matched no refusal there
  # are stale — the refusal was closed without pruning the inventory.
  # Tracked per inventory entry so partial-tree lints (a single file
  # from another package) never false-positive.
  inv_file_seen = [False] * len(REFUSAL_INVENTORY)
  inv_matched = [False] * len(REFUSAL_INVENTORY)
  inv_lines: Dict[int, str] = {}
  want_gl118 = rules is None or "GL118" in set(rules)
  for path in _iter_py_files(paths):
    with open(path) as f:
      source = f.read()
    try:
      out.extend(lint_source(source, path, ctx, rules))
    except SyntaxError as e:
      out.append(Finding("GL000", "error", path, e.lineno or 0,
                         f"syntax error: {e.msg}"))
      continue
    if not want_gl118:
      continue
    norm = path.replace(os.sep, "/")
    hits = [i for i, (sfx, _) in enumerate(REFUSAL_INVENTORY)
            if norm.endswith(sfx)]
    if not hits:
      continue
    refusals = multicontroller_refusals(ast.parse(source))
    for i in hits:
      inv_file_seen[i] = True
      inv_lines[i] = path
      if any(reason and REFUSAL_INVENTORY[i][1] in reason
             for _, reason in refusals):
        inv_matched[i] = True
  if want_gl118:
    for i, (sfx, snippet) in enumerate(REFUSAL_INVENTORY):
      if inv_file_seen[i] and not inv_matched[i]:
        out.append(Finding(
            "GL118", "error", inv_lines[i], 0,
            f"stale REFUSAL_INVENTORY entry ({sfx!r}, {snippet!r}): no "
            "multi-controller refusal in this file matches the snippet "
            "— the refusal was closed (congratulations), so prune the "
            "inventory entry and update the ARCHITECTURE §24 refusal "
            "matrix."))
  return out
