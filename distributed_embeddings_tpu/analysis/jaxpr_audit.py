"""Trace-time structural audit of the real step builders.

``jax.make_jaxpr`` traces the ACTUAL artifacts — the fused sparse train
step (guarded and not), the tiered train step, and the fused eval step —
on a small virtual-CPU-mesh fixture, and this module asserts the
invariants the whole performance/correctness story rests on, directly on
the traced program:

- **Exactly one scatter-add per fused table class** in the backward
  (attributed by operand shape: each sparse class's local packed buffer
  shape must receive exactly one ``scatter-add``). A second scatter on a
  class buffer defeats XLA's input/output aliasing and copies the
  multi-GiB buffer every step (ARCHITECTURE.md §3.2); zero scatters
  means the class silently stopped training. The eval step must contain
  NONE (a forward that writes is a bug).
- **Collective hygiene**: every collective's axis names ⊆ the mesh's
  axis names, and the guard's ``pmin`` (the collective bad-step verdict)
  is present exactly once iff ``guard=True`` — a guarded step without
  the pmin can fork replicated state across devices on a poison batch.
- **Wire contract**: per artifact, the ``all_to_all`` COUNT is pinned
  (3 per padded bucket in a train step — ids, activations, reverse
  cotangents; 2 in eval) and every FLOAT payload's element dtype must
  match the plan's ``wire_dtype`` (f32 identity wire, or bf16/fp8
  narrowed in flight by ``parallel.wire``). A stray f32 exchange under
  a narrowed plan multiplies wire bytes silently; an extra exchange is
  traffic the exchange budget does not account for. Plans with
  ``overlap='pipelined'`` additionally pin the ``ppermute`` ROUND count
  — exactly ``(world - 1) * exchange_chunks`` rounds per exchange, zero
  ``all_to_all``s — and the float dtype check covers the ppermute
  payloads (the fp8 wire's blocks must actually fly as float8_e4m3).
  Plans with ``overlap='fused'`` keep the same round pin AND pin the
  total ``gather`` op count: the just-in-time schedule gathers each
  round's rows inside the round body instead of a monolithic pre-pass,
  so the count is strictly higher than the pipelined trace of the same
  fixture — a drift back down means the pre-gather was re-hoisted.
- **No f64 leaks**: no equation produces a float64 value (CPU tracing
  would hide what TPU lowering rejects; an f64 constant also doubles a
  buffer).
- **No host callbacks / infeed in the hot path**: ``pure_callback``,
  ``io_callback``, ``debug_callback`` etc. serialize the device pipeline
  per step.
- **Jaxpr fingerprints**: per-artifact op-class counts persisted in
  ``tests/data/jaxpr_fingerprints.json``. Any structural drift — an
  extra collective, a vanished scatter, a new transfer — diffs loudly in
  lint; intentional changes regenerate via
  ``tools/graftlint.py --update-fingerprints``.

The fixture is deliberately tiny (3 tables, width 16, world 4) so the
audit traces in seconds; the invariants checked are scale-free.
"""

from __future__ import annotations

import json
import os
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

FINGERPRINT_PATH = os.path.join("tests", "data", "jaxpr_fingerprints.json")

CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "python_callback",
    "callback", "outside_call", "host_callback", "infeed", "outfeed",
})


def _jaxpr_types():
  try:
    from jax.core import ClosedJaxpr, Jaxpr
  except ImportError:  # newer jax: moved to jax.extend
    from jax.extend.core import ClosedJaxpr, Jaxpr
  return ClosedJaxpr, Jaxpr


def _subjaxprs(v) -> List[Any]:
  ClosedJaxpr, Jaxpr = _jaxpr_types()
  if isinstance(v, ClosedJaxpr):
    return [v.jaxpr]
  if isinstance(v, Jaxpr):
    return [v]
  if isinstance(v, (list, tuple)):
    out = []
    for x in v:
      out.extend(_subjaxprs(x))
    return out
  return []


def walk_eqns(jaxpr, _seen=None):
  """Yield every equation across nested jaxprs, visiting each distinct
  inner jaxpr once (pjit/custom_jvp params can alias the same jaxpr
  under several keys — naive walks double-count)."""
  if _seen is None:
    _seen = set()
  if id(jaxpr) in _seen:
    return
  _seen.add(id(jaxpr))
  for eqn in jaxpr.eqns:
    yield eqn
    for v in eqn.params.values():
      for sub in _subjaxprs(v):
        yield from walk_eqns(sub, _seen)


@dataclass
class JaxprSummary:
  """Everything the invariant checks need, extracted in one walk."""
  counts: Counter = field(default_factory=Counter)
  scatter_shapes: List[Tuple[int, ...]] = field(default_factory=list)
  collective_axes: List[Tuple[str, Tuple[str, ...]]] = field(
      default_factory=list)
  f64_prims: List[str] = field(default_factory=list)
  callback_prims: List[str] = field(default_factory=list)
  # element dtype of every all_to_all payload (first operand), in walk
  # order — the wire-contract evidence
  a2a_dtypes: List[str] = field(default_factory=list)
  # same for ppermute payloads (the pipelined wire's rounds)
  ppermute_dtypes: List[str] = field(default_factory=list)
  # (in, out) element dtypes of every convert_element_type — the serve
  # artifacts pin the int8 -> float32 dequant on this evidence
  convert_pairs: List[Tuple[str, str]] = field(default_factory=list)


# `psum_invariant`: what jax 0.9.0 traces a `psum` (and so a `pmean`) as in a
# `shard_map` body; `all_gather_invariant` is the gather of that family
_COLLECTIVES = frozenset({
    "psum", "psum2", "psum_invariant", "pmin", "pmax", "pmean", "all_to_all",
    "all_gather", "all_gather_invariant", "ppermute", "pbroadcast",
    "reduce_scatter", "axis_index",
})


def summarize(jaxpr) -> JaxprSummary:
  s = JaxprSummary()
  for eqn in walk_eqns(jaxpr):
    name = eqn.primitive.name
    s.counts[name] += 1
    if name.startswith("scatter"):
      s.scatter_shapes.append(tuple(eqn.invars[0].aval.shape))
    if name == "all_to_all":
      s.a2a_dtypes.append(str(eqn.invars[0].aval.dtype))
    if name == "ppermute":
      s.ppermute_dtypes.append(str(eqn.invars[0].aval.dtype))
    if name == "convert_element_type" and eqn.invars and eqn.outvars:
      s.convert_pairs.append((str(eqn.invars[0].aval.dtype),
                              str(eqn.outvars[0].aval.dtype)))
    if name in _COLLECTIVES:
      axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
      if not isinstance(axes, (tuple, list)):
        axes = (axes,)
      s.collective_axes.append(
          (name, tuple(str(a) for a in axes)))
    if name in CALLBACK_PRIMS or "callback" in name:
      s.callback_prims.append(name)
    for v in list(eqn.invars) + list(eqn.outvars):
      aval = getattr(v, "aval", None)
      dtype = getattr(aval, "dtype", None)
      if dtype is not None and str(dtype) == "float64":
        s.f64_prims.append(name)
  return s


def fingerprint(summary: JaxprSummary) -> Dict[str, int]:
  """Stable op-class counts (the persisted regression signature)."""
  return {k: int(v) for k, v in sorted(summary.counts.items())}


@dataclass
class Expectation:
  """Structural invariants one artifact's jaxpr must satisfy."""
  # sparse class name -> local packed buffer shape; each must receive
  # exactly `scatters_per_class` scatter-adds (0 for eval)
  class_shapes: Dict[str, Tuple[int, ...]]
  mesh_axes: Tuple[str, ...]
  guard: bool = False
  scatters_per_class: int = 1
  # exact all_to_all count (None: not checked). Train steps exchange 3x
  # per padded bucket (ids dp->mp, activations mp->dp, reverse
  # cotangents), eval 2x; ragged buckets add one (separate lengths wire).
  a2a_count: Optional[int] = None
  # required element dtype of every FLOAT all_to_all AND ppermute
  # payload (None: not checked) — the plan's wire_dtype contract
  # ('float32' | 'bfloat16' | 'float8_e4m3fn')
  wire_float_dtype: Optional[str] = None
  # exact ppermute round count (None: not checked). Pipelined plans fly
  # (world - 1) * exchange_chunks rounds per exchange, so a train step
  # carries 3 * buckets * (world - 1) * chunks of them and ZERO
  # all_to_alls; a drifting count means a chunk (or a whole exchange)
  # silently fell out of — or was added to — the schedule.
  ppermute_count: Optional[int] = None
  # exact TOTAL gather count (None: not checked). The fused-exchange
  # artifacts pin this: overlap='fused' replaces each bucket's single
  # monolithic pre-gather with one gather per (round, chunk) issued
  # just-in-time before that round's send, so the count RISES vs the
  # pipelined trace of the same fixture. A regression back to a
  # monolithic pre-pass collapses the count and fails here.
  gather_count: Optional[int] = None
  # exact TOTAL scatter count, any variant, any operand shape (None:
  # not checked). The serve artifacts pin 0: a forward-only inference
  # step that scatters anywhere is reverse-mode (or a write) leaking in.
  scatter_total: Optional[int] = None
  # exact all_gather / reduce_scatter counts (None: not checked). A
  # dense-kind class whose TABLES travel (the class block is fewer bytes
  # than the rows it would ship: ``wire.dense_class_side``) contributes
  # one all_gather forward and, in a train step, one reduce_scatter
  # backward, and NO all_to_all; a class whose rows travel contributes
  # neither. Nothing else in a step gathers or reduce-scatters.
  all_gather_count: Optional[int] = None
  reduce_scatter_count: Optional[int] = None
  # a (in_dtype, out_dtype) convert that must appear at least once —
  # the int8 serve artifact pins ('int8', 'float32'), the evidence that
  # the dequant actually widens gathered bytes on device (an f32 image
  # masquerading as int8 would gather f32 and convert nothing)
  require_convert: Optional[Tuple[str, str]] = None


def audit_summary(name: str, s: JaxprSummary, expect: Expectation
                  ) -> List[str]:
  """Check one artifact's summary; returns human-readable violations."""
  out = []
  for cname, shape in sorted(expect.class_shapes.items()):
    n = sum(1 for sh in s.scatter_shapes if sh == tuple(shape))
    if n != expect.scatters_per_class:
      out.append(
          f"{name}: class {cname} (local buffer {tuple(shape)}) receives "
          f"{n} scatter-adds, expected {expect.scatters_per_class} — "
          + ("a scatter chain copies the buffer every step"
             if n > expect.scatters_per_class else
             "the class is not being updated (or eval writes)"))
  for prim, axes in s.collective_axes:
    bad = [a for a in axes if a not in expect.mesh_axes]
    if bad:
      out.append(
          f"{name}: collective {prim} over unknown axis names {bad} "
          f"(mesh axes: {list(expect.mesh_axes)})")
  pmin = s.counts.get("pmin", 0)
  if expect.guard and pmin != 1:
    out.append(
        f"{name}: guard=True but {pmin} pmin collectives (expected "
        "exactly 1) — without the AND-reduced verdict a poison batch "
        "can commit on some devices and skip on others, forking the "
        "replicated state")
  if not expect.guard and pmin:
    out.append(
        f"{name}: guard=False but found {pmin} pmin collective(s) — an "
        "unguarded step has no business reducing a verdict")
  n_a2a = s.counts.get("all_to_all", 0)
  if expect.a2a_count is not None and n_a2a != expect.a2a_count:
    out.append(
        f"{name}: {n_a2a} all_to_all exchange(s), expected "
        f"{expect.a2a_count} — an extra exchange is wire traffic the "
        "exchange budget does not account for; a missing one means a "
        "payload stopped crossing the mesh")
  n_pp = s.counts.get("ppermute", 0)
  if expect.ppermute_count is not None and n_pp != expect.ppermute_count:
    out.append(
        f"{name}: {n_pp} ppermute round(s), expected "
        f"{expect.ppermute_count} (= exchanges x (world-1) x chunks) — "
        "the pipelined schedule drifted: a missing round strands a "
        "chunk's blocks on their source ranks, an extra one is wire "
        "traffic the budget does not account for")
  for prim, want in (("all_gather", expect.all_gather_count),
                     ("reduce_scatter", expect.reduce_scatter_count)):
    got = s.counts.get(prim, 0)
    if want is not None and got != want:
      out.append(
          f"{name}: {got} {prim}(s), expected {want} (one a dense-kind "
          "class whose tables travel) — a table crosses the mesh that "
          "the byte rule keeps at home, or one that should travel ships "
          "its rows")
  n_gather = s.counts.get("gather", 0)
  if expect.gather_count is not None and n_gather != expect.gather_count:
    out.append(
        f"{name}: {n_gather} gather op(s), expected "
        f"{expect.gather_count} — the fused just-in-time schedule "
        "drifted: fewer gathers means rounds re-grew a monolithic "
        "pre-gather (row staging the overlap was built to hide); more "
        "means a round body gathers twice")
  if expect.wire_float_dtype is not None:
    bad = sorted({d for d in s.a2a_dtypes + s.ppermute_dtypes
                  if "float" in d and d != expect.wire_float_dtype})
    if bad:
      out.append(
          f"{name}: float exchange payload(s) travel {bad}, expected "
          f"{expect.wire_float_dtype} — the plan's wire_dtype contract "
          "is broken (an f32 payload under a narrowed wire multiplies "
          "exchange bytes; a narrowed one under f32 silently loses "
          "precision)")
  if expect.scatter_total is not None \
      and len(s.scatter_shapes) != expect.scatter_total:
    out.append(
        f"{name}: {len(s.scatter_shapes)} scatter op(s) of any kind, "
        f"expected exactly {expect.scatter_total} — a forward-only "
        "serve step that scatters is reverse-mode (or a buffer write) "
        "leaking into the inference path")
  if expect.require_convert is not None \
      and tuple(expect.require_convert) not in set(s.convert_pairs):
    out.append(
        f"{name}: no {expect.require_convert[0]} -> "
        f"{expect.require_convert[1]} convert_element_type in the trace "
        "— the dequantize-on-gather path is not actually widening "
        "quantized rows on device")
  if s.f64_prims:
    out.append(
        f"{name}: float64 values produced by {sorted(set(s.f64_prims))} "
        "— f64 leaks double buffer bytes and fail TPU lowering")
  if s.callback_prims:
    out.append(
        f"{name}: host callback primitives in the hot path: "
        f"{sorted(set(s.callback_prims))}")
  return out


# ---------------------------------------------------------------------------
# the traced fixture: tiny real artifacts on a virtual CPU mesh
# ---------------------------------------------------------------------------

WORLD = 4
VOCAB = (5000, 300, 40)   # host-tier / device-sparse / MXU-dense at the
WIDTH = 16                # thresholds used below
BATCH = 16
BATCH_TABLES = 512        # the batch at which the 40-row table travels


def _require_cpu_devices():
  import jax
  if len(jax.devices()) < WORLD:
    raise RuntimeError(
        f"jaxpr audit needs >= {WORLD} devices (virtual CPU mesh); set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8 and "
        "JAX_PLATFORMS=cpu BEFORE importing jax (tools/graftlint.py and "
        "tests/conftest.py both do).")


def build_artifacts() -> Dict[str, Tuple[Any, Expectation]]:
  """Build and abstractly trace the audited artifacts.

  Returns ``{artifact_name: (jaxpr, Expectation)}`` for:

  - ``sparse_step``:        ``make_sparse_train_step(guard=False)``
  - ``sparse_step_guard``:  ``make_sparse_train_step(guard=True)``
  - ``sparse_step_tables``: ``sparse_step`` at a batch of 512, where the
    dense-kind class's 40-row table is fewer bytes than the rows it would
    ship: its TABLE travels (one all_gather, one reduce_scatter, no
    all_to_all for it). Every other artifact runs at a batch of 16, where
    its rows travel, and pins zero of both
  - ``sparse_step_dynvocab``: the guarded step on an ``oov='allocate'``
    plan — the dynamic-vocabulary artifact: still exactly one
    scatter-add per class and ZERO host callbacks (allocation is a
    host pass BETWEEN steps, never a callback from the trace), plus
    the allocate policy's commit gate (one pmin, like every guard)
  - ``sparse_step_wire``:   same step on a ``wire_dtype='bf16',
    dedup_exchange=True`` plan (every float exchange must be bf16)
  - ``sparse_step_pipe_f32`` / ``..._bf16`` / ``..._fp8``: the same
    step on ``overlap='pipelined', exchange_chunks=2`` plans — zero
    all_to_alls, exactly ``3 buckets x (world-1) x chunks`` ppermute
    rounds, float payloads in the mode's wire dtype (the fp8 artifact
    also dedups, pinning the pipelined x dedup composition)
  - ``sparse_step_fused_f32`` / ``..._fp8``: the same step on
    ``overlap='fused'`` plans (raw and dedup) — same ppermute-round and
    zero-all_to_all pins as pipelined, plus an exact total ``gather``
    count pinning the just-in-time per-(round, chunk) gather schedule
    (the absence of a monolithic pre-gather)
  - ``tiered_step``:        ``make_tiered_train_step`` (host-tier class)
  - ``tiered_step_guard``:  ``make_tiered_train_step(guard=True)`` —
    the commit gate's pmin must appear exactly once here too, so a
    poison batch cannot fork the tiers
  - ``eval_step``:          ``make_sparse_eval_step`` (zero scatters)
  - ``serve_step_f32`` / ``serve_step_int8``: ``serving.make_serve_step``
    over the frozen (optimizer-lanes-stripped) inference image — pinned
    at zero scatter ops of ANY kind (the no-reverse-mode pin), zero
    host callbacks, and (int8) the int8 -> f32 dequantize-on-gather
    convert
  """
  _require_cpu_devices()
  import jax
  import jax.numpy as jnp
  import numpy as np
  import optax

  from ..layers.embedding import TableConfig
  from ..layers.planner import DistEmbeddingStrategy
  from ..models import DLRM, bce_loss
  from ..models.dlrm import _dlrm_initializer
  from ..ops.packed_table import sparse_rule
  from ..parallel import create_mesh
  from ..parallel.lookup_engine import DistributedLookup, class_param_name
  from ..tiering import HostTierStore, TieredPrefetcher, TieringConfig, \
      TieringPlan
  from ..tiering.train import init_tiered_state
  from ..training import (
      init_sparse_state_direct,
      make_sparse_eval_step,
      make_sparse_train_step,
      make_tiered_train_step,
      shard_batch,
      shard_params,
  )

  mesh = create_mesh(WORLD)
  mesh_axes = tuple(str(a) for a in mesh.axis_names)
  rule = sparse_rule("adagrad", 0.05)
  opt = optax.adam(1e-3)
  model = DLRM(vocab_sizes=list(VOCAB), embedding_dim=WIDTH,
               bottom_mlp=(32, WIDTH), top_mlp=(32, 1), world_size=WORLD,
               strategy="memory_balanced", dense_row_threshold=60)

  r = np.random.default_rng(0)
  numerical = r.standard_normal((BATCH, 13)).astype(np.float32)
  cats = [r.integers(0, v, BATCH, dtype=np.int32) for v in VOCAB]
  labels = r.integers(0, 2, BATCH).astype(np.float32)
  batch0 = (numerical, cats, labels)
  dummy = [jnp.zeros((2, WIDTH), jnp.float32) for _ in VOCAB]
  dense_params = model.init(
      jax.random.PRNGKey(0), numerical[:2], [c[:2] for c in cats],
      emb_acts=dummy)["params"]

  def class_shapes(plan, layouts):
    out = {}
    for key in plan.class_keys:
      if plan.classes[key].kind == "sparse":
        name = class_param_name(*key)
        lay = layouts[name]
        out[name] = (lay.phys_rows, lay.phys_width)
    return out

  def n_padded_buckets(plan, batch=BATCH):
    # the fixture's inputs are all hotness-1 and dense, so every bucket
    # whose ROWS travel is a padded bucket: a train step exchanges 3x per
    # bucket (ids, activations, reverse cotangents), eval 2x. At BATCH
    # (4 samples a rank) the 40-row table is more bytes than its rows:
    # every class's rows travel.
    eng = DistributedLookup(plan, dp_input=True)
    return sum(len(eng._buckets(k, lambda i: 1)) for k in plan.class_keys
               if not eng.tables_travel(k, lambda i: 1, batch // WORLD))

  def side_counts(plan, train=True, batch=BATCH):
    # dense-kind classes whose TABLES travel instead: one all_gather
    # each, and one reduce_scatter where there is a backward
    eng = DistributedLookup(plan, dp_input=True)
    n = sum(eng.tables_travel(k, lambda i: 1, batch // WORLD)
            for k in plan.class_keys)
    return {"all_gather_count": n, "reduce_scatter_count": n if train else 0}

  artifacts: Dict[str, Tuple[Any, Expectation]] = {}

  # ---- all-device sparse step (guarded and not) + eval -------------------
  plan = DistEmbeddingStrategy(
      [TableConfig(input_dim=v, output_dim=WIDTH,
                   initializer=_dlrm_initializer(v)) for v in VOCAB],
      WORLD, "memory_balanced", dense_row_threshold=60)
  engine = DistributedLookup(plan, dp_input=True)
  shapes = class_shapes(plan, engine.fused_layouts(rule))
  state = shard_params(
      init_sparse_state_direct(plan, rule, dense_params, opt,
                               jax.random.PRNGKey(1)), mesh)
  bt = shard_batch(batch0, mesh)
  nb = n_padded_buckets(plan)
  for guard in (False, True):
    step = make_sparse_train_step(model, plan, bce_loss, opt, rule, mesh,
                                  state, batch0, donate=False, guard=guard)
    jx = jax.make_jaxpr(step)(state, *bt)
    artifacts["sparse_step_guard" if guard else "sparse_step"] = (
        jx.jaxpr, Expectation(shapes, mesh_axes, guard=guard,
                              a2a_count=3 * nb, ppermute_count=0,
                              wire_float_dtype="float32",
                              **side_counts(plan)))

  # ---- the same step at a batch the small table is fewer bytes than -----
  # 128 samples a rank against a 40-row table (64 with its window): the
  # dense-kind class's TABLE travels. One all_gather, one reduce_scatter,
  # and all_to_alls for the sparse-kind buckets alone.
  rt = np.random.default_rng(1)
  batch_t = (rt.standard_normal((BATCH_TABLES, 13)).astype(np.float32),
             [rt.integers(0, v, BATCH_TABLES, dtype=np.int32)
              for v in VOCAB],
             rt.integers(0, 2, BATCH_TABLES).astype(np.float32))
  step_tab = make_sparse_train_step(model, plan, bce_loss, opt, rule, mesh,
                                    state, batch_t, donate=False)
  jx = jax.make_jaxpr(step_tab)(state, *shard_batch(batch_t, mesh))
  assert side_counts(plan, batch=BATCH_TABLES)["all_gather_count"] == 1
  artifacts["sparse_step_tables"] = (
      jx.jaxpr, Expectation(shapes, mesh_axes, guard=False,
                            a2a_count=3 * n_padded_buckets(plan,
                                                           BATCH_TABLES),
                            ppermute_count=0, wire_float_dtype="float32",
                            **side_counts(plan, batch=BATCH_TABLES)))

  # ---- dynamic-vocabulary step (oov='allocate', round 13) ----------------
  # Same tables/state/batch: the dynamic id layer translates HOST-side
  # (between steps, the prefetcher pattern), so the traced step differs
  # from sparse_step_guard only by the allocate policy's commit gate
  # (untranslated-leak tripwire) — pinned here at ONE scatter-add per
  # class, ZERO host callbacks (the allocation protocol never calls
  # back into the translator from the trace), one pmin, and the same
  # 3-per-bucket a2a count. The batch needs no translator: ids already
  # in [0, vocab) are exactly what a translated stream looks like.
  plan_dv = DistEmbeddingStrategy(
      [TableConfig(input_dim=v, output_dim=WIDTH,
                   initializer=_dlrm_initializer(v)) for v in VOCAB],
      WORLD, "memory_balanced", dense_row_threshold=60,
      oov="allocate", admit_threshold=2, evict_ttl=100)
  step_dv = make_sparse_train_step(model, plan_dv, bce_loss, opt, rule,
                                   mesh, state, batch0, donate=False,
                                   guard=True)
  jx = jax.make_jaxpr(step_dv)(state, *bt)
  artifacts["sparse_step_dynvocab"] = (
      jx.jaxpr, Expectation(shapes, mesh_axes, guard=True,
                            a2a_count=3 * nb, ppermute_count=0,
                            wire_float_dtype="float32",
                            **side_counts(plan_dv)))

  ev = make_sparse_eval_step(model, plan, rule, mesh, state, batch0)
  jx = jax.make_jaxpr(ev)(state, *bt[:2])
  artifacts["eval_step"] = (
      jx.jaxpr,
      Expectation(shapes, mesh_axes, guard=False, scatters_per_class=0,
                  a2a_count=2 * nb, ppermute_count=0,
                  wire_float_dtype="float32",
                  **side_counts(plan, train=False)))

  # ---- serve steps on the frozen inference image (round 12) --------------
  # make_serve_step over export.freeze's stripped buffers: same exchange
  # structure as eval (ids dp->mp, activations mp->dp), but pinned HARD
  # at zero scatter ops of ANY operand shape (reverse mode through a
  # gather lowers to a scatter — forbidding them all is the
  # no-reverse-mode pin) and zero host callbacks. The int8 artifact
  # additionally pins the int8 -> f32 dequantize-on-gather convert on
  # the traced evidence.
  from ..serving.engine import make_serve_step
  from ..serving.export import freeze, frozen_device_state
  for q in ("f32", "int8"):
    frozen = freeze(plan, rule, state, quantize=q)
    sstate = frozen_device_state(frozen, plan, mesh)
    sstep = make_serve_step(model, plan, frozen.meta, mesh, sstate,
                            (batch0[0], batch0[1]))
    jx = jax.make_jaxpr(sstep)(sstate, *bt[:2])
    serve_shapes = {n: (m.packed.phys_rows, m.packed.phys_width)
                    for n, m in frozen.meta.items()}
    artifacts[f"serve_step_{q}"] = (
        jx.jaxpr,
        Expectation(serve_shapes, mesh_axes, guard=False,
                    scatters_per_class=0, a2a_count=2 * nb,
                    ppermute_count=0, wire_float_dtype="float32",
                    scatter_total=0,
                    require_convert=("int8", "float32") if q == "int8"
                    else None, **side_counts(plan, train=False)))

  # ---- compressed-wire sparse step (bf16 wire + dedup'd exchange) --------
  # identical table layout, so the f32 state and batch reuse verbatim;
  # only the exchange payloads change — which is exactly the contract
  # the dtype invariant pins
  plan_w = DistEmbeddingStrategy(
      [TableConfig(input_dim=v, output_dim=WIDTH,
                   initializer=_dlrm_initializer(v)) for v in VOCAB],
      WORLD, "memory_balanced", dense_row_threshold=60,
      wire_dtype="bf16", dedup_exchange=True)
  step_w = make_sparse_train_step(model, plan_w, bce_loss, opt, rule, mesh,
                                  state, batch0, donate=False)
  jx = jax.make_jaxpr(step_w)(state, *bt)
  artifacts["sparse_step_wire"] = (
      jx.jaxpr, Expectation(shapes, mesh_axes, guard=False,
                            a2a_count=3 * n_padded_buckets(plan_w),
                            ppermute_count=0,
                            wire_float_dtype="bfloat16",
                            **side_counts(plan_w)))

  # ---- pipelined exchange steps (chunked ppermute schedule) --------------
  # same table layout again (the overlap knobs change no buffer); each
  # pins ZERO all_to_alls and exactly 3 exchanges x (world-1) rounds x
  # chunks ppermutes, plus the mode's in-flight float dtype. The fp8
  # artifact also dedups — pinning that the pipelined schedule composes
  # with the unique-block exchange (the ISSUE's chunked dedup path).
  CHUNKS = 2
  for wname, dedup in (("f32", False), ("bf16", False), ("fp8", True)):
    plan_p = DistEmbeddingStrategy(
        [TableConfig(input_dim=v, output_dim=WIDTH,
                     initializer=_dlrm_initializer(v)) for v in VOCAB],
        WORLD, "memory_balanced", dense_row_threshold=60,
        wire_dtype=wname, dedup_exchange=dedup,
        overlap="pipelined", exchange_chunks=CHUNKS)
    step_p = make_sparse_train_step(model, plan_p, bce_loss, opt, rule,
                                    mesh, state, batch0, donate=False)
    jx = jax.make_jaxpr(step_p)(state, *bt)
    nb_p = n_padded_buckets(plan_p)
    artifacts[f"sparse_step_pipe_{wname}"] = (
        jx.jaxpr,
        Expectation(shapes, mesh_axes, guard=False, a2a_count=0,
                    ppermute_count=3 * nb_p * (WORLD - 1) * CHUNKS,
                    wire_float_dtype={
                        "f32": "float32", "bf16": "bfloat16",
                        "fp8": "float8_e4m3fn"}[wname],
                    **side_counts(plan_p)))

  # ---- fused exchange steps (just-in-time per-round gathers) -------------
  # overlap='fused' keeps the pipelined ROUND schedule (ids still ride
  # the chunked ppermute wire, and the k=0 self-round sends nothing, so
  # the ppermute pin is the SAME 3 x buckets x (world-1) x chunks
  # formula) but moves each round's row gather inside the round body.
  # The gather_count pin is the structural evidence: the pipelined
  # trace of this exact fixture carries 22 gathers (one monolithic
  # pre-gather per bucket plus model/reassembly takes); fused f32 raw
  # splits those into per-(round, chunk) gathers — 34 — and fused fp8
  # dedup (uniq-block rows gathered per round, plus the dedup build's
  # own takes) carries 42. A refactor that quietly re-hoists the
  # gather to a pre-pass collapses the count back toward 22 and fails.
  for wname, dedup, n_gather in (("f32", False, 34), ("fp8", True, 42)):
    plan_f = DistEmbeddingStrategy(
        [TableConfig(input_dim=v, output_dim=WIDTH,
                     initializer=_dlrm_initializer(v)) for v in VOCAB],
        WORLD, "memory_balanced", dense_row_threshold=60,
        wire_dtype=wname, dedup_exchange=dedup,
        overlap="fused", exchange_chunks=CHUNKS)
    step_f = make_sparse_train_step(model, plan_f, bce_loss, opt, rule,
                                    mesh, state, batch0, donate=False)
    jx = jax.make_jaxpr(step_f)(state, *bt)
    nb_f = n_padded_buckets(plan_f)
    artifacts[f"sparse_step_fused_{wname}"] = (
        jx.jaxpr,
        Expectation(shapes, mesh_axes, guard=False, a2a_count=0,
                    ppermute_count=3 * nb_f * (WORLD - 1) * CHUNKS,
                    gather_count=n_gather,
                    wire_float_dtype={
                        "f32": "float32",
                        "fp8": "float8_e4m3fn"}[wname],
                    **side_counts(plan_f)))

  # ---- tiered step (host-tier class + device tiers) ----------------------
  plan_t = DistEmbeddingStrategy(
      [TableConfig(input_dim=v, output_dim=WIDTH,
                   initializer=_dlrm_initializer(v)) for v in VOCAB],
      WORLD, "memory_balanced", dense_row_threshold=60,
      host_row_threshold=1000)
  tplan = TieringPlan(plan_t, rule, TieringConfig(cache_fraction=0.3,
                                                  staging_grps=64))
  store = HostTierStore(tplan)
  state_t = shard_params(
      init_tiered_state(tplan, store, rule, dense_params, opt,
                        jax.random.PRNGKey(2), mesh=mesh), mesh)
  prefetcher = TieredPrefetcher(tplan, store, mesh)
  staged = prefetcher.prepare(cats)
  step_t = make_tiered_train_step(model, tplan, bce_loss, opt, rule, mesh,
                                  state_t, batch0, donate=False)
  # effective layouts: tiered classes' compact buffers grow by this
  # step's staging shapes (see make_tiered_train_step)
  engine_t = DistributedLookup(plan_t, dp_input=True)
  layouts_t = dict(engine_t.fused_layouts(
      rule, rows_overrides=tplan.rows_overrides))
  from ..ops.packed_table import PackedLayout
  for name, spec in tplan.tier_specs.items():
    s = staged.s_eff[name]  # padded per-rank staging rows this step
    layouts_t[name] = PackedLayout(
        rows=(spec.cache_grps + s) * spec.rpp,
        width=layouts_t[name].width, n_aux=rule.n_aux)
  shapes_t = class_shapes(plan_t, layouts_t)
  jx = jax.make_jaxpr(step_t)(state_t, staged.device, *bt)
  artifacts["tiered_step"] = (
      jx.jaxpr, Expectation(shapes_t, mesh_axes, guard=False,
                            a2a_count=3 * n_padded_buckets(plan_t),
                            ppermute_count=0,
                            wire_float_dtype="float32",
                            **side_counts(plan_t)))

  # ---- guarded tiered step (PR 2 carried follow-on) -----------------------
  # same plan/state/staging; the guard adds exactly one pmin (the
  # collective commit gate now also covering the staged write-back) and
  # the psum'd OOV counters — both pinned by Expectation + fingerprint
  step_tg = make_tiered_train_step(model, tplan, bce_loss, opt, rule, mesh,
                                   state_t, batch0, donate=False,
                                   guard=True)
  jx = jax.make_jaxpr(step_tg)(state_t, staged.device, *bt)
  artifacts["tiered_step_guard"] = (
      jx.jaxpr, Expectation(shapes_t, mesh_axes, guard=True,
                            a2a_count=3 * n_padded_buckets(plan_t),
                            ppermute_count=0,
                            wire_float_dtype="float32",
                            **side_counts(plan_t)))
  return artifacts


# ---------------------------------------------------------------------------
# audit + fingerprint persistence
# ---------------------------------------------------------------------------


def run_audit(update_fingerprints: bool = False,
              fingerprint_path: Optional[str] = None,
              log: Callable[[str], None] = lambda s: None
              ) -> Tuple[List[str], Dict[str, Dict[str, int]]]:
  """Trace, audit, and diff fingerprints for every artifact.

  Returns ``(violations, fingerprints)``. With ``update_fingerprints``
  the persisted baselines are rewritten instead of diffed (structural
  violations still report)."""
  path = fingerprint_path or FINGERPRINT_PATH
  violations: List[str] = []
  prints: Dict[str, Dict[str, int]] = {}
  artifacts = build_artifacts()
  for name, (jaxpr, expect) in artifacts.items():
    log(f"auditing {name} ...")
    s = summarize(jaxpr)
    violations.extend(audit_summary(name, s, expect))
    prints[name] = fingerprint(s)

  if update_fingerprints:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
      json.dump(prints, f, indent=1, sort_keys=True)
      f.write("\n")
    log(f"wrote {path}")
    return violations, prints

  if not os.path.exists(path):
    violations.append(
        f"no fingerprint baseline at {path} — run "
        "`python tools/graftlint.py --update-fingerprints` and commit it")
    return violations, prints
  with open(path) as f:
    baseline = json.load(f)
  violations.extend(diff_fingerprints(baseline, prints))
  return violations, prints


def diff_fingerprints(baseline: Dict[str, Dict[str, int]],
                      prints: Dict[str, Dict[str, int]]) -> List[str]:
  """Loud per-op-class diff of traced fingerprints vs the committed
  baseline (empty when identical)."""
  out = []
  for name, fp in prints.items():
    base = baseline.get(name)
    if base is None:
      out.append(
          f"{name}: no baseline fingerprint — regenerate with "
          "--update-fingerprints")
      continue
    if base != fp:
      drift = []
      for k in sorted(set(base) | set(fp)):
        a, b = base.get(k, 0), fp.get(k, 0)
        if a != b:
          drift.append(f"{k}: {a} -> {b}")
      out.append(
          f"{name}: jaxpr fingerprint drift ({'; '.join(drift)}). If "
          "intentional, regenerate with "
          "`python tools/graftlint.py --update-fingerprints`.")
  for name in baseline:
    if name not in prints:
      out.append(
          f"{name}: baseline fingerprint exists but artifact is no "
          "longer audited — regenerate with --update-fingerprints")
  return out
