"""DistributedEmbedding: hybrid model-parallel embedding over a TPU mesh.

Counterpart of the reference wrapper
(`/root/reference/distributed_embeddings/python/layers/dist_model_parallel.py:327-693`)
with the same constructor surface (embeddings, strategy,
column_slice_threshold, row_slice, dp_input, input_table_map) but a
TPU-native execution model:

- Physical layout: per (width, combiner) class, all ranks' fused tables are
  stacked row-wise into one 2-D array ``[world * max_rows, width]`` sharded
  over the mesh axis. One array per class instead of N per-rank variables
  makes the whole model a uniform SPMD program (see
  ``parallel/lookup_engine.py``).
- Comm: ``lax.all_to_all`` inside ``shard_map`` replaces ``hvd.alltoall``.
- Hybrid single-backward: embedding grads are grads of mesh-sharded arrays —
  local by construction. Dense grads are finalized by ``DistributedOptimizer``
  (an optax transformation) — replacing the reference's Horovod tape/optimizer
  monkey-patching (`dist_model_parallel.py:696-799`) with ~20 functional lines.
- Checkpoint: :func:`get_weights` / :func:`set_weights` give the reference's
  global-view numpy semantics (`dist_model_parallel.py:471-664`); per-shard
  assembly goes through ``jax.make_array_from_callback`` so each device
  materializes only its slice (the TPU equivalent of the reference's chunked
  scatter-update/allgather dance around MPI 32-bit limits).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compat import axis_size
from ..parallel.lookup_engine import (
    DistributedLookup,
    class_param_name,
    pack_mp_inputs,
    padded_rows,
)
from .embedding import resolve_initializer
from .planner import DistEmbeddingStrategy

MP_PARAM_PREFIX = "mp_table_"


def is_model_parallel_param(path_element_names: Sequence[str]) -> bool:
  """True if a param pytree path belongs to a sharded embedding table."""
  return any(str(p).startswith(MP_PARAM_PREFIX) for p in path_element_names)


def make_class_initializer(plan: DistEmbeddingStrategy, key):
  """Initializer for one class buffer [world * max_rows, width].

  Each member shard's rows are drawn from its own table initializer (column
  slices get independent draws at slice shape, matching the reference where
  each slice is its own variable); padding rows are zeros. Equivalent of the
  reference ``ConcatInitializer`` (`dist_model_parallel.py:29-40`) extended
  with row padding. Rank blocks concatenate along the row axis (see
  ``DistributedLookup.param_shapes``).
  """
  cp = plan.classes[key]
  world = plan.world_size
  rows = padded_rows(plan, key)

  def init(rng, shape, dtype=jnp.float32):
    del shape  # fixed by the plan
    blocks = []
    for rank in range(world):
      parts = []
      for sh in cp.shards_per_rank[rank]:
        rng, sub = jax.random.split(rng)
        fn = resolve_initializer(sh.initializer)
        parts.append(jnp.asarray(fn(sub, (sh.input_dim, cp.width)), dtype))
      pad = rows - cp.rows_per_rank[rank]
      if pad:
        parts.append(jnp.zeros((pad, cp.width), dtype))
      blocks.append(jnp.concatenate(parts, axis=0) if parts
                    else jnp.zeros((rows, cp.width), dtype))
    return jnp.concatenate(blocks, axis=0)

  return init


class DistributedEmbedding(nn.Module):
  """Hybrid-parallel distributed embedding layer (flax).

  Args:
    embeddings: global list of ``TableConfig``s / ``Embedding`` layers / dicts.
    strategy: 'basic' | 'memory_balanced' | 'memory_optimized'.
    column_slice_threshold: max elements per slice; None = auto when there
      are fewer tables than workers.
    row_slice: max elements per row (vocabulary) slice, or None. Tables
      larger than this are split along the vocab dim into the smallest
      power-of-two number of row slices under the threshold (capped by
      world size), placed like any other shard. Goes beyond the reference,
      which stubs row slicing with NotImplementedError
      (`dist_model_parallel.py:364-365`). Column slicing wins when both
      thresholds trigger on one table.
    dp_input: True = [B_local, ...] data-parallel inputs; False = packed
      model-parallel inputs from :func:`pack_mp_inputs`.
    input_table_map: input i feeds table input_table_map[i]; None = identity.
    world_size: number of mesh shards (defaults to 1; must equal the mesh
      axis size when used under shard_map).
    axis_name: mesh axis to communicate over.

  Usage with a mesh (world > 1): init params outside shard_map (class params
  get shape [world * max_rows, width]), shard them with
  ``PartitionSpec(axis_name, None)``, and call apply inside
  ``shard_map``. With world == 1 it is an ordinary layer.
  """

  embeddings: Sequence[Any]
  strategy: str = "basic"
  column_slice_threshold: Optional[int] = None
  row_slice: Optional[Any] = None
  dp_input: bool = True
  input_table_map: Optional[Sequence[int]] = None
  world_size: int = 1
  axis_name: str = "mp"
  # Tables with input_dim <= dense_row_threshold are served by the MXU
  # one-hot path instead of HBM row gathers (see planner); 0 disables.
  dense_row_threshold: int = 0
  # Per global input id, its static hotness. Used in BOTH input modes:
  # the planner weighs it when balancing width-class generations so every
  # backward scatter stays in XLA's fast regime (None falls back to
  # inputs-per-table weights — pass it whenever hotness is known up
  # front). With dp_input=False it is additionally REQUIRED to match what
  # was passed to pack_mp_inputs. None = all one-hot.
  input_hotness: Optional[Sequence[int]] = None
  # Expected per-step GLOBAL batch (optional): lets the planner score
  # generation layouts with its measured scatter-regime cost model instead
  # of ratio balancing alone (see planner._assign_generations).
  batch_hint: Optional[int] = None

  def __post_init__(self):
    super().__post_init__()
    if self.row_slice is not None and (isinstance(self.row_slice, bool)
                                       or not isinstance(self.row_slice,
                                                         int)):
      raise TypeError(
          f"row_slice must be an int element threshold, got "
          f"{self.row_slice!r}")

  @property
  def plan(self) -> DistEmbeddingStrategy:
    if not hasattr(self, "_plan_cache"):
      object.__setattr__(
          self, "_plan_cache",
          DistEmbeddingStrategy(
              list(self.embeddings), self.world_size, self.strategy,
              input_table_map=(list(self.input_table_map)
                               if self.input_table_map is not None else None),
              column_slice_threshold=self.column_slice_threshold,
              dense_row_threshold=self.dense_row_threshold,
              row_slice_threshold=self.row_slice,
              input_hotness=(list(self.input_hotness)
                             if self.input_hotness is not None else None),
              batch_hint=self.batch_hint))
    return self._plan_cache

  @nn.compact
  def __call__(self, inputs):
    plan = self.plan
    engine = DistributedLookup(plan, dp_input=self.dp_input,
                               axis_name=self.axis_name)
    shapes = engine.param_shapes()
    class_params = {}
    for key in plan.class_keys:
      name = class_param_name(*key)
      shape = shapes[name]
      if self.is_initializing():
        class_params[name] = self.param(
            name, make_class_initializer(plan, key), shape)
      else:
        # Read the stored value directly: under shard_map the
        # [world * R, w] param arrives as its local [R, w] block, which
        # flax's shape-checking self.param would reject.
        class_params[name] = self.scope.get_variable("params", name)

    if self.is_initializing() and self.world_size > 1:
      # init runs outside shard_map on global shapes; skip the collective
      # forward and just report output structure.
      if self.dp_input:
        from ..parallel.lookup_engine import _batch_of
        b = _batch_of(inputs)
      else:
        first = next(iter(inputs.values()))
        b = first.shape[2] // self.world_size
      return [jnp.zeros((b, cfg.output_dim))
              for cfg in (plan.global_configs[t] for t in plan.input_table_map)]

    if self.dp_input:
      self._sow_oov_metrics(engine, inputs)
      return engine.forward(class_params, inputs)
    return engine.forward_mp(class_params, inputs,
                             hotness=self.input_hotness)

  def _sow_oov_metrics(self, engine, inputs) -> None:
    """Per-class OOV occurrence counters via the ``'metrics'`` variable
    collection — the module-forward counterpart of the counters the
    guarded train step and ``make_sparse_eval_step(with_metrics=True)``
    already return. DP-INPUT forwards only: the packed-mp path
    (``dp_input=False``) receives pre-routed tensors whose per-input id
    view no longer exists here — its ids were clipped/validated at
    ``pack_mp_inputs`` time on the host, where the policy is already
    enforceable eagerly.

    Opt-in by mutability: ``module.apply(vars, x, mutable=['metrics'])``
    returns ``(out, {'metrics': {'oov_<class>': count}})``; a plain
    apply (serving) neither computes nor carries the counters, and init
    never records them (the collection would otherwise pollute the
    variables tree every caller threads around). Counters are int32
    scalars, psum'd across the mesh under ``world_size > 1`` (the
    forward already runs inside shard_map there) — matching the train
    step's global-count convention."""
    if self.is_initializing() or not self.is_mutable_collection("metrics"):
      return
    oov = engine.oov_counts(inputs)
    if self.world_size > 1:
      oov = {n: jax.lax.psum(c, self.axis_name) for n, c in oov.items()}
    for name, c in oov.items():
      # reduce_fn accumulates across calls within one apply (a module
      # invoked twice sums, like the step metrics would)
      self.sow("metrics", f"oov_{name}", c,
               init_fn=lambda: jnp.zeros((), jnp.int32),
               reduce_fn=lambda a, b: a + b)


# ---------------------------------------------------------------------------
# Global-view checkpoint get/set (reference `dist_model_parallel.py:471-664`)
# ---------------------------------------------------------------------------


def _fetch_rows(arr, row0: int, n: int, width: int,
                max_fetch_elements: int) -> np.ndarray:
  """Fetch rows ``[row0, row0+n)`` of a (possibly sharded) device array in
  bounded host-memory chunks.

  Multi-process safe: when ``arr`` is a jax.Array this process cannot
  fully address (multi-controller runs), the window is assembled from
  ``addressable_shards`` instead of global indexing — which works exactly
  when this process's devices hold the window. A window owned by another
  process raises with guidance instead of hanging or crashing inside
  XLA (the reference handles the same situation with chunked
  ``hvd.allgather``, `dist_model_parallel.py:596-617`; here cross-process
  windows are served by the per-process checkpoint files instead)."""
  if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
    from ..parallel.mesh import addressable_row_spans
    out = np.empty((n, arr.shape[1]) if arr.ndim == 2 else (n,),
                   arr.dtype)
    have = np.zeros((n,), bool)
    for s0, s1, shard in addressable_row_spans(arr):
      lo, hi = max(s0, row0), min(s1, row0 + n)
      if lo < hi:
        # slice ON DEVICE before the host copy: a small window over a
        # multi-GiB local shard must not stage the whole shard on host
        # (this function's bounded-host-memory contract)
        out[lo - row0:hi - row0] = np.asarray(
            shard.data[lo - s0:hi - s0])
        have[lo - row0:hi - row0] = True
    if not have.all():
      raise RuntimeError(
          f"rows [{row0}, {row0 + n}) of a non-fully-addressable array are "
          "not owned by this process. In multi-controller runs, fetch "
          "global weights from the per-process checkpoint files "
          "(checkpoint.save writes only locally-addressable rank blocks) "
          "or restrict get_weights to tables whose shards are local.")
    return out
  chunk = max(1, max_fetch_elements // max(1, width))
  if n <= chunk:
    return np.asarray(jax.device_get(arr[row0:row0 + n]))
  out = None
  for c0 in range(0, n, chunk):
    cn = min(chunk, n - c0)
    block = np.asarray(jax.device_get(arr[row0 + c0:row0 + c0 + cn]))
    if out is None:
      out = np.empty((n,) + block.shape[1:], block.dtype)
    out[c0:c0 + cn] = block
  return out


def get_weights(plan: DistEmbeddingStrategy,
                class_params: Dict[str, Any],
                max_fetch_elements: int = 1 << 27) -> List[np.ndarray]:
  """Reassemble the global per-table weights from class-stacked params.

  Inverse of :func:`set_weights`: unstacks each rank's fused rows, undoes
  concat fusion via shard row offsets, and re-concatenates column slices in
  column order. On a single-controller setup the sharded arrays are fully
  addressable so this is collective-free (the reference needed chunked
  ``hvd.allgather``, capped at 2G elements per chunk,
  `dist_model_parallel.py:596-617`, for the same reason this function
  fetches per-shard row windows in ``max_fetch_elements``-bounded blocks:
  a global view of a jumbo class buffer must never be staged on one host
  at once — peak extra host memory here is one table plus one block).
  """
  weights = []
  for t, config in enumerate(plan.global_configs):
    parts = []
    row_sliced = False
    for rank, shard in plan.table_shard_map(t):
      key = plan.class_key_of(shard)
      cp = plan.classes[key]
      idx = cp.shards_per_rank[rank].index(shard)
      row0 = rank * padded_rows(plan, key) + \
          cp.row_offsets_per_rank[rank][idx]
      parts.append(_fetch_rows(class_params[class_param_name(*key)],
                               row0, shard.input_dim, cp.width,
                               max_fetch_elements))
      row_sliced = shard.row_sliced
    if len(parts) == 1:
      weights.append(parts[0])
    else:
      # table_shard_map orders by (col_start, row_start); a table is sliced
      # along exactly one dim, so this is a plain concat either way
      weights.append(np.concatenate(parts, axis=0 if row_sliced else 1))
  return weights


def set_weights(plan: DistEmbeddingStrategy,
                weights: Sequence[Union[np.ndarray, str]],
                mesh: Optional[Mesh] = None,
                axis_name: str = "mp") -> Dict[str, Any]:
  """Build class-stacked params from global per-table weights.

  Args:
    plan: the strategy.
    weights: per original table, [input_dim, output_dim] numpy arrays or
      ``.npy`` paths (mmap'd, like the reference `dist_model_parallel.py:492-493`).
    mesh: if given, assemble directly into mesh-sharded arrays via
      ``jax.make_array_from_callback`` — each device materializes only its
      own [max_rows, width] slice, so terabyte tables never exist on one host
      (TPU-native replacement for the reference's chunked scatter_update).

  Returns:
    name -> [world * max_rows, width] arrays (numpy if mesh is None).
  """
  if len(weights) != len(plan.global_configs):
    raise ValueError(
        f"Expected {len(plan.global_configs)} weights, got {len(weights)}")
  loaded = [np.load(w, mmap_mode="r") if isinstance(w, str) else np.asarray(w)
            for w in weights]
  for t, (w, cfg) in enumerate(zip(loaded, plan.global_configs)):
    if w.shape != (cfg.input_dim, cfg.output_dim):
      raise ValueError(f"weights[{t}] has shape {w.shape}, expected "
                       f"{(cfg.input_dim, cfg.output_dim)}")

  def rank_block(key, rank) -> np.ndarray:
    cp = plan.classes[key]
    block = np.zeros((padded_rows(plan, key), cp.width), np.float32)
    for idx, shard in enumerate(cp.shards_per_rank[rank]):
      row0 = cp.row_offsets_per_rank[rank][idx]
      block[row0:row0 + shard.input_dim] = (
          loaded[shard.table_id][
              shard.row_start:shard.row_start + shard.input_dim,
              shard.col_start:shard.col_end])
    return block

  out = {}
  for key in plan.class_keys:
    cp = plan.classes[key]
    name = class_param_name(*key)
    rows = padded_rows(plan, key)
    shape = (plan.world_size * rows, cp.width)
    if mesh is None:
      out[name] = np.concatenate([rank_block(key, r)
                                  for r in range(plan.world_size)])
    else:
      sharding = NamedSharding(mesh, P(axis_name, None))

      def cb(index, key=key, rows=rows):
        rank = (index[0].start or 0) // rows
        return rank_block(key, rank)

      out[name] = jax.make_array_from_callback(shape, sharding, cb)
  return out


# ---------------------------------------------------------------------------
# Hybrid-parallel training utilities
# (replacing the reference Horovod shims, `dist_model_parallel.py:696-799`)
# ---------------------------------------------------------------------------


def broadcast_variables(variables, root_rank: int = 0):
  """API-parity shim for the reference ``broadcast_variables``
  (`dist_model_parallel.py:698-712`).

  Under JAX there is nothing to broadcast: dense (data-parallel) params are
  *replicated by sharding* (``PartitionSpec()``), so every device reads the
  same buffer by construction, and model-parallel class params are sharded.
  Returns the variables unchanged.
  """
  del root_rank
  return variables


def hybrid_partition_specs(tree, axis_name: str = "mp"):
  """PartitionSpecs for any params-structured pytree (incl. optax states).

  Leaves under an ``mp_table_*`` key get ``P(axis_name, None)`` (the
  class-stacked ``[world * rows, width]`` table layout); everything else is
  replicated ``P()``. Use for shard_map in/out_specs of params, grads, and
  optimizer states — e.g. adagrad's ``sum_of_squares`` mirrors the param
  tree and must shard the same way (the reference gets this implicitly from
  per-rank TF slot variables; here it is one tree_map).
  """
  def spec(path, leaf):
    names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
    if is_model_parallel_param(names) and getattr(leaf, "ndim", 0) == 2:
      return P(axis_name, None)
    return P()

  return jax.tree_util.tree_map_with_path(spec, tree)


def finalize_hybrid_grads(grads, axis_name: str = "mp"):
  """Convert in-shard_map autodiff grads to global-batch-mean grads.

  The single-backward hybrid-parallel core, TPU-style. With a per-device
  loss of ``mean(batch_shard)``, autodiff under ``jax.shard_map`` already
  produces, per leaf:

  - dense (replicated, ``P()``) params: the *psum* of all devices'
    local-mean grads — shard_map inserts the psum because the transpose of
    replication is a sum (so do NOT psum again);
  - ``mp_table_*`` (sharded) params: the local shard's grad, with remote
    contributions already summed in by the reverse ``all_to_all``.

  Both are ``world_size ×`` the single-device global-batch-mean gradient, so
  dividing every leaf by the axis size yields grads *numerically identical*
  to non-distributed training — which is what the reference achieves with
  ``register_local_var`` + averaging Horovod allreduce
  (`dist_model_parallel.py:715-773`).
  """
  scale = 1.0 / axis_size(axis_name)
  return jax.tree_util.tree_map(lambda g: g * scale, grads)


def DistributedOptimizer(optimizer, axis_name: str = "mp"):
  """Wrap an optax optimizer for hybrid parallel in a single backward.

  Equivalent of the reference ``DistributedOptimizer``
  (`dist_model_parallel.py:743-773`): rescales shard_map autodiff grads to
  the global-batch-mean convention (see :func:`finalize_hybrid_grads`) and
  applies model-parallel (``mp_table_*``) grads locally. Use inside
  shard_map with a local-mean loss.
  """
  import optax

  def init_fn(params):
    return optimizer.init(params)

  def update_fn(updates, state, params=None):
    updates = finalize_hybrid_grads(updates, axis_name)
    return optimizer.update(updates, state, params)

  return optax.GradientTransformation(init_fn, update_fn)


def DistributedGradientTape(*args, **kwargs):
  """The reference patches Horovod's tape to mix local (model-parallel) and
  allreduced (data-parallel) grads in one backward
  (`dist_model_parallel.py:715-740`). JAX has no tape: use
  ``jax.value_and_grad`` inside shard_map and pass the grads through
  :func:`finalize_hybrid_grads` (or use :func:`DistributedOptimizer`)."""
  raise NotImplementedError(
      "JAX has no gradient tape. Use jax.value_and_grad inside shard_map + "
      "finalize_hybrid_grads / DistributedOptimizer for hybrid parallel.")


class BroadcastGlobalVariablesCallback:
  """API-parity shim (reference `dist_model_parallel.py:776-799`): dense
  variables are replicated by sharding, so initial-state broadcast is a
  no-op under JAX. Provided so training scripts can keep their structure."""

  def __init__(self, root_rank: int = 0, *args, **kwargs):
    self.root_rank = root_rank

  def on_batch_end(self, batch, logs=None):
    return None
