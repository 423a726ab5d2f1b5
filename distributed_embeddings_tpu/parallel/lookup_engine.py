"""SPMD distributed lookup engine: route ids, look up local shards, route back.

TPU-native re-design of the reference's ``DistributedEmbedding._call_base``
(`/root/reference/distributed_embeddings/python/layers/dist_model_parallel.py:401-463`):

  reference (MPMD, per-rank programs)        this engine (SPMD, one program)
  -----------------------------------        --------------------------------
  hvd.alltoall(ids, uneven splits)       ->  lax.all_to_all over the mesh axis
                                             on a uniform [world, slots, B, H]
                                             routing tensor (slot/hotness
                                             padding with a sentinel id)
  per-rank Python loop over local            two uniform local paths:
  Embedding layers (different code           * sparse classes: one fused-row
  on every rank)                               gather over the rank's packed
                                               class buffer (ops/packed_table)
                                             * dense classes (small vocab):
                                               windowed one-hot MXU matmuls —
                                               zero indexed row ops
  hvd.alltoall(outputs)                  ->  lax.all_to_all back
  reorder via rev_global_input_ids       ->  static piece-indexed reassembly
                                             (handles column-slice re-concat)

Performance model (measured, v5e): indexed row ops cost ~8 ns/row gathered
and ~23 ns/row scattered regardless of row bytes, and ``sort_key_val`` is
~200 ns/element. The engine therefore (1) serves small-vocab tables from the
MXU (no rows touched), (2) stores sparse tables lane-packed with optimizer
state interleaved so one gather feeds the forward AND the optimizer read,
and one scatter-add applies the whole update (`ops/packed_table.py`), and
(3) keeps the sort-based exact dedup (the reference's CUB pipeline,
`embedding_lookup_kernels.cu:464-633`) as an opt-in ``exact=True`` path.

Uneven all-to-all splits (the reference's hardest comm case, SURVEY §5) are
made uniform by padding each width class to its max slot count and bucketing
by hotness; padded entries carry a sentinel id and contribute nothing in
either direction. All shapes static, fully jit/grad compatible; ``shard_map``
differentiates through ``all_to_all`` natively, which is what replaces the
reference's ~100 lines of Horovod tape patching.

Small tables travel to the samples: where a dense (MXU one-hot) class's
block is fewer bytes than the rows it would ship (padded slots x global
batch x width against (world - 1) x class rows x width, static at trace
time: ``wire.dense_class_side``), the class is all-gathered inside the
step and every rank looks its OWN samples up in all of the class's real
tables; its ids, rows and cotangents leave the exchange altogether, and
autodiff's reduce-scatter lands each owner's summed gradient on its own
block (:meth:`DistributedLookup.tables_travel`). Placement, state and
checkpoints are the row exchange's: only the step gathers. In the fused
training step a sparse-kind class travels by the same byte rule, on its
packed block: optimizer lanes and all it is all-gathered, the local
samples read their fused rows from it by a row gather
(:class:`LocalIds`), and their per-occurrence deltas, scatter-added into
zeros of the gathered shape, are reduce-scattered home and added to the
owner's block; the planner gives small sparse tables a class of their own
where that saves every rank a padded slot
(``DistEmbeddingStrategy._split_small_sparse_tables``).

Every exchange rides :mod:`parallel.wire` (the sanctioned all_to_all /
ppermute home, graftlint GL109): the plan knobs compress and hide the wire
without touching the f32 master state — ``wire_dtype='bf16' | 'fp8'``
narrows float payloads (activations + reverse cotangents) in flight only
(fp8 ships a per-block amax scale inside the block), ``dedup_exchange=True``
ships each destination block's sorted-unique ids and ONE
activation/cotangent row per unique id (:class:`DedupRouted`; the dp side
keeps the inverse map, expands and combines locally, and the expansion's
transpose segment-sums duplicate cotangents before the reverse exchange),
and ``overlap='pipelined'`` replaces each monolithic exchange with
``(world - 1) * exchange_chunks`` ppermute rounds so consumption of chunk k
overlaps chunk k+1's flight. ``overlap='fused'`` goes one step further on
the fused sparse path: each round's activation payload is GATHERED
just-in-time immediately before its own send (:class:`FusedChunks`,
:meth:`DistributedLookup._z_sparse_fused_jit`), so round k's collective
can overlap round k+1's gather — and the reverse cotangent rounds each
carry only their own segment-sum/expand work. See ARCHITECTURE.md §13,
§15 and §26.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# One-shot one-hot staging bound, in [G, vcap] CELLS per bucket: buckets
# under it run a single windowed MXU matmul; above it, a lax.scan over
# batch chunks bounds the live staging. The bench swept 1<<25 / 1<<26 /
# 1<<27 / 1<<28 at 0.909 / 0.912 / 0.925 / 0.982 vs baseline — HIGHER is
# better (samples/s ratio, round 4): bigger one-shot blocks win
# consistently (the scan's per-chunk transposes cost ~4 ms/step at
# batch 64k; the big bf16 staging block is live only across one matmul
# pair). Default 1<<28 cells (512 MiB bf16) one-shots every Criteo
# bucket at batch 64k. Env-tunable, read ONCE at import (same convention
# as DE_TPU_GATHER_CHUNK: 0/unset = built-in default).
_ONEHOT_ONESHOT_CELLS = (
    int(os.environ.get("DE_TPU_ONEHOT_CELLS", "0") or "0") or (1 << 28))

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # annotation-only: importing layers here would close the
  # layers/__init__ -> dist_model_parallel -> parallel.lookup_engine cycle
  # and make `import distributed_embeddings_tpu.parallel` order-dependent
  from ..layers.planner import DistEmbeddingStrategy

from ..ops.packed_table import (
    LANES,
    PackedLayout,
    SparseRule,
    _grp_sub,
    gather_fused,
    gather_fused_chunked,
    mxu_operand_dtype,
    scatter_add_fused,
)
from ..ops.ragged import RaggedIds
from ..ops.sparse_grad import expand_unique_rows, unique_ids_map
from ..telemetry import scopes
from . import wire

PAD_ID = -1  # marks hotness padding in dense-padded ragged inputs


def _use_pallas_delta() -> bool:
  """True when the Pallas delta-build kernel (`ops/pallas_delta.py`) may
  run: ``DE_TPU_PALLAS_DELTA=1`` AND a real TPU backend (the graftlint
  GL126 gate/predicate contract).

  Default OFF: measured NET-NEGATIVE on Tiny (178 vs 162 ms wall) — the
  kernel runs 16.7 ms where the XLA chain's removable share is smaller
  than it traced: h=1 parts pay a whole extra HBM round-trip the XLA
  form never materializes (its delta fuses into the scatter's
  producer), and the batch-minor copies it targeted partially remain on
  the gather side. Kept as measured infrastructure + the delta_lanes
  twins (docs/BENCHMARKS.md round-5 staging study)."""
  if os.environ.get("DE_TPU_PALLAS_DELTA", "0") != "1":
    return False
  return jax.default_backend() == "tpu"


def class_param_name(width: int, combiner: Optional[str],
                     kind: str = "sparse", gen: int = 0) -> str:
  base = f"mp_table_w{width}_{combiner if combiner else 'cat'}"
  if kind != "sparse":
    base += "_dense"
  return base if gen == 0 else f"{base}_g{gen}"


def vocab_cap(n: int) -> int:
  """Static one-hot window size for a dense-class slot: pow2, >= 8."""
  cap = 8
  while cap < n:
    cap *= 2
  return cap


class Bucket(NamedTuple):
  """Slots of one class sharing (hotness, one-hot window size, row-sliced)."""

  h: int
  vcap: int  # 0 for sparse classes
  slot_idx_per_rank: tuple  # per rank, indices into slots_per_rank[rank]
  n_b: int  # padded slot count (max over ranks)
  rs: bool = False  # slots of row-sliced shards (partial-sum semantics)


class BucketKey(NamedTuple):
  """Sortable dict key for one (class, hotness, vocab-window) bucket.

  These keys live in dicts that cross jit/autodiff boundaries, where JAX
  sorts dict keys during pytree flattening; ``combiner=None`` is encoded as
  ``""`` so keys stay totally ordered when same-width classes mix a None
  and a string combiner."""

  width: int
  combiner: str  # "" encodes combiner=None
  kind: str
  gen: int
  h: int
  vcap: int
  rs: bool = False

  @property
  def class_key(self):
    return (self.width, self.combiner or None, self.kind, self.gen)


def bucket_key(class_key, h: int, vcap: int, rs: bool = False) -> BucketKey:
  w, c, kind, gen = class_key
  return BucketKey(w, c or "", kind, gen, h, vcap, rs)


def class_buckets(plan: DistEmbeddingStrategy, key, hotness_of) -> List[Bucket]:
  """Split a class's slots into static (hotness, vocab-window) buckets.

  Inputs of different hotness in one class would otherwise pad to the class
  max (e.g. the synthetic Tiny model mixes 1-hot and 10-hot inputs of the
  same width -> 10x wasted gather and all_to_all volume); dense-class slots
  of very different vocab would pad the one-hot window to the class max.
  """
  cp = plan.classes[key]
  dense = cp.kind == "dense"

  def bkey(slot):
    # row-sliced slots bucket separately: their routing windows make
    # per-shard sentinel counts partial, so mean division moves to the
    # dp side (assemble) instead of the mp-side combine
    h = hotness_of(slot.input_id)
    if h < 0:  # ragged value stream
      if dense:
        # unreachable through the planner when the input was declared
        # ragged (negative input_hotness demotes the table to sparse);
        # reachable when raggedness appears only at call time
        raise NotImplementedError(
            "ragged inputs into a dense-class (MXU one-hot) table: declare "
            "the input ragged up front (negative input_hotness entry) so "
            "the planner keeps its table on the sparse path, or pre-pad "
            "the input (ragged_to_padded)")
      if cp.combiner is None:
        raise ValueError("ragged distributed inputs require a combiner "
                         "('sum' or 'mean')")
    return (h, vocab_cap(slot.shard.input_dim) if dense else 0,
            slot.shard.row_sliced)

  keys = sorted({bkey(s) for slots in cp.slots_per_rank for s in slots})
  buckets = []
  for h, vcap_, rs in keys:
    per_rank = tuple(
        tuple(i for i, s in enumerate(slots) if bkey(s) == (h, vcap_, rs))
        for slots in cp.slots_per_rank)
    buckets.append(Bucket(h, vcap_, per_rank,
                          max(len(i) for i in per_rank), rs))
  return buckets


def padded_rows(plan: DistEmbeddingStrategy, key) -> int:
  """Buffer rows for a class: max fused rows, plus for dense classes enough
  tail padding that every slot's one-hot window fits inside the buffer."""
  cp = plan.classes[key]
  rows = cp.max_rows
  if cp.kind == "dense":
    for slots in cp.slots_per_rank:
      for s in slots:
        rows = max(rows, s.row_offset + vocab_cap(s.shard.input_dim))
  return rows


def _padded_slot_rows(plan: DistEmbeddingStrategy, key,
                      buckets: Sequence[Bucket]) -> int:
  """Rows a sample that every rank ships for the class: the buckets'
  padded slots, a sequence input's counted hotness times (its rows travel
  side by side)."""
  cp = plan.classes[key]
  return sum(b.n_b * (b.h if cp.combiner is None and b.h > 1 else 1)
             for b in buckets)


def _row_value_bytes(plan: DistEmbeddingStrategy) -> int:
  wd = wire.plan_wire_dtype(plan)
  return 4 if wd is None else jnp.dtype(wd).itemsize


def dense_class_traffic(plan: DistEmbeddingStrategy, key,
                        buckets: Sequence[Bucket], batch_local: int,
                        dp_input: bool = True):
  """``(side, rows_bytes, tables_bytes)`` of one dense-kind class at this
  local batch: :func:`wire.dense_class_side` on the class's own counts
  (``buckets``: its :func:`class_buckets` at the inputs' hotness).

  What a model-parallel small table costs is its PADDED slot count: every
  rank runs every (hotness, window) bucket at ``n_b`` = the most slots any
  rank has in it, so a class of 15 tables over four ranks can ship 11
  slots a rank for 3.75 real ones."""
  return wire.dense_class_side(
      plan.world_size, dp_input, _padded_slot_rows(plan, key, buckets),
      plan.world_size * batch_local, padded_rows(plan, key),
      plan.classes[key].width, _row_value_bytes(plan))


def sparse_class_traffic(plan: DistEmbeddingStrategy, key,
                         buckets: Sequence[Bucket], batch_local: int,
                         dp_input: bool, layout: PackedLayout):
  """:func:`dense_class_traffic` for a sparse-kind class packed as
  ``layout``: the same rows' side, against the class's packed block,
  ``phys_rows`` rows of ``phys_width`` lanes, optimizer lanes and all.

  The rows stay, whatever the bytes, where the travelled form does not
  exist: a row-sliced shard (an id outside a shard's window reads nothing
  there, and the owner alone knows its window's partial sum), a ragged
  value stream, a host-tier class (its device buffer is a cache, not the
  table), the deduplicated exchange (which already ships a row once)."""
  home = (plan.class_tiers.get(key) == "host"
          or wire.plan_dedup_exchange(plan)
          or any(b.rs or b.h < 0 for b in buckets))
  return wire.dense_class_side(
      plan.world_size, dp_input and not home,
      _padded_slot_rows(plan, key, buckets), plan.world_size * batch_local,
      layout.phys_rows, plan.classes[key].width, _row_value_bytes(plan),
      layout.phys_width)


def ragged_to_padded(ids: RaggedIds, max_hot: int) -> jax.Array:
  """RaggedIds -> dense [B, max_hot] with PAD_ID padding (for dp routing)."""
  b = ids.nrows
  lengths = ids.row_lengths()
  pos = jax.lax.broadcasted_iota(jnp.int32, (b, max_hot), 1)
  flat_idx = ids.row_splits[:-1, None] + pos
  valid = pos < lengths[:, None]
  gathered = jnp.take(ids.values, jnp.clip(flat_idx, 0, ids.values.shape[0] - 1),
                      mode="clip").astype(jnp.int32)
  return jnp.where(valid, gathered, PAD_ID)


def ragged_hotness(x) -> int:
  """Engine-internal hotness code of one input: ``>= 1`` = static hotness;
  ``-(V + 1)`` = ragged with value-stream capacity V (``values.shape[0]``;
  the +1 keeps a capacity-0 ragged input distinct from the static codes)."""
  if isinstance(x, RaggedIds):
    return -(int(x.values.shape[0]) + 1)
  x = jnp.asarray(x)
  return 1 if x.ndim == 1 else int(x.shape[1])


def _normalize_input(x):
  """-> [B, H] int32/int64 with PAD_ID for invalid entries, or RaggedIds.

  Ragged inputs flow through the engine as their VALUE STREAM (static
  capacity = ``values.shape[0]``) plus per-sample lengths — the TPU
  equivalent of the reference's uneven-split alltoall for true variable
  hotness (`dist_model_parallel.py:407-429`): comm and gather volume scale
  with the actual number of ids, not ``B x max_hotness``.

  int64 inputs stay int64 (the reference registers ``Tindices`` for both
  widths, `embedding_lookup_ops.cc:24-88`): a >2B-row table's GLOBAL ids
  only fit int64. The routing arithmetic localizes them (clip +
  ``row_start`` subtraction for row slices), after which every value is
  a per-rank slot-local id — bounded by the per-rank buffer's 2^31
  element limit — and ``_build_routing`` narrows the routed tensor to
  int32 for the wire."""
  if isinstance(x, RaggedIds):
    return x
  x = jnp.asarray(x)
  if x.ndim == 1:
    x = x[:, None]
  if x.ndim != 2:
    raise ValueError(f"Distributed inputs must be 1-D or 2-D, got {x.ndim}-D")
  return x.astype(jnp.int64 if x.dtype == jnp.int64 else jnp.int32)


def _require_wide_ids(plan, shard, ids):
  """Refuse int32 ids addressing a >int32 table (silent-fold guard).

  Without x64, ``jnp.asarray`` canonicalizes int64 inputs to int32 with
  wraparound BEFORE ``_normalize_input`` can see the wide dtype, so the
  only safe policy is: a table whose id space exceeds int32 must receive
  int64 ids, which requires ``jax.enable_x64``. Raising here (trace
  time) turns the silent wrong-rows failure into an actionable error."""
  vocab = plan.global_configs[shard.table_id].input_dim
  if vocab > 2 ** 31 - 1 and ids.dtype != jnp.int64:
    raise ValueError(
        f"table {shard.table_id} has input_dim={vocab:,} > int32 max but "
        f"its ids arrived as {ids.dtype} — ids above 2^31 would have "
        "wrapped already (JAX canonicalizes int64 to int32 when x64 is "
        "disabled). Enable x64 (jax.enable_x64() / jax_enable_x64) and "
        "pass int64 ids for this table.")


def _seg_ids(lengths: jax.Array, capacity: int) -> jax.Array:
  """Per value-stream position, its sample index (clamped to B-1 for the
  sentinel-padded tail). lengths: [B] -> [capacity] int32."""
  splits = jnp.concatenate(
      [jnp.zeros((1,), jnp.int32), jnp.cumsum(lengths).astype(jnp.int32)])
  pos = jnp.arange(capacity, dtype=jnp.int32)
  return jnp.clip(
      jnp.searchsorted(splits, pos, side="right").astype(jnp.int32) - 1,
      0, lengths.shape[0] - 1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DedupRouted:
  """Deduplicated exchange bundle for one padded sparse bucket.

  Built by :meth:`DistributedLookup.route_ids` when the plan sets
  ``dedup_exchange=True`` (sparse-kind classes, world > 1): per
  destination rank, the routing block's ids are sorted and uniqued
  dp-side (static capacity ``K = min(block occurrences, sentinel + 1)``
  — the value range bounds the distinct count, so the capacity can never
  overflow) and only the unique block crosses the wire. The receiving
  (mp) side gathers ONE fused row per unique id and returns ``[K, w]``
  rows; the dp side re-expands them through its locally-kept inverse map
  and runs the combiner there. On the backward, the expansion's
  transpose segment-sums duplicate ids' cotangents (f32) BEFORE the
  reverse exchange, so the grad wire shrinks identically.

  A deliberately NOT-a-tuple pytree: routed ragged buckets travel as
  plain ``(vals, lens)`` tuples and several consumers dispatch on
  ``isinstance(ids, tuple)``.

  ``overflow`` is only present (non-None) when the plan caps the unique
  capacity below its safe bound (``dedup_capacity``): this device's
  count of distinct ids that did NOT get their own slot, summed over the
  bucket's destination blocks — each one aliased onto the cap's last
  slot and gathered the wrong row. The guarded step psums it into the
  ``dedup_overflow`` metric; uncapped plans trace no counter at all (the
  pre-knob jaxpr is preserved byte-for-byte).
  """

  uniq: jax.Array        # [world_src, K] mp-side unique ids (post-exchange)
  inv: jax.Array         # [world_dst, n_b, B(, h)] dp-LOCAL inverse map
  uniq_local: jax.Array  # [world_dst, K] dp-LOCAL unique blocks (pre-exchange)
  overflow: Optional[jax.Array] = None  # scalar int32 iff dedup_capacity set

  def tree_flatten(self):
    return (self.uniq, self.inv, self.uniq_local, self.overflow), None

  @classmethod
  def tree_unflatten(cls, aux, children):
    del aux
    return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LocalIds:
  """One bucket of a sparse-kind class whose TABLES travel
  (:meth:`DistributedLookup.tables_travel`): the LOCAL samples' ids for
  every real slot of the bucket on any rank, where a routed bucket holds
  the global batch's ids for this rank's padded slots.

  ``ids [n, B_local(, h)]`` address each slot's rows as its owner does
  (row offset added, sentinel = the class's padded rows), so every
  combiner sees the sentinel pattern it sees in a routed bucket; where the
  rows are read from and written to the gathered block, slot ``i``'s ids
  are re-based to its owner's rows (:meth:`DistributedLookup._gathered_ids`).
  ``slots[i] = (owner rank, position in the owner's part of the bucket)``,
  static: how :meth:`DistributedLookup.assemble` keys a slot.

  A marker the data carries, not a flag: built once, by
  :meth:`DistributedLookup.route_ids`, and every later stage (the gather,
  the exchange it skips, the delta streams, the apply) follows the bucket
  it is handed. Like :class:`DedupRouted`, deliberately not a tuple."""

  ids: jax.Array
  slots: tuple

  def tree_flatten(self):
    return (self.ids,), self.slots

  @classmethod
  def tree_unflatten(cls, aux, children):
    return cls(children[0], aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseResiduals:
  """Forward-saved state for the fused sparse backward: post-exchange ids and
  the optimizer-state rows that rode along in the forward gather."""

  ids_all: Dict[tuple, jax.Array]  # bk -> [n_b, G, h]
  # Per-occurrence rows feeding the apply's aux extraction, in TWO layouts
  # distinguished by the trailing dim (aux_occ in apply_sparse dispatches
  # on it): [n_b, G, h, stride] RAW fused gather rows (1-hot and ragged
  # paths; empty [..., 0] slice when the rule has no aux state), or
  # [n_b, G, h, rpp*stride] window-MASKED physical rows (the multi-hot
  # narrow fast path — exactly one sub-row window nonzero, so summing the
  # windows' aux halves extracts the occurrence's state). Slicing aux
  # lanes here per occurrence instead would cost a ~25 ns/row relayout
  # right after the gather (measured on v5e).
  aux_rows: Dict[tuple, jax.Array]

  def tree_flatten(self):
    ik = sorted(self.ids_all)
    ak = sorted(self.aux_rows)
    return (tuple(self.ids_all[k] for k in ik)
            + tuple(self.aux_rows[k] for k in ak)), (tuple(ik), tuple(ak))

  @classmethod
  def tree_unflatten(cls, aux, children):
    ik, ak = aux
    return cls(ids_all=dict(zip(ik, children[:len(ik)])),
               aux_rows=dict(zip(ak, children[len(ik):])))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FusedChunks:
  """Round-major fused-exchange payload for one sparse bucket
  (``overlap='fused'``).

  ``blocks[k][c]`` is chunk ``c`` of the activations this rank gathered
  for ROUND ``k``'s destination, rank ``(i + k) % world`` — ``[n_b,
  rows_c, w]`` combined activations for raw/ragged buckets (``kind ==
  "raw"``), ``[rows_c, w]`` unique rows for dedup'd buckets (``kind ==
  "dedup"``). Keeping the rounds as SEPARATE pytree leaves instead of
  one dest-major array is the whole point of the fused schedule: each
  leaf's producer chain (slice ids -> gather -> combine) feeds exactly
  one :func:`wire.fused_block_send`, so the traced program has no
  monolithic pre-gather and round ``k``'s collective can overlap round
  ``k + 1``'s gather. The structure flows through
  ``jax.value_and_grad`` as a registered pytree: the cotangent comes
  back in the same per-round form (each reverse send is preceded only
  by ITS round's expand-transpose/segment-sum work), and
  :meth:`DistributedLookup._sparse_parts_by_class` reassembles it into
  the standard dest-major layout — pure data movement, so f32 stays
  bit-exact vs the monolithic and pipelined forms.

  Like :class:`DedupRouted`, deliberately NOT a tuple: routed ragged
  buckets travel as plain tuples and consumers dispatch on isinstance.
  """

  blocks: tuple  # blocks[k][c]: round k's c-th row chunk
  kind: str      # "raw" | "dedup"

  def tree_flatten(self):
    counts = tuple(len(blk) for blk in self.blocks)
    return (tuple(c for blk in self.blocks for c in blk),
            (counts, self.kind))

  @classmethod
  def tree_unflatten(cls, aux, children):
    counts, kind = aux
    it = iter(children)
    return cls(
        blocks=tuple(tuple(next(it) for _ in range(n)) for n in counts),
        kind=kind)


def _batch_of(inputs) -> int:
  x = inputs[0]
  return x.nrows if isinstance(x, RaggedIds) else x.shape[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _onehot_window_matmul(two_d: bool, vcap: int, ids_c, wins):
  """``one_hot(ids) @ wins`` with asymmetric forward/backward precision.

  Forward: bf16 one-hot (exact — values are 0/1) against the f32 window
  at HIGHEST precision, so the emitted activations are the exact table
  rows, matching gather semantics (this path replaces a gather; the
  reference's equivalent is the ``ConcatOneHotEmbedding`` gather,
  `embedding.py:155-180`).

  Backward: ``d_wins = one_hot^T @ d_z`` rebuilds the one-hot (cheaper
  than keeping the [G, vcap] block live as a residual) and contracts at
  the backend's default operand precision (`mxu_operand_dtype`): on TPU
  the cotangent operand is stored bf16 — the same one-bf16-pass product
  class a DEFAULT-precision f32 matmul uses — which halves the backward
  matmul passes vs inheriting the forward's HIGHEST. Unlike the forward
  (whose output must be bit-exact rows), the backward is a gradient
  accumulation in the TF32/AMP precision class the reference trains in.

  Being a ``custom_vjp``, this op supports reverse-mode AD only —
  ``jax.jvp``/``jacfwd`` over a model with dense-path tables raises.
  """
  out, _ = _onehot_window_matmul_fwd(two_d, vcap, ids_c, wins)
  return out


def _onehot_window_matmul_fwd(two_d, vcap, ids_c, wins):
  oh = jax.nn.one_hot(ids_c, vcap, dtype=jnp.bfloat16)
  eq = "ngv,nvw->ngw" if two_d else "nghv,nvw->ngw"
  z = jnp.einsum(eq, oh, wins, precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
  return z, (ids_c,)


def _onehot_window_matmul_bwd(two_d, vcap, res, d_z):
  (ids_c,) = res
  oh = jax.nn.one_hot(ids_c, vcap, dtype=jnp.bfloat16)
  eq = "ngv,ngw->nvw" if two_d else "nghv,ngw->nvw"
  cd = mxu_operand_dtype(jnp.float32)
  d_wins = jnp.einsum(eq, oh, d_z.astype(cd),
                      preferred_element_type=jnp.float32)
  d_ids = np.zeros(ids_c.shape, dtype=jax.dtypes.float0)
  return d_ids, d_wins


_onehot_window_matmul.defvjp(_onehot_window_matmul_fwd,
                             _onehot_window_matmul_bwd)


# Staged-id padding sentinel for the tiering searchsorted: larger than any
# physical row id (buffers are bounded by 2^31 ELEMENTS of >= 128 lanes, so
# phys rows stay far below int32 max), keeps padded staging slots sorting
# after every real id and matching nothing.
TIER_PAD_GRP = np.int32(2 ** 31 - 1)


@dataclasses.dataclass(frozen=True)
class TierSpec:
  """Device-side geometry of one host-tiered class (per rank).

  The compact device buffer is ``[(cache_grps + staging_grps) * ...phys]``:
  physical rows ``[0, cache_grps)`` hold the frequency-ranked resident hot
  set, rows ``[cache_grps, cache_grps + staging_grps)`` are the per-step
  staging region for the batch's cold rows. ``rows``/``rpp`` describe the
  LOGICAL vocabulary the routing tensors address."""

  name: str
  rows: int          # logical rows (sentinel base; = padded_rows(plan, key))
  rpp: int           # logical rows per physical row (layout.rows_per_phys)
  cache_grps: int    # resident physical rows per rank
  staging_grps: int  # persistent staging physical rows per rank

  @property
  def compact_rows(self) -> int:
    """Logical row capacity of the persistent compact buffer."""
    return (self.cache_grps + self.staging_grps) * self.rpp


def _translate_tier(ids: jax.Array, spec: TierSpec, sentinel: int,
                    resident_local: jax.Array, staged_local: jax.Array):
  """One routing tensor's logical ids -> compact ids + hit counters.

  ``resident_local``: [phys_rows] int32, cache physical row or -1;
  ``staged_local``: [S] sorted staged physical-row ids (TIER_PAD_GRP
  padding). Valid ids resolve hot -> cache slot, cold-staged -> staging
  slot; anything else (including the routing sentinel) maps to
  ``sentinel`` — an OOB id the gather zero-fills and the scatter drops."""
  valid = (ids >= 0) & (ids < spec.rows)
  safe = jnp.where(valid, ids, 0)
  grp = safe // spec.rpp
  sub = safe % spec.rpp
  cache_slot = jnp.take(resident_local, grp, axis=0, mode="clip")
  s = staged_local.shape[0]
  pos = jnp.clip(
      jnp.searchsorted(staged_local, grp).astype(jnp.int32), 0, max(s - 1, 0))
  staged_hit = (jnp.take(staged_local, pos, mode="clip") == grp) if s else \
      jnp.zeros(grp.shape, bool)
  slot = jnp.where(cache_slot >= 0, cache_slot,
                   jnp.where(staged_hit, spec.cache_grps + pos, -1))
  translated = jnp.where(valid & (slot >= 0), slot * spec.rpp + sub,
                         sentinel).astype(ids.dtype)
  hot = jnp.sum((valid & (cache_slot >= 0)).astype(jnp.int32))
  staged = jnp.sum((valid & (cache_slot < 0) & staged_hit).astype(jnp.int32))
  missed = jnp.sum((valid & (slot < 0)).astype(jnp.int32))
  total = jnp.sum(valid.astype(jnp.int32))
  return translated, jnp.stack([hot, staged, missed, total])


class DistributedLookup:
  """Functional lookup engine bound to one :class:`DistEmbeddingStrategy`.

  Call the methods inside ``shard_map`` (world > 1) with each class param
  passed as the local block ``[rows, width]`` (simple layout) or
  ``[phys_rows, phys_width]`` (fused layout), or anywhere when world == 1.
  Global class params are ``[world * rows, width]`` with rank blocks
  stacked along the row axis, sharded ``PartitionSpec(axis, None)``.

  Two layouts/paths:

  - **simple** (:meth:`forward`): fully differentiable (XLA autodiff
    produces dense table grads). Used by the flax module, tests, eval,
    and small models.
  - **fused** (:meth:`forward_fused` / :meth:`apply_sparse`): sparse-class
    params packed with optimizer-state rows (`ops/packed_table.py`); the
    performance training path — forward gathers carry the optimizer state,
    backward is one scatter-add per class.
  """

  def __init__(self, plan: DistEmbeddingStrategy, dp_input: bool = True,
               axis_name: str = "mp", apply_chunk: int = 1 << 22,
               dense_remat: bool = True):
    self.plan = plan
    self.dp_input = dp_input
    self.axis_name = axis_name
    # rematerialize the dense-class one-hot staging in the backward
    # (memory/time tradeoff); DE_TPU_DENSE_REMAT=0/1 overrides, any other
    # value keeps the constructor argument (same convention as
    # DE_TPU_PALLAS_APPLY)
    env = os.environ.get("DE_TPU_DENSE_REMAT", "")
    self.dense_remat = dense_remat if env not in ("0", "1") else env == "1"
    # occurrences per scatter chunk in apply_sparse (bounds the backward's
    # lane-expansion temporaries; exposed mainly so tests can exercise the
    # multi-chunk path at small sizes)
    self.apply_chunk = apply_chunk
    # trace-time caches keyed by (class key, per-slot hotness signature):
    # bucket enumeration is pure Python over every slot and would otherwise
    # rerun per bucket lookup on each trace (quadratic on big models)
    self._bucket_cache: Dict[tuple, List[Bucket]] = {}
    self._slot_map_cache: Dict[tuple, Dict[tuple, tuple]] = {}
    self._key_of_class = {class_param_name(*k): k for k in plan.class_keys}

  # ---- shapes ------------------------------------------------------------
  def param_shapes(self) -> Dict[str, tuple]:
    """Simple-layout class param shapes (flax module / checkpoint view).

    ``[world * padded_rows, width]``: rank r's fused block lives at rows
    ``[r * padded_rows, (r + 1) * padded_rows)``; sharding the row axis
    over the mesh (``PartitionSpec(axis, None)``) gives each device
    exactly its block."""
    shapes = {}
    for key in self.plan.class_keys:
      cp = self.plan.classes[key]
      shapes[class_param_name(*key)] = (
          self.plan.world_size * padded_rows(self.plan, key), cp.width)
    return shapes

  def fused_layouts(self, rule: SparseRule,
                    rows_overrides: Optional[Dict[str, int]] = None
                    ) -> Dict[str, PackedLayout]:
    """Per sparse-class :class:`PackedLayout` under ``rule`` (n_aux slots).

    ``rows_overrides`` (class name -> logical rows) substitutes a
    COMPACT row count for host-tiered classes: their device buffer holds
    only the hot cache + staging region (`tiering/`), so the 2^31-element
    indexing bound applies to the compact size, not the logical
    vocabulary — which is exactly what lets a table bigger than any
    device buffer train at all."""
    layouts = {}
    for key in self.plan.class_keys:
      cp = self.plan.classes[key]
      if cp.kind != "sparse":
        continue
      name = class_param_name(*key)
      rows = padded_rows(self.plan, key)
      if rows_overrides and name in rows_overrides:
        rows = rows_overrides[name]
      layout = PackedLayout(rows=rows, width=cp.width, n_aux=rule.n_aux)
      if layout.phys_rows * layout.phys_width > 2 ** 31:
        raise ValueError(
            f"class {name}: per-rank packed buffer "
            f"[{layout.phys_rows:,} x {layout.phys_width}] exceeds XLA's "
            f"2^31-element indexing under rule {rule.name!r} "
            f"(n_aux={rule.n_aux}). Shard finer (more workers, smaller "
            "row/column slice thresholds, or a smaller max_class_bytes)"
            + ("" if rows_overrides and name in rows_overrides else
               ", or host-offload the class (host_row_threshold)") + ".")
      layouts[name] = layout
    return layouts

  # ---- dp-side routing ---------------------------------------------------
  def _my_rank(self):
    if self.plan.world_size == 1:
      return 0
    return lax.axis_index(self.axis_name)

  # ---- the plan's wire, in one place -------------------------------------
  def _pipelined_wire(self) -> bool:
    """The plan asked for the chunked ppermute pipeline (inert at world
    1 — there is no wire to pipeline). ``overlap='fused'`` rides the
    same pipeline for every exchange that has no per-round gather to
    fuse (ids, ragged value streams, dense-class floats, the simple
    differentiable forward)."""
    return (wire.plan_overlap(self.plan) in ("pipelined", "fused")
            and self.plan.world_size > 1)

  def _fused_wire(self) -> bool:
    """The plan asked for the just-in-time fused schedule: sparse-class
    activations are gathered per ROUND immediately before each
    :func:`wire.fused_block_send` (:meth:`_z_sparse_fused_jit` /
    :meth:`_exchange_fused`) instead of in one monolithic pre-gather.
    Inert at world 1 — there is no wire to overlap, and the monolithic
    gather is already optimal."""
    return (wire.plan_overlap(self.plan) == "fused"
            and self.plan.world_size > 1)

  @jax.named_scope(scopes.EXCHANGE)
  def _wire_exchange_ids(self, x: jax.Array) -> jax.Array:
    """Integer payload exchange under the plan's overlap knob."""
    if self._pipelined_wire():
      return wire.pipelined_exchange_ids(
          x, self.axis_name, wire.plan_exchange_chunks(self.plan))
    return wire.exchange_ids(x, self.axis_name)

  def _wire_exchange_float(self, x: jax.Array) -> jax.Array:
    """Float payload exchange under the plan's wire_dtype AND overlap
    knobs (the reverse cotangent exchange mirrors whichever path is
    taken, through each path's custom_vjp)."""
    wd = wire.plan_wire_dtype(self.plan)
    if self._pipelined_wire():
      return wire.pipelined_float_exchange(
          x, self.axis_name, wd, wire.plan_exchange_chunks(self.plan))
    return wire.float_all_to_all(x, self.axis_name, wd)

  def _build_routing(self, key, bucket: Bucket,
                     inputs: Sequence[jax.Array]) -> jax.Array:
    """[world, n_b, B_local, h] routing tensor for one bucket (h == 1
    buckets drop the hotness axis: [world, n_b, B_local]).

    Squeezing the trailing unit axis matters: TPU tiling pads the minor
    dim to 128 lanes, so an int32 [..., B, 1] tensor occupies (and an
    all_to_all would move) 128x its logical bytes.

    Sentinel (= buffer row count) marks padded slots and PAD_ID entries; for
    dense-class slots ids stay slot-local *plus row_offset* exactly like
    sparse ones — the lookup subtracts the offset again inside its window."""
    cp = self.plan.classes[key]
    world = self.plan.world_size
    sentinel = padded_rows(self.plan, key)
    if bucket.h < 0:
      return self._build_ragged_routing(key, bucket, inputs)
    b = _batch_of(inputs)
    pad_shape = (b,) if bucket.h == 1 else (b, bucket.h)
    pad_block = jnp.full(pad_shape, sentinel, jnp.int32)
    per_dest = []
    for rank in range(world):
      idxs = bucket.slot_idx_per_rank[rank]
      per_slot = []
      for k in range(bucket.n_b):
        if k < len(idxs):
          per_slot.append(self._routed_slot(
              cp.slots_per_rank[rank][idxs[k]], bucket.h, inputs, sentinel))
        else:
          per_slot.append(pad_block)
      per_dest.append(jnp.stack(per_slot))
    return jnp.stack(per_dest)

  def _routed_slot(self, slot, h: int, inputs, sentinel: int) -> jax.Array:
    """One slot's ids of the local batch as its owner addresses them:
    ``[B]`` (h == 1) or ``[B, h]`` int32, row offset added, PAD and
    out-of-window entries at the sentinel."""
    ids = inputs[slot.input_id]
    if h == 1:
      ids = ids[:, 0]
    sh = slot.shard
    _require_wide_ids(self.plan, sh, ids)
    if sh.row_sliced:
      # row shard: serve only ids inside this shard's vocab window
      # [row_start, row_start + rows); other shards' rows and PAD go
      # to the sentinel and contribute zeros to the partial sum.
      # Out-of-vocab ids clamp to the last table row FIRST so
      # enabling row_slice (a sharding knob) cannot change numerics
      # vs the unsliced clamp policy. Arithmetic runs in the input
      # dtype (int64 for >2B-row tables); the result is slot-local
      # (< the per-rank buffer's 2^31 bound), so it narrows to
      # int32 for the routing tensor.
      vocab = self.plan.global_configs[sh.table_id].input_dim
      clamped = jnp.clip(ids, 0, vocab - 1)
      in_win = (ids >= 0) & (clamped >= sh.row_start) & (
          clamped < sh.row_start + sh.input_dim)
      routed = jnp.where(
          in_win, clamped - sh.row_start + slot.row_offset, sentinel)
    else:
      # OOV clamp to the last row — COUNTED, not silent: the plan's
      # oov policy governs it (oov_counts feeds the guarded step's
      # per-class metrics; oov='error' raises in route_ids)
      routed = jnp.where(ids < 0, sentinel,
                         jnp.clip(ids, 0, sh.input_dim - 1)
                         + slot.row_offset)
    return routed.astype(jnp.int32)

  # ---- small tables: which side travels ----------------------------------
  def tables_travel(self, key, hotness_of, batch_local: int,
                    layouts: Optional[Dict[str, PackedLayout]] = None
                    ) -> bool:
    """True where this class's TABLES cross the mesh (the class block
    all-gathered, looked up on the local samples) instead of its ids and
    rows: :func:`dense_class_traffic` says that is fewer bytes. Static per
    trace; never at world 1, never for model-parallel inputs.

    A sparse-kind class is asked about with the packed ``layouts`` it will
    be gathered and updated in (:func:`sparse_class_traffic` on its own):
    the travelled form reads fused rows and writes additive per-occurrence
    deltas, so it exists in the fused training step alone. Without
    ``layouts`` (the simple differentiable forward, a server's own gather,
    an ``exact=True`` or summed update, which is applied once a distinct
    row of the GLOBAL batch and so cannot be summed from ranks' parts) a
    sparse-kind class keeps its rows."""
    if self.plan.classes[key].kind == "dense":
      side = dense_class_traffic(
          self.plan, key, self._buckets(key, hotness_of), batch_local,
          self.dp_input)
    elif layouts is None:
      return False
    else:
      side = sparse_class_traffic(
          self.plan, key, self._buckets(key, hotness_of), batch_local,
          self.dp_input, layouts[class_param_name(*key)])
    return side[0] == "tables"

  @staticmethod
  def _real_slots(bucket: Bucket):
    """A bucket's real slots over ALL ranks, rank-major: ``[(rank,
    position in the rank's part of the bucket, slot index)]``. The order
    of a gathered class's slot axis."""
    return [(rank, pos, idx)
            for rank, idxs in enumerate(bucket.slot_idx_per_rank)
            for pos, idx in enumerate(idxs)]

  def _build_local_ids(self, key, bucket: Bucket,
                       inputs: Sequence[jax.Array]) -> jax.Array:
    """``[n, B_local(, h)]`` ids of the LOCAL samples for every real slot
    of the bucket on any rank (:meth:`_real_slots` order), addressed as
    their owners address them. No padded slot, nothing crosses: the
    tables come here (:meth:`tables_travel`)."""
    cp = self.plan.classes[key]
    sentinel = padded_rows(self.plan, key)
    return jnp.stack([
        self._routed_slot(cp.slots_per_rank[rank][idx], bucket.h, inputs,
                          sentinel)
        for rank, _, idx in self._real_slots(bucket)])

  def _gathered_layout(self, layout: PackedLayout) -> PackedLayout:
    """``layout`` of one rank's packed block -> the layout of all ranks'
    blocks as :func:`wire.gather_tables` stacks them: ``world *
    phys_rows`` physical rows, rank r's logical rows from ``r * phys_rows
    * rows_per_phys`` (a block's last physical row may be part filled;
    the next block starts on a physical row all the same)."""
    return dataclasses.replace(
        layout, rows=(self.plan.world_size * layout.phys_rows
                      * layout.rows_per_phys))

  def _gathered_ids(self, ids: jax.Array, slots: tuple, key,
                    layout: PackedLayout) -> jax.Array:
    """A :class:`LocalIds` bucket's ``ids [n, ...]``, each slot's re-based
    to its owner's rows of the gathered block (:meth:`_gathered_layout`);
    PAD ids at that layout's ``rows``, out of range as a sentinel is. An
    add of a constant a slot: no index into the gathered block is static."""
    block = layout.phys_rows * layout.rows_per_phys
    base = np.array([rank * block for rank, _ in slots], np.int32)
    base = base.reshape((-1,) + (1,) * (ids.ndim - 1))
    return jnp.where(ids < padded_rows(self.plan, key), ids + base,
                     self._gathered_layout(layout).rows)

  def _build_ragged_routing(self, key, bucket: Bucket, inputs):
    """Value-stream routing for a ragged bucket.

    Returns ``(vals [world, n_b, V], lens [world, n_b, B])``: per dest
    rank and slot, the sentinel-padded routed value stream and per-sample
    POSITIONAL lengths (row_lengths; they segment the value stream — the
    mean combiner's divisor is the VALID-id count, recomputed mp-side
    from the sentinel pattern). V is the bucket's exact static capacity:
    bucket membership is keyed on ``values.shape[0]``, so all member
    inputs share it."""
    cp = self.plan.classes[key]
    world = self.plan.world_size
    sentinel = padded_rows(self.plan, key)
    cap = -bucket.h - 1
    b = _batch_of(inputs)
    pad_vals = jnp.full((cap,), sentinel, jnp.int32)
    pad_lens = jnp.zeros((b,), jnp.int32)
    all_vals, all_lens = [], []
    for rank in range(world):
      idxs = bucket.slot_idx_per_rank[rank]
      vals_r, lens_r = [], []
      for k in range(bucket.n_b):
        if k < len(idxs):
          slot = cp.slots_per_rank[rank][idxs[k]]
          rg: RaggedIds = inputs[slot.input_id]
          v = rg.values.astype(
              jnp.int64 if rg.values.dtype == jnp.int64 else jnp.int32)
          total = rg.row_splits[-1].astype(jnp.int32)
          live = jnp.arange(cap, dtype=jnp.int32) < total
          sh = slot.shard
          _require_wide_ids(self.plan, sh, v)
          if sh.row_sliced:
            # row shard: serve only values inside this shard's vocab
            # window (same clamp-first policy as the padded routing so
            # enabling row_slice never changes numerics); out-of-window
            # values go to the sentinel and contribute zeros to this
            # shard's partial sum
            vocab = self.plan.global_configs[sh.table_id].input_dim
            clamped = jnp.clip(v, 0, vocab - 1)
            in_win = live & (v >= 0) & (clamped >= sh.row_start) & (
                clamped < sh.row_start + sh.input_dim)
            routed = jnp.where(
                in_win, clamped - sh.row_start + slot.row_offset, sentinel)
          else:
            routed = jnp.where(
                live & (v >= 0),
                jnp.clip(v, 0, sh.input_dim - 1) + slot.row_offset, sentinel)
          # localized values fit the per-rank buffer's 2^31 bound: narrow
          # int64 streams to the int32 wire format (same as the padded
          # routing)
          vals_r.append(routed.astype(jnp.int32))
          lens_r.append(rg.row_lengths().astype(jnp.int32))
        else:
          vals_r.append(pad_vals)
          lens_r.append(pad_lens)
      all_vals.append(jnp.stack(vals_r))
      all_lens.append(jnp.stack(lens_r))
    return jnp.stack(all_vals), jnp.stack(all_lens)

  @jax.named_scope(scopes.ROUTE)
  def route_ids(self, inputs: Sequence[jax.Array], hotness_of=None,
                layouts: Optional[Dict[str, PackedLayout]] = None
                ) -> Dict[tuple, jax.Array]:
    """dp->mp id exchange: per bucket, global-batch ids for my local tables.

    Returns ``bk -> [n_b, G, h]`` (bk = (class_key, h, vcap)); G = world * B.
    The all_to_all here is the reference's first Horovod exchange
    (`dist_model_parallel.py:414-423`) with splits made uniform by padding.
    A class whose tables travel (:meth:`tables_travel`) exchanges nothing
    here: its buckets hold ``[n, B(, h)]``, the local samples' ids for
    every real slot of any rank (:meth:`_build_local_ids`), a dense-kind
    class's as the array, a sparse-kind class's as :class:`LocalIds`.
    ``layouts``: the packed layouts the caller gathers and updates the
    sparse classes in; only with them can a sparse-kind class travel.

    Out-of-vocabulary ids: the routing clamps ``ids >= input_dim`` to the
    table's last row (reference numeric semantics) under the plan's
    ``oov`` POLICY — ``"clip"`` keeps the clamp but guarded train steps
    count it per class (:meth:`oov_counts`); ``"error"`` additionally
    raises here for concrete (non-traced) inputs, naming the offending
    id (jitted callers enforce the policy host-side from the metrics,
    ``resilience.guards.check_oov``).
    """
    plan = self.plan
    world = plan.world_size
    inputs = [_normalize_input(x) for x in inputs]
    if len(inputs) != plan.num_inputs:
      raise ValueError(f"Expected {plan.num_inputs} inputs, got {len(inputs)}")
    b = _batch_of(inputs)
    for x in inputs:
      nrows = x.nrows if isinstance(x, RaggedIds) else x.shape[0]
      if nrows != b:
        raise ValueError("All inputs need the same batch size "
                         f"(got {nrows} vs {b}).")
    if getattr(plan, "oov", "clip") == "error":
      self._oov_error_eager(inputs)
    if hotness_of is None:
      hotness_of = lambda i: ragged_hotness(inputs[i])  # noqa: E731

    ids_all: Dict[tuple, jax.Array] = {}
    for key in plan.class_keys:
      tables = self.tables_travel(key, hotness_of, b, layouts)
      for bucket in self._buckets(key, hotness_of):
        if tables:  # the local samples' ids stay here: [n, B(, h)]
          local = self._build_local_ids(key, bucket, inputs)
          if plan.classes[key].kind == "sparse":
            local = LocalIds(local, tuple(
                (rank, pos) for rank, pos, _ in self._real_slots(bucket)))
          ids_all[bucket_key(key, bucket.h, bucket.vcap, bucket.rs)] = local
          continue
        x = self._build_routing(key, bucket, inputs)  # [world, n_b, B(, h)]
        if bucket.h < 0:  # ragged: (vals [world,n_b,V], lens [world,n_b,B])
          vals, lens = x
          if world > 1:
            vals = self._wire_exchange_ids(vals)
            lens = self._wire_exchange_ids(lens)
          # -> (vals [n_b, world, V], lens [n_b, world, B]); the world
          # (source-rank) axis stays explicit because each source block
          # has its own CSR segmentation
          routed = (jnp.transpose(vals, (1, 0, 2)),
                    jnp.transpose(lens, (1, 0, 2)))
        elif world > 1 and self._dedup_class(key):
          routed = self._dedup_route(key, x)
        elif world > 1:
          y = self._wire_exchange_ids(x)
          routed = self._reshape_routed(y, bucket, world, b)
        else:
          routed = self._reshape_routed(x, bucket, world, b)
        ids_all[bucket_key(key, bucket.h, bucket.vcap, bucket.rs)] = routed
    return ids_all

  def _dedup_class(self, key) -> bool:
    """Dedup'd exchange applies: sparse-kind padded buckets only. Dense
    MXU classes have no row gather to dedup; ragged value streams (which
    never reach here — ``h < 0`` routes first) already scale with the
    true id count."""
    return (wire.plan_dedup_exchange(self.plan)
            and self.plan.classes[key].kind == "sparse")

  def _dedup_route(self, key, x) -> "DedupRouted":
    """Unique-then-exchange id routing for one padded bucket.

    ``x [world, n_b, B(, h)]`` is the dest-major routing tensor. Each
    destination block is sorted+uniqued dp-side to the static capacity
    ``K = min(occurrences, sentinel + 1)`` (the block's values live in
    ``[0, sentinel]``, so K can never overflow) and only the unique
    blocks cross the wire; the inverse maps stay local for the return
    expansion (:meth:`_exchange_dedup`).

    ``plan.dedup_capacity`` caps K below the safe bound: the wire
    shrinks further, but distinct ids past the cap ALIAS onto its last
    slot — so the capped path additionally counts the per-block distinct
    overflow into ``DedupRouted.overflow`` (the guarded step's psum'd
    ``dedup_overflow`` metric; the step builders refuse a capped plan
    without that counter path)."""
    world = self.plan.world_size
    sentinel = padded_rows(self.plan, key)
    m = int(np.prod(x.shape[1:]))
    cap = min(m, sentinel + 1)
    cap_knob = getattr(self.plan, "dedup_capacity", None)
    overflow = None
    if cap_knob is not None and cap_knob < cap:
      cap = cap_knob
      uniq_local, inv, n_distinct = jax.vmap(
          lambda ids: unique_ids_map(ids, sentinel, cap, with_count=True)
      )(x.reshape(world, m))
      overflow = jnp.sum(jnp.maximum(n_distinct - cap, 0))
    else:
      uniq_local, inv = jax.vmap(
          lambda ids: unique_ids_map(ids, sentinel, cap))(x.reshape(world, m))
    uniq = self._wire_exchange_ids(uniq_local)  # [world_src, K]
    return DedupRouted(uniq=uniq, inv=inv.reshape(x.shape),
                       uniq_local=uniq_local, overflow=overflow)

  @staticmethod
  def _reshape_routed(y, bucket, world, b):
    if bucket.h == 1:  # [world, n_b, B] -> [n_b, G]
      return jnp.transpose(y, (1, 0, 2)).reshape(bucket.n_b, world * b)
    return jnp.transpose(y, (1, 0, 2, 3)).reshape(  # -> [n_b, G, h]
        bucket.n_b, world * b, bucket.h)

  # ---- mp-side local lookups ---------------------------------------------
  def _combine(self, rows: jax.Array, ids_all: jax.Array, key,
               rs: bool = False) -> jax.Array:
    """Gathered rows -> [n_b, G, w] via the class combiner.

    ``ids_all`` is [n_b, G] for hotness-1 buckets (rows [n_b, G, w] pass
    through) or [n_b, G, h] for multi-hot (rows [n_b, G, h, w] reduce).

    For row-sliced buckets (``rs``) the mean division is deferred to
    :meth:`assemble`: the sentinel count here reflects only the ids this
    shard's vocab window served, not the sample's true hotness."""
    cp = self.plan.classes[key]
    sentinel = padded_rows(self.plan, key)
    if ids_all.ndim == 2 or ids_all.shape[-1] == 1:
      return rows if ids_all.ndim == 2 else rows[:, :, 0, :]
    if cp.combiner is None:
      # a sequence input: the h rows stay in order, side by side on the
      # width axis through the exchange; :meth:`assemble` gives them their
      # own axis back ([B, h, w])
      return rows.reshape(rows.shape[:2] + (-1,))
    summed = jnp.sum(rows, axis=2)
    if cp.combiner == "mean" and not rs:
      counts = jnp.sum(ids_all < sentinel, axis=2).astype(summed.dtype)
      summed = summed / jnp.maximum(counts, 1)[..., None]
    return summed

  def _z_sparse_simple(self, key, table_local: jax.Array,
                       ids_all: jax.Array, rs: bool = False) -> jax.Array:
    """Differentiable gather path on the simple [rows, w] buffer."""
    if isinstance(ids_all, DedupRouted):
      # one row per unique id; the combiner runs dp-side after the return
      # exchange re-expands (_exchange_dedup)
      return jnp.take(table_local, ids_all.uniq, axis=0, mode="fill",
                      fill_value=0)
    if isinstance(ids_all, tuple):  # ragged value stream
      vals, lens = ids_all
      rows = jnp.take(table_local, vals, axis=0, mode="fill", fill_value=0)
      return self._combine_ragged(rows, vals, lens, key, rs)
    rows = jnp.take(table_local, ids_all, axis=0, mode="fill", fill_value=0)
    return self._combine(rows, ids_all, key, rs)

  def _ragged_valid_counts(self, vals, lens, key):
    """Per-sample VALID-id counts [n_b*world, B]: entries a sample's length
    window covers minus the ones routed to the sentinel (invalid/negative
    ids) — the same divisor the padded path's ``sum(ids < sentinel)``
    computes, keeping ragged and padded mean semantics identical."""
    sentinel = padded_rows(self.plan, key)
    n_b, world, cap = vals.shape
    b = lens.shape[2]
    seg = jax.vmap(lambda l: _seg_ids(l, cap))(
        lens.reshape(n_b * world, b))
    valid = (vals < sentinel).astype(jnp.int32).reshape(n_b * world, cap)
    counts = jax.vmap(
        lambda v, s: jax.ops.segment_sum(v, s, num_segments=b))(valid, seg)
    return seg, counts

  def _combine_ragged(self, rows: jax.Array, vals: jax.Array,
                      lens: jax.Array, key, rs: bool = False) -> jax.Array:
    """Per-occurrence rows [n_b, world, V, w] + lens [n_b, world, B]
    -> [n_b, G, w] via segment-sum over each source block's CSR structure.

    Sentinel-padded tail positions gathered zero rows and clamp to the
    last segment, so they never perturb the sums; the mean combiner
    divides by the per-sample VALID-id counts. Row-sliced buckets
    (``rs``) defer the division to :meth:`assemble` — this shard's
    sentinel pattern counts only the ids its vocab window served, the
    same reasoning as the padded path's rs handling."""
    cp = self.plan.classes[key]
    n_b, world, cap, w = rows.shape
    b = lens.shape[2]
    seg, counts = self._ragged_valid_counts(vals, lens, key)
    summed = jax.vmap(
        lambda r, s: jax.ops.segment_sum(r, s, num_segments=b))(
            rows.reshape(n_b * world, cap, w), seg)
    summed = summed.reshape(n_b, world * b, w)
    if cp.combiner == "mean" and not rs:
      counts = counts.reshape(n_b, world * b).astype(summed.dtype)
      summed = summed / jnp.maximum(counts, 1)[..., None]
    return summed

  def _dense_offsets(self, key, bucket: Bucket) -> np.ndarray:
    cp = self.plan.classes[key]
    offs = np.zeros((self.plan.world_size, bucket.n_b), np.int32)
    for rank in range(self.plan.world_size):
      for k, idx in enumerate(bucket.slot_idx_per_rank[rank]):
        offs[rank, k] = cp.slots_per_rank[rank][idx].row_offset
    return offs

  @jax.named_scope(scopes.ONEHOT)
  def _z_dense(self, key, bucket: Bucket, table_local: jax.Array,
               ids_all: jax.Array, gathered: bool = False) -> jax.Array:
    """Small-vocab lookup as windowed one-hot MXU matmuls (zero row ops).

    The TPU equivalent of the reference's ``ConcatOneHotEmbedding``
    (`embedding.py:155-180`) — but applied automatically to every table
    under ``dense_row_threshold``. Per slot, a ``[vcap, w]`` window of the
    class buffer starting at the slot's row offset is contracted with the
    slot's one-hot ids; out-of-window / sentinel ids one-hot to zero.

    Two forms. ``table_local [rows, w]``, this rank's block, with
    ``ids_all [n_b, G(, h)]`` the global batch's ids for this rank's
    (padded) slots: SPMD uniform, window starts are data (indexed by
    ``lax.axis_index``), window size is the bucket's static ``vcap``. Or
    (``gathered``) ``[world * rows, w]``, every rank's block
    (:func:`wire.gather_tables`, where :meth:`tables_travel`), with
    ``[n, B_local(, h)]`` the local samples' ids for every real slot
    (:meth:`_real_slots`): each window is a static slice ``[owner * rows
    + offset : + vcap]``, and every rank runs the same tables.
    """
    two_d = ids_all.ndim == 2  # hotness-1 buckets drop the h axis
    n_b, g = ids_all.shape[:2]
    h = 1 if two_d else ids_all.shape[2]
    if self.plan.classes[key].combiner is None and h != 1:
      # a sequence input (same contract as the sparse path's _combine):
      # every position is a hotness-1 lookup of its own, the h rows of a
      # sample side by side on the width axis
      z = self._z_dense(key, bucket, table_local,
                        ids_all.reshape(n_b, g * h), gathered)
      return z.reshape(n_b, g, -1)
    vcap = bucket.vcap
    if gathered:
      slots = self.plan.classes[key].slots_per_rank
      rows = padded_rows(self.plan, key)
      # (first row of the owner's block, the slot's offset inside it)
      starts = [(rank * rows, slots[rank][idx].row_offset)
                for rank, _, idx in self._real_slots(bucket)]
      offs = jnp.asarray(np.array([o for _, o in starts], np.int32))  # [n]
    else:
      offs_const = jnp.asarray(self._dense_offsets(key, bucket))  # [world, n_b]
      offs = offs_const[self._my_rank()]  # [n_b]
    off_bcast = offs[:, None] if two_d else offs[:, None, None]
    ids_local = ids_all - off_bcast  # slot-local; OOB -> no one-hot

    def window(o):
      return lax.dynamic_slice(table_local, (o, 0), (vcap, table_local.shape[1]))

    if gathered:
      wins = jnp.stack([table_local[base + o:base + o + vcap]
                        for base, o in starts])
    else:
      wins = jax.vmap(window)(offs)  # [n_b, vcap, w]

    def z_of(ids_c):  # [n_b, C(, h)] -> [n_b, C, w]
      return _onehot_window_matmul(two_d, vcap, ids_c,
                                   wins).astype(table_local.dtype)

    if n_b * g * h * vcap <= _ONEHOT_ONESHOT_CELLS:
      z = z_of(ids_local)
    else:
      # chunk the batch axis so the one-hot staging stays bounded (the
      # custom VJP's only residual is ids_c, so the backward rebuilds each
      # chunk's one-hot rather than stacking it)
      chunk = max(1, _ONEHOT_ONESHOT_CELLS // max(1, n_b * h * vcap))
      nchunks = -(-g // chunk)
      pad = nchunks * chunk - g
      ids_c = ids_local
      if pad:
        pad_shape = (n_b, pad) if two_d else (n_b, pad, h)
        ids_c = jnp.concatenate(
            [ids_c, jnp.full(pad_shape, -1, ids_c.dtype)], axis=1)
      if two_d:
        xs = ids_c.reshape(n_b, nchunks, chunk).transpose(1, 0, 2)
      else:
        xs = ids_c.reshape(n_b, nchunks, chunk, h).transpose(1, 0, 2, 3)
      _, zs = lax.scan(lambda c, i: (c, z_of(i)), None, xs)
      z = zs.transpose(1, 0, 2, 3).reshape(n_b, nchunks * chunk, -1)[:, :g]
    cp = self.plan.classes[key]
    if cp.combiner == "mean" and h > 1:
      sentinel = padded_rows(self.plan, key)
      counts = jnp.sum(ids_all < sentinel, axis=2).astype(z.dtype)
      z = z / jnp.maximum(counts, 1)[..., None]
    return z

  def _z_sparse_fused(self, key, layout: PackedLayout, buf_local: jax.Array,
                      ids_all: jax.Array, rs: bool = False,
                      keep_rows: bool = False,
                      rows_at: Optional[jax.Array] = None):
    """Fused gather: returns (z, fused_rows) — optimizer state rides along.

    ``rows_at``: where ``ids_all``'s rows lie in ``buf_local``, if not at
    the ids themselves (a travelled class: ``buf_local`` is every rank's
    block, ``ids_all`` address each owner's own; the combine still reads
    the sentinel pattern from ``ids_all``).

    The combine sums the FULL fused stride (table + aux lanes together) and
    slices the table half at bag granularity; the per-occurrence residual is
    the raw gather output, whose aux lanes the apply slices off inside the
    delta computation (where it fuses with the rule math). Per-occurrence
    lane splits right after the gather measured ~25 ns/row on v5e — at bag
    granularity they are ~free."""
    w = layout.width
    if isinstance(ids_all, DedupRouted):
      # dedup'd exchange: gather each unique id's fused row ONCE — the
      # duplicate-heavy gather work and the return-exchange payload both
      # shrink to the unique count. No combine here: the dp side expands
      # via its inverse map and combines there (_exchange_dedup), so the
      # cotangent arriving in the backward is already per unique id.
      fused = gather_fused_chunked(layout, buf_local, ids_all.uniq)
      aux = fused if (layout.n_aux or keep_rows) else fused[..., w:]
      return fused[..., :w], aux
    if isinstance(ids_all, tuple):  # ragged value stream
      vals, lens = ids_all
      fused = gather_fused_chunked(layout, buf_local, vals)
      aux = fused if (layout.n_aux or keep_rows) else fused[..., w:]
      return self._combine_ragged(fused[..., :w], vals, lens, key, rs), aux
    sequence = self.plan.classes[key].combiner is None
    at = ids_all if rows_at is None else rows_at
    if (layout.rows_per_phys > 1 and layout.n_aux and ids_all.ndim == 3
        and ids_all.shape[-1] > 1 and not sequence):
      # Multi-hot narrow class: keep the whole pipeline at PHYSICAL width.
      # Gathered rows are window-MASKED per occurrence (zero outside the
      # occurrence's sub-row window — a fused VPU select), the bag combine
      # sums at 128 lanes, and the rpp windows fold ONCE PER BAG instead
      # of extracting once per occurrence (the extraction adds measured
      # ~14 ms/step on Tiny's traces). The residual is the masked
      # phys-width rows; the apply folds their aux halves per occurrence.
      masked = gather_fused_chunked(layout, buf_local, at,
                                    masked_phys=True)
      cp = self.plan.classes[key]
      bag = jnp.sum(masked, axis=2)  # [n_b, G, rpp*stride]
      rpp, stride = layout.rows_per_phys, layout.stride
      folded = jnp.sum(
          bag.reshape(bag.shape[:-1] + (rpp, stride)), axis=-2)
      z = folded[..., :w]
      if cp.combiner == "mean" and not rs:
        sentinel = padded_rows(self.plan, key)
        counts = jnp.sum(ids_all < sentinel, axis=2).astype(z.dtype)
        z = z / jnp.maximum(counts, 1)[..., None]
      return z, masked
    fused = gather_fused_chunked(layout, buf_local, at)  # [n_b,G,h,stride]
    if layout.n_aux == 0:
      # stride == width: no aux lanes ride along; keep_rows saves the full
      # rows anyway (the weight-decay delta needs the forward-time row)
      return self._combine(fused, ids_all, key, rs), (
          fused if keep_rows else fused[..., w:])
    if ids_all.ndim == 2 or ids_all.shape[-1] == 1 or sequence:
      # nothing is summed over the hotness: slice the table lanes first
      return self._combine(fused[..., :w], ids_all, key, rs), fused
    zf = self._combine(fused, ids_all, key, rs)  # [n_b, G, stride]
    return zf[..., :w], fused

  # ---- just-in-time fused schedule (overlap='fused') ---------------------
  def _fused_chunk_slices(self, rows: int):
    """Static ``(start, size)`` row chunks of one fused round block.

    The fused schedule chunks along gathered ROWS (rows gather whole —
    chunking the flattened payload like the pipelined wire would split
    rows across gathers), capped at the block's row count so no chunk
    is empty (an empty fp8 chunk has no amax). The tail chunk may be
    smaller; every rank computes the same static bounds, so each chunk
    is a legal uniform ppermute payload."""
    chunks = max(1, min(wire.plan_exchange_chunks(self.plan), rows))
    per = -(-rows // chunks)
    return [(s, min(per, rows - s)) for s in range(0, rows, per)]

  def _fused_gather(self, layout: PackedLayout, buf_local: jax.Array,
                    ids: jax.Array, masked_phys: bool = False) -> jax.Array:
    """One round block's gather, with the optional Pallas send-buffer
    kernel (``ops/pallas_exchange.py``, gated ``DE_TPU_PALLAS_EXCHANGE``
    + real TPU) fusing the row gather into the send staging for
    plain-row (rpp == 1) f32 classes. Off-TPU (and for every layout the
    kernel does not serve) this IS ``gather_fused_chunked`` — the XLA
    gather the monolithic path uses, so fused f32 numerics are the same
    gather's numerics."""
    if (not masked_phys and layout.rows_per_phys == 1
        and buf_local.dtype == jnp.float32):
      from ..ops import pallas_exchange
      if pallas_exchange._use_pallas_exchange():
        return pallas_exchange.gather_rows(layout, buf_local, ids)
    return gather_fused_chunked(layout, buf_local, ids,
                                masked_phys=masked_phys)

  def _fused_reassemble(self, per_round, kind: str) -> jax.Array:
    """Round-major blocks -> the standard dest-major layout.

    ``per_round[k]`` is round ``k``'s full block (chunks already
    concatenated): the payload for rank ``(i + k) % world``. Destination
    ``d`` therefore sits at round ``(d - i) % world``; one stack + take
    + (for raw payloads) moveaxis/reshape rebuilds exactly the layout
    the monolithic path produces — pure data movement, bit-exact. Used
    for the non-diff aux residuals (so :meth:`apply_sparse` and the
    delta streams see their usual layouts) and for the FusedChunks
    cotangent in :meth:`_sparse_parts_by_class`."""
    world = self.plan.world_size
    i = self._my_rank()
    stacked = jnp.stack(per_round)  # [world (round-major), ...]
    dst_pos = jnp.mod(jnp.arange(world, dtype=jnp.int32) - i, world)
    out = jnp.take(stacked, dst_pos, axis=0)
    if kind == "dedup":
      return out  # [world_req, K, ...]
    out = jnp.moveaxis(out, 0, 1)  # [n_b, world, rows, ...]
    return out.reshape((out.shape[0], world * out.shape[2])
                       + out.shape[3:])

  def _z_sparse_fused_jit(self, key, layout: PackedLayout,
                          buf_local: jax.Array, ids_all, rs: bool = False,
                          keep_rows: bool = False):
    """Just-in-time counterpart of :meth:`_z_sparse_fused`.

    Returns ``(FusedChunks, aux)``: instead of one monolithic gather
    over all routed ids, each ppermute round's payload is gathered (and
    combined / segment-summed) from ONLY the ids that round ships —
    round ``k`` slices destination ``(i + k) % world``'s id block out of
    the routing tensor (a dynamic slice: pure data movement), gathers
    its rows per chunk, and hands each chunk straight to
    :func:`wire.fused_block_send` in :meth:`_exchange_fused`. Gather and
    combine are elementwise per (slot, sample) over the hotness axis, so
    slicing ids BEFORE the gather+combine equals slicing the monolithic
    result after it — f32 is bit-exact vs both other schedules, branch
    by branch (same gather, same combine code). The aux residuals are
    reassembled to their standard dest-major layouts here (non-diff
    side, off the wire's critical path) so the apply/delta machinery is
    untouched.

    Ragged value streams gather per ROUND (each destination block's CSR
    segmentation is self-contained) and chunk the combined rows — the
    segment-sum cannot split mid-sample."""
    world = self.plan.world_size
    i = self._my_rank()
    w = layout.width
    if isinstance(ids_all, DedupRouted):
      # one row per unique id, gathered per round: round k gathers ONLY
      # rank (i + k) % world's unique block (the dp side expands and
      # combines after the return, _exchange_dedup semantics)
      kcap = ids_all.uniq.shape[1]
      blocks, aux_rounds = [], []
      for k in range(world):
        d = jnp.mod(i + k, world)
        uniq_d = lax.dynamic_index_in_dim(ids_all.uniq, d, axis=0,
                                          keepdims=False)  # [K]
        zc, ac = [], []
        for s0, sz in self._fused_chunk_slices(kcap):
          fused = self._fused_gather(layout, buf_local,
                                     lax.slice_in_dim(uniq_d, s0, s0 + sz))
          zc.append(fused[..., :w])
          ac.append(fused if (layout.n_aux or keep_rows)
                    else fused[..., w:])
        blocks.append(tuple(zc))
        aux_rounds.append(ac[0] if len(ac) == 1
                          else jnp.concatenate(ac, axis=0))
      aux = self._fused_reassemble(aux_rounds, "dedup")
      return FusedChunks(tuple(blocks), "dedup"), aux
    if isinstance(ids_all, tuple):  # ragged value stream
      vals, lens = ids_all  # [n_b, world, V], [n_b, world, B]
      b = lens.shape[2]
      blocks, aux_rounds = [], []
      for k in range(world):
        d = jnp.mod(i + k, world)
        vals_d = lax.dynamic_index_in_dim(vals, d, axis=1)  # [n_b, 1, V]
        lens_d = lax.dynamic_index_in_dim(lens, d, axis=1)
        fused = self._fused_gather(layout, buf_local, vals_d)
        zblk = self._combine_ragged(fused[..., :w], vals_d, lens_d, key,
                                    rs)  # [n_b, b, w]
        blocks.append(tuple(
            lax.slice_in_dim(zblk, s0, s0 + sz, axis=1)
            for s0, sz in self._fused_chunk_slices(b)))
        aux_rounds.append(fused if (layout.n_aux or keep_rows)
                          else fused[..., w:])
      aux = self._fused_reassemble(aux_rounds, "raw")  # [n_b, world, V, .]
      return FusedChunks(tuple(blocks), "raw"), aux
    # padded routing tensor [n_b, G(, h)], G = world * B dest-major
    bsz = ids_all.shape[1] // world
    masked = (layout.rows_per_phys > 1 and layout.n_aux
              and ids_all.ndim == 3 and ids_all.shape[-1] > 1)
    cp = self.plan.classes[key]
    sequence = cp.combiner is None
    masked = masked and not sequence
    sentinel = padded_rows(self.plan, key)
    blocks, aux_rounds = [], []
    for k in range(world):
      d = jnp.mod(i + k, world)
      ids_d = lax.dynamic_slice_in_dim(ids_all, d * bsz, bsz, axis=1)
      zc, ac = [], []
      for s0, sz in self._fused_chunk_slices(bsz):
        ids_c = lax.slice_in_dim(ids_d, s0, s0 + sz, axis=1)
        if masked:
          # multi-hot narrow class: same phys-width masked pipeline as
          # _z_sparse_fused, per chunk
          mrows = self._fused_gather(layout, buf_local, ids_c,
                                     masked_phys=True)
          bag = jnp.sum(mrows, axis=2)  # [n_b, sz, rpp*stride]
          rpp, stride = layout.rows_per_phys, layout.stride
          folded = jnp.sum(
              bag.reshape(bag.shape[:-1] + (rpp, stride)), axis=-2)
          z = folded[..., :w]
          if cp.combiner == "mean" and not rs:
            counts = jnp.sum(ids_c < sentinel, axis=2).astype(z.dtype)
            z = z / jnp.maximum(counts, 1)[..., None]
          zc.append(z)
          ac.append(mrows)
          continue
        fused = self._fused_gather(layout, buf_local, ids_c)
        if layout.n_aux == 0:
          zc.append(self._combine(fused, ids_c, key, rs))
          ac.append(fused if keep_rows else fused[..., w:])
        elif ids_c.ndim == 2 or ids_c.shape[-1] == 1 or sequence:
          zc.append(self._combine(fused[..., :w], ids_c, key, rs))
          ac.append(fused)
        else:
          zf = self._combine(fused, ids_c, key, rs)  # [n_b, sz, stride]
          zc.append(zf[..., :w])
          ac.append(fused)
      blocks.append(tuple(zc))
      aux_rounds.append(ac[0] if len(ac) == 1
                        else jnp.concatenate(ac, axis=1))
    aux = self._fused_reassemble(aux_rounds, "raw")  # [n_b, G(, h), .]
    return FusedChunks(tuple(blocks), "raw"), aux

  # ---- mp -> dp exchange + assembly --------------------------------------
  @jax.named_scope(scopes.EXCHANGE)
  def exchange(self, z: Dict[tuple, jax.Array], batch_local: int,
               ids_all: Optional[Dict[tuple, jax.Array]] = None
               ) -> Dict[tuple, jax.Array]:
    """mp->dp activation exchange (reference `dist_model_parallel.py:449-459`).

    z: bk -> [n_b, G, w]; returns bk -> [world_owner, n_b, B_local, w].
    Differentiable — autodiff inserts the reverse all_to_all, which is how
    the backward routes output cotangents to the owning shard without any of
    the reference's tape patching. Float payloads ride the plan's wire
    dtype (``parallel.wire``): under ``wire_dtype='bf16'`` activations
    are narrowed in flight and widened on arrival, and the reverse
    cotangent exchange narrows identically — compute on both sides stays
    at the payload's own (f32) precision.

    ``ids_all`` (the :meth:`route_ids` dict) is required when the plan
    dedups the exchange: buckets routed as :class:`DedupRouted` carry
    ``z[bk] = [world_src, K, w]`` unique rows and return through
    :meth:`_exchange_dedup` (exchange one row per unique id, expand via
    the dp-local inverse map, combine dp-side)."""
    world = self.plan.world_size
    received = {}
    for bk, zb in z.items():
      dr = ids_all.get(bk) if ids_all is not None else None
      if isinstance(zb, FusedChunks):
        received[bk] = self._exchange_fused(bk, zb, dr)
        continue
      if isinstance(dr, DedupRouted):
        received[bk] = self._exchange_dedup(bk, zb, dr)
        continue
      n_b = zb.shape[0]
      zb = zb.reshape(n_b, world, batch_local, -1).transpose(1, 0, 2, 3)
      if world > 1:
        zb = self._wire_exchange_float(zb)
      received[bk] = zb
    return received

  def _exchange_fused(self, bk, fz: FusedChunks,
                      dr: Optional["DedupRouted"]) -> jax.Array:
    """mp->dp return of a :class:`FusedChunks` payload, one send per
    just-gathered chunk (``overlap='fused'``).

    Round ``k``'s chunks each ride their own
    :func:`wire.fused_block_send` — the only ops between a chunk's
    gather (:meth:`_z_sparse_fused_jit`) and its send are that chunk's
    own encode, so XLA can launch round ``k``'s collective while round
    ``k + 1`` is still gathering. Received round ``k`` came FROM rank
    ``(i - k) % world``; one stack + take places the rounds
    source-major, reproducing the monolithic exchange bit-for-bit under
    f32 (pure data movement). Dedup'd buckets expand AND combine PER
    ROUND through the round's own inverse-map slice — the whole dp-side
    tail (expand, h-sum, mean divisor) runs inside the round body, so
    the stack + take reassembles COMBINED rows (``B`` per round, not
    ``B x h`` expanded occurrences), and on the backward each reverse
    send is preceded only by ITS round's combine transpose +
    segment-sum (the expand transpose) — the fused reverse-cotangent
    schedule. The combine is the one shared :meth:`_combine` (the same
    h-sum/mean-divisor code the monolithic/pipelined tail runs, per
    source block — combine never mixes source blocks, so running it
    round-by-round is the same math on the same values in the same
    order: bit-exact)."""
    world = self.plan.world_size
    wd = wire.plan_wire_dtype(self.plan)
    i = self._my_rank()
    src_pos = jnp.mod(i - jnp.arange(world, dtype=jnp.int32), world)
    if fz.kind == "dedup":
      w = fz.blocks[0][0].shape[-1]
      inv_shape = dr.inv.shape  # [world, n_b, B(, h)]
      m = int(np.prod(inv_shape[1:]))
      inv_flat = dr.inv.reshape(world, m)
      combined_rounds = []
      for k, blk in enumerate(fz.blocks):
        got = [wire.fused_block_send(c, self.axis_name, k, world, wd)
               for c in blk]
        ret_k = got[0] if len(got) == 1 else jnp.concatenate(got, axis=0)
        # round k's rows answer the unique block I sent to (i - k) %
        # world — expand through THAT destination's inverse map
        j = jnp.mod(i - k, world)
        inv_j = lax.dynamic_index_in_dim(inv_flat, j, axis=0,
                                         keepdims=False)
        rows_k = expand_unique_rows(ret_k, inv_j).reshape(
            inv_shape[1:] + (w,))  # [n_b, B(, h), w]
        if len(inv_shape) == 3:  # hotness-1: ids only carry the 2-D tag
          ids_k = inv_j.reshape(inv_shape[1:])
        else:  # rebuild ORIGINAL logical ids: the combiner's sentinels
          uniq_j = lax.dynamic_index_in_dim(dr.uniq_local, j, axis=0,
                                            keepdims=False)
          ids_k = jnp.take(uniq_j, inv_j, axis=0).reshape(inv_shape[1:])
        combined_rounds.append(
            self._combine(rows_k, ids_k, bk.class_key, bk.rs))
      return jnp.take(jnp.stack(combined_rounds), src_pos, axis=0)
    rounds = []
    for k, blk in enumerate(fz.blocks):
      got = [wire.fused_block_send(c, self.axis_name, k, world, wd)
             for c in blk]
      rounds.append(got[0] if len(got) == 1
                    else jnp.concatenate(got, axis=1))
    # [world (round-major), n_b, B, w] -> source-major [world, n_b, B, w]
    return jnp.take(jnp.stack(rounds), src_pos, axis=0)

  def _exchange_dedup(self, bk, z_u: jax.Array, dr: DedupRouted
                      ) -> jax.Array:
    """Dedup'd mp->dp return: ``z_u [world_src, K, w]`` unique rows ->
    ``[world_owner, n_b, B_local, w]`` combined activations.

    The exchange ships one row per unique id (narrowed to the wire dtype
    in flight); the dp side re-expands through its locally-kept inverse
    map and runs the combiner HERE — differentiably, so the backward's
    per-occurrence cotangents are segment-summed per unique id (f32, the
    transpose of :func:`expand_unique_rows`) before the reverse exchange
    narrows and ships them. Sentinel-padded unique slots gathered zero
    rows, so expansion reproduces the raw path's rows bit-for-bit; the
    h-axis sum and the mean divisor run over the same values in the same
    order as the raw path's mp-side combine, and row-sliced buckets
    defer their mean division to :meth:`assemble` exactly as before."""
    world = self.plan.world_size
    w = z_u.shape[-1]
    ret = self._wire_exchange_float(z_u)
    inv_shape = dr.inv.shape  # [world, n_b, B] | [world, n_b, B, h]
    m = int(np.prod(inv_shape[1:]))
    expanded = jax.vmap(expand_unique_rows)(ret, dr.inv.reshape(world, m))
    return self._dedup_combine_tail(bk, expanded.reshape(inv_shape + (w,)),
                                    dr)

  def _dedup_combine_tail(self, bk, expanded: jax.Array, dr: DedupRouted
                          ) -> jax.Array:
    """Shared dp-side combine of re-expanded dedup rows — the monolithic
    and pipelined dedup returns end here (the fused return runs the
    same expand + :meth:`_combine` sequence per round inside
    :meth:`_exchange_fused`, on h-fold-smaller reassembly copies).

    Runs the ONE shared combiner (:meth:`_combine` — the bit-exact
    parity contract rides its h-sum/mean-divisor code being the same
    code): fold [world, n_b] into the leading axis it expects. Hot-1
    buckets pass 2-D ids through untouched, so they skip the id
    reconstruction; multi-hot buckets rebuild the ORIGINAL logical ids
    (uniq_local[inv]) so the combiner sees exactly the sentinel
    pattern the raw path's mp-side combine saw."""
    key = bk.class_key
    world = self.plan.world_size
    inv_shape = dr.inv.shape
    m = int(np.prod(inv_shape[1:]))
    n_b = inv_shape[1]
    rows = expanded.reshape((world * n_b,) + expanded.shape[2:])
    if len(inv_shape) == 3:  # hotness-1: ids only carry the ndim==2 tag
      ids_f = dr.inv.reshape((world * n_b,) + inv_shape[2:])
    else:
      ids_f = jax.vmap(lambda u, iv: jnp.take(u, iv, axis=0))(
          dr.uniq_local, dr.inv.reshape(world, m)).reshape(
              (world * n_b,) + inv_shape[2:])
    out = self._combine(rows, ids_f, key, bk.rs)
    return out.reshape((world, n_b) + out.shape[1:])

  def _hot_sig(self, key, hotness_of) -> tuple:
    cp = self.plan.classes[key]
    return tuple(hotness_of(s.input_id)
                 for slots in cp.slots_per_rank for s in slots)

  def _buckets(self, key, hotness_of) -> List[Bucket]:
    """Cached :func:`class_buckets` (pure-Python, hotness-dependent)."""
    ck = (key, self._hot_sig(key, hotness_of))
    got = self._bucket_cache.get(ck)
    if got is None:
      got = class_buckets(self.plan, key, hotness_of)
      self._bucket_cache[ck] = got
    return got

  def _slot_bucket_map(self, hotness_of) -> Dict[tuple, tuple]:
    """(class_key, rank, slot_idx) -> (bucket key, index within bucket),
    built in one pass over each class's buckets (assemble would otherwise
    rescan every bucket per output piece — quadratic trace-time cost on
    thousand-table models)."""
    ck = tuple((key, self._hot_sig(key, hotness_of))
               for key in self.plan.class_keys)
    got = self._slot_map_cache.get(ck)
    if got is not None:
      return got
    out = {}
    for key in self.plan.class_keys:
      for bucket in self._buckets(key, hotness_of):
        bk = bucket_key(key, bucket.h, bucket.vcap, bucket.rs)
        for rank, idxs in enumerate(bucket.slot_idx_per_rank):
          for pos, slot_idx in enumerate(idxs):
            out[(key, rank, slot_idx)] = (bk, pos)
    self._slot_map_cache[ck] = out
    return out

  def assemble(self, received: Dict[tuple, jax.Array],
               hotness_of,
               mean_counts: Optional[Dict[int, jax.Array]] = None
               ) -> List[jax.Array]:
    """Per-input output reassembly: column-slice concat, row-slice sum.

    Replaces the reference's rev_global_input_ids shuffle + range-wise output
    concat (`dist_model_parallel.py:462-469`) with static piece indexing.
    Row-sliced pieces are full-width partial sums and ADD; their mean
    division happens here (differentiably) using ``mean_counts`` — per
    input id, the [B_local] count of valid (non-PAD) ids per sample (see
    :meth:`mean_counts`)."""
    plan = self.plan
    slot_map = self._slot_bucket_map(hotness_of)
    results = []
    for input_id, pieces in enumerate(plan.output_pieces):
      parts = []
      for p in pieces:
        bk, idx = slot_map[(p.class_key, p.rank, p.slot)]
        part = received[bk][p.rank, idx]
        if bk.combiner == "" and bk.h > 1:
          # a sequence input: [B, h * w] as it travelled -> [B, h, w]
          part = part.reshape(part.shape[0], bk.h, -1)
        parts.append(part)
      if pieces and pieces[0].row_sliced:
        out = parts[0] if len(parts) == 1 else sum(parts[1:], parts[0])
        combiner = plan.global_configs[
            plan.input_table_map[input_id]].combiner
        h_code = hotness_of(input_id)
        if combiner == "mean" and (h_code > 1 or h_code < 0):
          # h_code < 0 marks a ragged value stream (variable hotness);
          # hotness-1 inputs skip the division (mean of one element)
          if mean_counts is None or input_id not in mean_counts:
            raise ValueError(
                "mean combiner on a row-sliced table needs mean_counts "
                "(pass the forward inputs through DistributedLookup."
                "mean_counts)")
          counts = mean_counts[input_id].astype(out.dtype)
          out = out / jnp.maximum(counts, 1)[:, None]
        results.append(out)
      else:
        results.append(parts[0] if len(parts) == 1 else
                       jnp.concatenate(parts, axis=-1))
    return results

  @jax.named_scope(scopes.ROUTE)
  def mean_counts(self, inputs: Sequence[jax.Array]
                  ) -> Dict[int, jax.Array]:
    """Per-sample valid-id counts for mean x row-sliced inputs.

    Returns ``input_id -> [B_local]`` for every input that feeds a
    row-sliced mean-combined table (empty dict when none exist)."""
    plan = self.plan
    out = {}
    for input_id, pieces in enumerate(plan.output_pieces):
      if not (pieces and pieces[0].row_sliced):
        continue
      if plan.global_configs[plan.input_table_map[input_id]].combiner \
          != "mean":
        continue
      x = _normalize_input(inputs[input_id])
      if isinstance(x, RaggedIds):
        # per-sample VALID-id count over the value stream: live window
        # entries that are non-negative (same divisor the padded path's
        # sum(x >= 0) computes)
        cap = x.values.shape[0]
        lens = x.row_lengths().astype(jnp.int32)
        seg = _seg_ids(lens, cap)
        live = jnp.arange(cap, dtype=jnp.int32) < \
            x.row_splits[-1].astype(jnp.int32)
        valid = (live & (x.values >= 0)).astype(jnp.int32)
        out[input_id] = jax.ops.segment_sum(valid, seg,
                                            num_segments=x.nrows)
      else:
        out[input_id] = jnp.sum(x >= 0, axis=1)
    return out

  # ---- OOV observability -------------------------------------------------
  def _input_vocab(self, input_id: int) -> int:
    return self.plan.global_configs[
        self.plan.input_table_map[input_id]].input_dim

  @jax.named_scope(scopes.ROUTE)
  def oov_counts(self, inputs: Sequence[jax.Array]) -> Dict[str, jax.Array]:
    """Per-class out-of-vocabulary OCCURRENCE counts for one batch.

    An occurrence is OOV when its id ``>= input_dim`` of the table the
    input feeds (negative ids are hotness PADDING by the engine contract,
    not OOV). Counts are per width class — the granularity the train
    step's params and metrics use — with shared/sliced tables counted
    once per class. jit-safe (one compare+reduce per input, fused into
    the step); the guarded train step psums these across devices and
    surfaces them in its metrics dict, which is what makes the ``clip``
    policy observable instead of silent.

    Returns class name -> int32 scalar (this device's local batch
    shard)."""
    plan = self.plan
    out = {class_param_name(*k): jnp.zeros((), jnp.int32)
           for k in plan.class_keys}
    for input_id, pieces in enumerate(plan.output_pieces):
      x = _normalize_input(inputs[input_id])
      vocab = self._input_vocab(input_id)
      vals = x.values if isinstance(x, RaggedIds) else x
      if vocab > np.iinfo(np.dtype(vals.dtype)).max:
        continue  # ids of this dtype cannot reach the vocab bound
      if isinstance(x, RaggedIds):
        cap = vals.shape[0]
        live = jnp.arange(cap, dtype=jnp.int32) < \
            x.row_splits[-1].astype(jnp.int32)
        n = jnp.sum((live & (vals >= vocab)).astype(jnp.int32))
      else:
        n = jnp.sum((vals >= vocab).astype(jnp.int32))
      for ck in sorted({p.class_key for p in pieces}):
        name = class_param_name(*ck)
        out[name] = out[name] + n
    return out

  @jax.named_scope(scopes.ROUTE)
  def dedup_overflow_counts(self, ids_all: Dict[tuple, jax.Array]
                            ) -> Dict[str, jax.Array]:
    """Per-class dedup-capacity overflow counts for one routed batch.

    Only meaningful on plans with ``dedup_capacity`` set: each
    :class:`DedupRouted` bucket routed under a capped capacity carries
    the count of distinct ids that aliased past the cap
    (``DedupRouted.overflow``); this sums them per width class — the
    same granularity as :meth:`oov_counts` — so the guarded train step
    and the with-metrics eval step can psum and surface them. Classes
    with no capped buckets report 0. A nonzero count means those ids
    gathered (and in training, updated) the WRONG rows; the counter is
    what keeps the smaller cap observable instead of silent.

    Returns class name -> int32 scalar (this device's local counts)."""
    out = {class_param_name(*k): jnp.zeros((), jnp.int32)
           for k in self.plan.class_keys}
    for bk, ids in ids_all.items():
      if isinstance(ids, DedupRouted) and ids.overflow is not None:
        name = class_param_name(*bk.class_key)
        out[name] = out[name] + ids.overflow.astype(jnp.int32)
    return out

  def _oov_error_eager(self, inputs: Sequence[jax.Array]) -> None:
    """``oov='error'`` enforcement for CONCRETE inputs: raise naming the
    input, table, first offending id, and vocab. Traced inputs are
    skipped — under jit the policy is enforced host-side from the
    guarded step's metrics (``resilience.guards.check_oov``)."""
    from jax import core as jax_core
    for input_id, x in enumerate(inputs):
      vals = x.values if isinstance(x, RaggedIds) else x
      lens = x.row_splits if isinstance(x, RaggedIds) else None
      if isinstance(vals, jax_core.Tracer) or \
          isinstance(lens, jax_core.Tracer):
        continue
      vocab = self._input_vocab(input_id)
      arr = np.asarray(vals).reshape(-1)
      if lens is not None:
        arr = arr[:int(np.asarray(lens)[-1])]
      bad = arr[arr >= vocab]
      if bad.size:
        table = self.plan.input_table_map[input_id]
        raise ValueError(
            f"OOV policy 'error': input {input_id} carries {bad.size} id(s)"
            f" outside table {table}'s vocabulary [0, {vocab}) — first "
            f"offender {int(bad[0])}. The 'clip' policy would have "
            "silently mapped these to the last row; fix the id pipeline "
            "or construct the plan with oov='clip'.")

  # ---- composed forwards -------------------------------------------------
  def forward(self, class_params: Dict[str, jax.Array],
              inputs: Sequence[jax.Array],
              return_residuals: bool = False):
    """Differentiable distributed lookup on simple-layout params.

    Args:
      class_params: name -> [rows, width] local block (under shard_map
        with ``PartitionSpec(axis, None)``; with world == 1 the full
        array is the block).
      inputs: per global input, [B_local] or [B_local, H] int ids
        (PAD_ID entries ignored).
      return_residuals: also return the post-exchange id tensors
        (``bk -> [n_b, G, H]``) for an external sparse backward.

    Returns:
      Per global input, [B_local, table_width] activations; with
      ``return_residuals``, ``(outputs, ids_all)``.
    """
    inputs = [_normalize_input(x) for x in inputs]
    hotness_of = lambda i: ragged_hotness(inputs[i])  # noqa: E731
    b = _batch_of(inputs)
    counts = self.mean_counts(inputs)
    ids_all = self.route_ids(inputs, hotness_of)
    z = {}
    for bk, ids in ids_all.items():
      key = bk.class_key
      if self.plan.classes[key].kind != "dense":
        table_local = self._squeeze_local(
            class_params[class_param_name(*key)])
        with jax.named_scope(scopes.GATHER):
          z[bk] = self._z_sparse_simple(key, table_local, ids, bk.rs)
    with jax.named_scope(scopes.COMBINE):
      z_rows, z_here = self._lookup_dense(class_params, ids_all, b,
                                          hotness_of, remat=False)
      received = self.exchange({**z, **z_rows}, b, ids_all)
      received.update(z_here)
      outs = self.assemble(received, hotness_of, counts)
    if return_residuals:
      return outs, ids_all
    return outs

  def _find_bucket(self, key, h, vcap, hotness_of) -> Bucket:
    for bucket in self._buckets(key, hotness_of):
      if bucket.h == h and bucket.vcap == vcap:
        return bucket
    raise KeyError((key, h, vcap))

  @staticmethod
  def _squeeze_local(p: jax.Array) -> jax.Array:
    """Validate a local class-param block.

    Class params are 2-D ``[world * rows, width]`` sharded
    ``PartitionSpec(axis, None)``; inside shard_map the local block is
    ``[rows, width]`` and is used directly. (An earlier ``[world, rows,
    width]`` convention left a unit leading dim on the local block, which
    made XLA pick a non-default {2,0,1:T(1,128)} layout for the multi-GiB
    buffer and insert full layout-conversion copies every step.)
    """
    if p.ndim != 2:
      raise ValueError(
          f"class param must be 2-D [rows, width] (the local block of a "
          f"[world * rows, width] array), got {p.shape}")
    return p

  # ---- fused training path -----------------------------------------------
  @jax.named_scope(scopes.GATHER)
  def lookup_sparse_fused(self, fused_params: Dict[str, jax.Array],
                          layouts: Dict[str, PackedLayout],
                          ids_all: Dict[tuple, jax.Array],
                          keep_rows: bool = False):
    """Non-differentiable mp-side fused lookup for all sparse classes.

    Returns ``(z_sparse, residuals)``; run *outside* autodiff, then feed
    ``z_sparse`` into the differentiable tail (exchange/assemble/model) and
    its cotangent into :meth:`apply_sparse`. ``keep_rows`` saves the
    forward-time table rows in the residuals even for aux-free rules
    (needed by ``rule.weight_decay``; n_aux > 0 residuals carry them
    already).

    Under ``overlap='fused'`` (world > 1) each bucket's ``z`` is a
    :class:`FusedChunks` of per-round just-in-time gathers instead of
    one monolithic array (:meth:`_z_sparse_fused_jit`); the residual aux
    rows keep their standard layouts either way, so everything
    downstream of the cotangent reassembly is schedule-blind.

    A :class:`LocalIds` bucket (its class's tables travel) is read from
    the all-gathered packed block instead: ``z[bk] = [n, B_local, w]``,
    the local samples' combined rows for every real slot, which
    :meth:`finish_forward` hands to :meth:`assemble` without an exchange;
    its residual rows are the same fused rows, per local occurrence."""
    jit_gather = self._fused_wire()
    z: Dict[tuple, jax.Array] = {}
    aux: Dict[tuple, jax.Array] = {}
    gathered: Dict[str, jax.Array] = {}
    for bk, ids in ids_all.items():
      key = bk.class_key
      if self.plan.classes[key].kind != "sparse":
        continue
      name = class_param_name(*key)
      buf_local = self._squeeze_local(fused_params[name])
      if isinstance(ids, LocalIds):
        # the class's tables travel: every rank's packed block comes here
        # (once a class, however many buckets read it), and the local
        # samples gather their fused rows from it; z is [n, B_local, w]
        if name not in gathered:
          with jax.named_scope(scopes.EXCHANGE):
            gathered[name] = wire.gather_tables(buf_local, self.axis_name)
        z[bk], aux[bk] = self._z_sparse_fused(
            key, self._gathered_layout(layouts[name]), gathered[name], ids.ids, bk.rs, keep_rows=keep_rows,
            rows_at=self._gathered_ids(ids.ids, ids.slots, key,
                                       layouts[name]))
        continue
      if jit_gather:
        zb, auxb = self._z_sparse_fused_jit(key, layouts[name], buf_local,
                                            ids, bk.rs,
                                            keep_rows=keep_rows)
      else:
        zb, auxb = self._z_sparse_fused(key, layouts[name], buf_local, ids,
                                        bk.rs, keep_rows=keep_rows)
      z[bk] = zb
      aux[bk] = auxb
    return z, SparseResiduals(ids_all=dict(ids_all), aux_rows=aux)

  @jax.named_scope(scopes.COMBINE)
  def finish_forward(self, z_sparse: Dict[tuple, jax.Array],
                     dense_params: Dict[str, jax.Array],
                     ids_all: Dict[tuple, jax.Array],
                     batch_local: int, hotness_of,
                     mean_counts: Optional[Dict[int, jax.Array]] = None
                     ) -> List[jax.Array]:
    """Differentiable tail: dense-class lookups + exchange + assembly.

    Differentiable w.r.t. ``z_sparse`` (cotangents feed
    :meth:`apply_sparse`) and ``dense_params`` (dense autodiff grads for the
    MXU one-hot tables). ``mean_counts`` (from :meth:`mean_counts`) is
    required when a row-sliced table uses the mean combiner — the division
    happens in this differentiable tail, so its cotangent reaches
    :meth:`apply_sparse` pre-divided."""
    z_rows, z_here = self._lookup_dense(dense_params, ids_all, batch_local,
                                        hotness_of, self.dense_remat)
    to_cross = {}
    for bk, zb in z_sparse.items():
      local = ids_all.get(bk)
      if isinstance(local, LocalIds):  # looked up here: nothing to cross
        z_here[bk] = {slot: zb[i] for i, slot in enumerate(local.slots)}
      else:
        to_cross[bk] = zb
    received = self.exchange({**to_cross, **z_rows}, batch_local, ids_all)
    received.update(z_here)
    return self.assemble(received, hotness_of, mean_counts)

  def _lookup_dense(self, dense_params, ids_all, batch_local: int,
                    hotness_of, remat: bool):
    """Every dense-kind bucket's one-hot lookup -> ``(z_rows, z_here)``.

    ``z_rows[bk] = [n_b, G, w]``: the global batch's rows for this rank's
    slots, still to cross (:meth:`exchange`). ``z_here[bk][rank, pos] =
    [B_local, w]``: where the class's tables travel instead
    (:meth:`tables_travel`), the local samples' rows for every real slot,
    keyed as :meth:`assemble` reads an exchanged ``[world, n_b, B_local,
    w]`` block, without the padded slots. A travelling class is gathered
    once however many buckets read it, OUTSIDE the rematerialised lookup:
    inside, the backward would fly it a second time."""
    z_rows, z_here, gathered = {}, {}, {}
    for bk, ids in ids_all.items():
      key = bk.class_key
      if self.plan.classes[key].kind != "dense":
        continue
      table = self._squeeze_local(dense_params[class_param_name(*key)])
      bucket = self._find_bucket(key, bk.h, bk.vcap, hotness_of)
      travels = self.tables_travel(key, hotness_of, batch_local)
      if travels:
        if key not in gathered:
          with jax.named_scope(scopes.EXCHANGE):
            gathered[key] = wire.gather_tables(table, self.axis_name)
        table = gathered[key]
      z_fn = lambda t, i, key=key, bucket=bucket, travels=travels: \
          self._z_dense(key, bucket, t, i, travels)  # noqa: E731
      if remat:
        # don't keep the [G, vcap] one-hot staging alive for the backward —
        # rebuilding it is a few VPU compares, and it saves ~1.5 GiB live
        # at batch 64k (needed when the chip is near its HBM limit)
        z_fn = jax.checkpoint(z_fn)
      zb = z_fn(table, ids)
      if travels:
        z_here[bk] = {(rank, pos): zb[i] for i, (rank, pos, _)
                      in enumerate(self._real_slots(bucket))}
      else:
        z_rows[bk] = zb
    return z_rows, z_here

  @staticmethod
  def _aux_occ(aux, layout, rule):
    """Residual rows -> per-occurrence aux rows [-1, n_aux, w].

    Residuals come in two layouts: stride-width fused rows (1-hot /
    ragged paths) or window-MASKED phys-width rows (multi-hot narrow
    path) — for the latter, exactly one sub-row window is nonzero, so
    summing the rpp windows' aux halves extracts it."""
    if aux is None or not rule.n_aux:
      return None
    w, stride, rpp = layout.width, layout.stride, layout.rows_per_phys
    last = aux.shape[-1]
    flat = aux.reshape(-1, last)
    if last == stride:
      lanes = flat[:, w:]
    else:  # masked phys rows [.., rpp*stride]
      lanes = None
      for s in range(rpp):
        part = flat[:, s * stride + w:(s + 1) * stride]
        lanes = part if lanes is None else lanes + part
    return lanes.reshape(-1, rule.n_aux, w)

  @staticmethod
  def _decayed(g, res, layout, rule):
    """Touched-rows l2: add ``2λ * row`` (forward-time row from the
    residuals — same layouts as _aux_occ) to the occurrence cotangent."""
    if not rule.weight_decay or res is None:
      return g
    w, stride, rpp = layout.width, layout.stride, layout.rows_per_phys
    last = res.shape[-1]
    flat = res.reshape(-1, last)
    if last == stride:
      row = flat[:, :w]
    else:  # masked phys rows: exactly one window nonzero per occurrence
      row = None
      for s in range(rpp):
        part = flat[:, s * stride:s * stride + w]
        row = part if row is None else row + part
    return g + (2.0 * rule.weight_decay) * row.reshape(g.shape)

  def _sparse_parts_by_class(self, d_z, residuals, rule, layouts):
    """Group per-bucket cotangents into per-class ``(ids, dz, aux, h)``
    parts: ragged buckets expand to per-occurrence rows (h=0 marks them),
    mean combiners divide by the forward's valid counts. Shared by
    :meth:`apply_sparse` and :meth:`sparse_delta_streams`.

    Returns ``(by_class, travelled)``. ``travelled``: the classes whose
    buckets are :class:`LocalIds` (all of a class's are, or none). Their
    parts hold the LOCAL samples' occurrences, ids re-based to the
    gathered block (:meth:`_gathered_ids`): what :meth:`_travelled_delta`
    scatters."""
    plan = self.plan
    by_class: Dict[str, list] = {}
    travelled = set()
    for bk, dzb in d_z.items():
      key, h = bk.class_key, bk.h
      if plan.classes[key].kind != "sparse":
        continue
      if isinstance(dzb, FusedChunks):
        # fused schedule: the cotangent arrives per (round, chunk) — the
        # reverse sends already happened round by round inside the
        # backward; reassembling to the standard dest-major layout here
        # is pure data movement, so everything below is schedule-blind
        dzb = self._fused_reassemble(
            [blk[0] if len(blk) == 1 else jnp.concatenate(
                blk, axis=0 if dzb.kind == "dedup" else 1)
             for blk in dzb.blocks], dzb.kind)
      if os.environ.get("DE_TPU_COTANGENT_PIN", "0") == "1":
        # EXPERIMENT (default off — measured NEUTRAL-to-negative on Tiny:
        # 162 -> 167 ms): pinning the per-sample cotangent row-major here
        # does not stick — XLA re-transposes it back to batch-minor for
        # the h-broadcast materialization downstream (trace round 5)
        from ..ops.pallas_layout import row_major
        dzb = row_major(dzb)
      cp = plan.classes[key]
      name = class_param_name(*key)
      ids = residuals.ids_all[bk]  # [n_b, G, h] | ragged | DedupRouted
      placed = lambda x: x  # noqa: E731
      if isinstance(ids, LocalIds):  # [n, B_local, h], its owners' rows
        travelled.add(name)
        placed = functools.partial(self._gathered_ids, slots=ids.slots,
                                   key=key, layout=layouts[name])
        ids = ids.ids
      sentinel = padded_rows(plan, key)
      aux = (residuals.aux_rows[bk]
             if (rule.n_aux or rule.weight_decay) else None)
      if isinstance(ids, DedupRouted):
        # dedup'd bucket: the cotangent arrives per UNIQUE id — duplicate
        # occurrences' cotangents were segment-summed by the dp-side
        # expansion's transpose (before the reverse exchange), and the
        # mean division lives in the differentiable dp-side combine — so
        # parts are pre-expanded (h=0: no hotness broadcast, no divisor).
        # rule.delta consequently applies ONCE per unique id per source
        # block (the exact=True-style dedup semantics, restricted to one
        # exchange block; exact=True still merges across blocks).
        by_class.setdefault(name, []).append(
            (ids.uniq.reshape(-1), dzb.reshape(-1, cp.width), aux, 0))
        continue
      if h < 0:
        # ragged: expand the per-sample cotangent to per-occurrence rows
        # (h=0 marks pre-expanded parts downstream: no hotness broadcast)
        vals, lens = ids
        n_b, world, cap = vals.shape
        b = lens.shape[2]
        w = cp.width
        seg, counts = self._ragged_valid_counts(vals, lens, key)
        dz_blocks = dzb.reshape(n_b * world, b, w)
        g_occ = jax.vmap(lambda d, s: jnp.take(d, s, axis=0))(
            dz_blocks, seg)  # [n_b*world, V, w]
        if cp.combiner == "mean" and not bk.rs:
          # mirror the forward's valid-count divisor exactly (row-sliced
          # buckets: the division lives in the differentiable assemble,
          # so d_z arrives pre-divided — same as the padded path)
          cnt = jax.vmap(lambda c, s: jnp.take(c, s))(
              counts, seg).astype(g_occ.dtype)
          g_occ = g_occ / jnp.maximum(cnt, 1)[..., None]
        by_class.setdefault(name, []).append(
            (vals.reshape(-1), g_occ.reshape(-1, w), aux, 0))
        continue
      if cp.combiner is None and h > 1:
        # a sequence input: the cotangent [n_b, G, h * w] already holds one
        # row per occurrence (h=0 marks pre-expanded parts)
        by_class.setdefault(name, []).append(
            (placed(ids).reshape(-1), dzb.reshape(-1, cp.width), aux, 0))
        continue
      if cp.combiner == "mean" and h > 1 and not bk.rs:
        # row-sliced buckets skip this: their mean division lives in the
        # differentiable assemble, so d_z arrives pre-divided
        counts = jnp.sum(ids < sentinel, axis=2).astype(dzb.dtype)
        dzb = dzb / jnp.maximum(counts, 1)[..., None]
      by_class.setdefault(name, []).append((placed(ids), dzb, aux, h))
    return by_class, travelled

  def _pallas_delta_rows(self, layout, ids, dzb, aux, h, rule, step):
    """Gate + dispatch for the Pallas delta-build kernel
    (`ops/pallas_delta.py`): returns the pre-expanded ``[n, phys]`` update
    rows, or None to take the XLA chain. TPU-only; needs the rule's
    ``delta_lanes`` twin, a 128-lane physical layout, f32, and no
    weight_decay (the decay path needs forward-row extraction the kernel
    does not carry)."""
    if not _use_pallas_delta():
      return None
    if (rule.delta_lanes is None or rule.linear_scale is not None
        or rule.weight_decay):
      return None
    if layout.phys_width != 128 or dzb.dtype != jnp.float32:
      return None
    if rule.n_aux and (aux is None or aux.dtype != jnp.float32):
      return None
    hh = max(1, int(h))  # h == 0: ragged parts arrive pre-expanded per occ
    n = int(np.prod(ids.shape))
    if n == 0 or n % hh:
      return None
    k = n // hh
    if k % 8:  # no even VMEM blocking
      return None
    if aux is not None and aux.shape[-1] not in (layout.stride,
                                                 layout.phys_width):
      return None
    from ..ops.pallas_delta import build_delta_rows, pick_block
    if not pick_block(k, hh, aux.shape[-1] if aux is not None else 0):
      return None  # no VMEM-feasible block (e.g. extreme hotness)
    _, sub, _ = _grp_sub(layout, ids.reshape(-1))
    aux_flat = (aux.reshape(n, aux.shape[-1])
                if aux is not None and rule.n_aux else None)
    return build_delta_rows(layout, rule, dzb.reshape(k, -1), sub,
                            aux_flat, hh, step)

  def _stream_of_parts(self, layout, parts, rule, step):
    """Concatenate a class's parts into one occurrence stream.

    Returns ``(ids_cat [n], rows_cat [n, w|stride])`` — raw (decayed)
    cotangent rows for scale-only rules (the scatter backend applies the
    scalar), fused ``rule.delta`` rows otherwise. Shared by the one-shot
    fast path and the deferred micro-batch path so their numerics are the
    same code."""
    w = layout.width
    scale_only = rule.linear_scale is not None
    all_ids, all_rows = [], []
    # all-or-nothing per class: mixing pre-expanded [n, phys] kernel rows
    # with stride-width XLA rows would break the concat below
    built_all = [self._pallas_delta_rows(layout, ids, dzb, aux, h, rule,
                                         step)
                 for ids, dzb, aux, h in parts]
    if all(b is not None for b in built_all):
      return (jnp.concatenate([ids.reshape(-1) for ids, _, _, _ in parts])
              if len(parts) > 1 else parts[0][0].reshape(-1),
              jnp.concatenate(built_all) if len(parts) > 1 else built_all[0])
    for ids, dzb, aux, h in parts:
      n = int(np.prod(ids.shape))
      g = dzb.reshape(-1, w)
      if h > 1:
        g = jnp.broadcast_to(g[:, None, :], (n // h, h, w)).reshape(n, w)
      aux_r = self._aux_occ(aux, layout, rule)
      g = self._decayed(g, aux, layout, rule)
      all_ids.append(ids.reshape(-1))
      all_rows.append(g if scale_only else rule.delta(g, aux_r, step))
    ids_cat = all_ids[0] if len(all_ids) == 1 else jnp.concatenate(all_ids)
    rows_cat = (all_rows[0] if len(all_rows) == 1
                else jnp.concatenate(all_rows))
    return ids_cat, rows_cat

  @jax.named_scope(scopes.APPLY)
  def sparse_delta_streams(self, layouts: Dict[str, PackedLayout],
                           d_z: Dict[tuple, jax.Array],
                           residuals: SparseResiduals,
                           rule: SparseRule, step: jax.Array):
    """Per-class deferred update streams ``name -> (ids, rows)``.

    The micro-batch accumulation path (``make_sparse_train_step(...,
    micro_batches=n)``) calls this once per micro-batch inside its scan:
    deltas are computed from the micro-batch's OWN forward-gathered
    optimizer-state rows (the fused buffers are untouched until the final
    :meth:`apply_sparse_streams`), so concatenating the streams and
    scattering once reproduces the one-shot step's numerics exactly —
    the memory win is that the per-occurrence gather/extract/backward
    temporaries only ever exist for one micro-batch at a time."""
    by_class, travelled = self._sparse_parts_by_class(d_z, residuals, rule,
                                                      layouts)
    streams = {}
    for name, parts in by_class.items():
      layout = layouts[name]
      if name in travelled:
        # the owner's summed block delta as a stream of its own: one
        # "occurrence" a physical row, each row already whole, so the
        # guard's gate, the micro-batch stack and the one scatter of
        # :meth:`apply_sparse_streams` take it as they take any other
        streams[name] = (
            jnp.arange(layout.phys_rows, dtype=jnp.int32)
            * layout.rows_per_phys,
            self._travelled_delta(layout, parts, rule, step))
      else:
        streams[name] = self._stream_of_parts(layout, parts, rule, step)
    return streams

  def _travelled_delta(self, layout: PackedLayout, parts, rule: SparseRule,
                       step: jax.Array) -> jax.Array:
    """The update of THIS rank's block of a class whose tables travelled:
    ``[phys_rows, phys_width]``, to be added (a scale-only rule's times
    ``rule.linear_scale(step)`` first, as its stream's rows are).

    ``parts`` hold the local samples' occurrences for every table of the
    class on any rank. Their per-occurrence deltas are what the owner
    would have built from the same cotangents and the same forward-saved
    state (:meth:`_stream_of_parts`); they are scatter-added into zeros
    of the gathered shape (as many occurrences as rows, or more: XLA's
    fast scatter regime by :meth:`_kernel_regime`'s own ratio), and
    :func:`wire.scatter_tables` lands on every owner the sum over ranks
    of its own rows. Deltas are additive, so every per-occurrence rule
    keeps its result up to float32 summation order."""
    glayout = self._gathered_layout(layout)
    ids_cat, rows_cat = self._stream_of_parts(glayout, parts, rule, step)
    if rule.linear_scale is None:  # as apply_sparse_streams: keep the delta
      # computation out of the scatter's update loop
      ids_cat, rows_cat = lax.optimization_barrier((ids_cat, rows_cat))
    summed = scatter_add_fused(
        glayout, jnp.zeros(glayout.shape, rows_cat.dtype), ids_cat, rows_cat)
    with jax.named_scope(scopes.EXCHANGE):
      return wire.scatter_tables(summed, self.axis_name)

  @staticmethod
  def _kernel_regime(n_ids: int, layout: PackedLayout) -> bool:
    """The static scatter-regime rule: below ~0.15 ids a physical row XLA's
    scatter never reaches its fast path and the Pallas RMW kernel wins
    (`packed_table.scatter_add_fused`, docs/BENCHMARKS.md)."""
    return n_ids / max(1, layout.phys_rows) < 0.15

  def _apply_head_starts(self, name: str, layout: PackedLayout,
                         n_ids: int) -> Optional[jax.Array]:
    """This rank's ``[K]`` starts (physical rows of the class block) of the
    blocks the apply kernel keeps resident in VMEM: the first
    ``pallas_apply.HEAD_ROWS`` physical rows of every table of the class,
    where a frequency-sorted vocabulary (rank = id) has its hot rows.

    ``None`` where the stream would not take the kernel (the static regime
    rule of :meth:`apply_sparse_streams`; a physical row wider than the 128
    lanes the kernel serves), where ``layout`` is not the
    plan's own (a host-tiered class's compact buffer: its ids are cache
    slots, and where a table starts says nothing about them), or where no
    block can be placed. A row-sliced shard that begins past row 0 of its
    table holds no hot rows at its start and gets no block. The starts are
    data indexed by the rank, as :meth:`_dense_offsets` are: under
    ``shard_map`` every rank holds other tables."""
    from ..ops.pallas_apply import HEAD_PAD, HEAD_ROWS, head_block_starts
    key = self._key_of_class.get(name)
    if key is None or not self._kernel_regime(n_ids, layout) \
        or layout.phys_width != LANES \
        or layout.rows != padded_rows(self.plan, key):
      return None
    cp = self.plan.classes[key]
    rpp = layout.rows_per_phys
    per_rank = []
    for shards, offs in zip(cp.shards_per_rank, cp.row_offsets_per_rank):
      heads = []
      for sh, off in zip(shards, offs):
        if sh.row_sliced and sh.row_start > 0:
          continue
        lo = off // rpp
        heads.append((lo, min(lo + HEAD_ROWS, -(-(off + sh.input_dim) // rpp))))
      per_rank.append(head_block_starts(heads, layout.phys_rows))
    k = max(len(starts) for starts in per_rank)
    if k == 0:
      return None
    const = np.full((len(per_rank), k), HEAD_PAD, np.int32)
    for rank, starts in enumerate(per_rank):
      const[rank, :len(starts)] = starts
    return jnp.asarray(const)[self._my_rank()]

  @jax.named_scope(scopes.APPLY)
  def apply_head_counts(self, layouts: Dict[str, PackedLayout],
                        streams) -> Dict[str, jax.Array]:
    """Per sparse class ``[2]`` int32: the occurrences of ``streams``
    (``name -> (ids [n], rows)``, as :meth:`apply_sparse_streams` takes
    them) that fall in a VMEM-resident head of the apply kernel, and the
    valid occurrences. Their ratio is the guarded step's
    ``apply_head_share``: how often the head engages on this traffic. A
    class with no heads (XLA's scatter regime, a compact layout) counts 0
    in a head. Computed from the id stream and the same block starts the
    kernel is handed, on any backend. This device's local counts."""
    from ..ops.pallas_apply import head_slots
    out = {}
    for name, layout in layouts.items():
      ids = streams[name][0] if name in streams else jnp.zeros((0,), jnp.int32)
      grp, _, valid = _grp_sub(layout, ids)
      starts = self._apply_head_starts(name, layout, ids.shape[0])
      in_head = (jnp.zeros((), jnp.int32) if starts is None else jnp.sum(
          head_slots(grp, starts, layout.phys_rows) >= 0, dtype=jnp.int32))
      out[name] = jnp.stack([in_head, jnp.sum(valid, dtype=jnp.int32)])
    return out

  @jax.named_scope(scopes.APPLY)
  def apply_sparse_streams(self, fused_params: Dict[str, jax.Array],
                           layouts: Dict[str, PackedLayout],
                           streams, rule: SparseRule,
                           step: jax.Array) -> Dict[str, jax.Array]:
    """One regime-dispatched scatter-add per class over prebuilt streams
    (``name -> (ids [n], rows [n, k])``; flatten any leading micro-batch
    axes first)."""
    new_params = dict(fused_params)
    scale_only = rule.linear_scale is not None
    for name, (ids_cat, rows_cat) in streams.items():
      layout = layouts[name]
      buf = self._squeeze_local(fused_params[name])
      if not scale_only:
        # materialize the updates before the scatter: letting XLA fuse
        # the delta computation into the scatter slows its update loop
        ids_cat, rows_cat = lax.optimization_barrier((ids_cat, rows_cat))
      new_params[name] = scatter_add_fused(
          layout, buf, ids_cat, rows_cat,
          prefer_pallas=self._kernel_regime(ids_cat.shape[0], layout),
          delta_scale=(rule.linear_scale(step) if scale_only else None),
          head_starts=self._apply_head_starts(name, layout,
                                              ids_cat.shape[0]))
    return new_params

  @jax.named_scope(scopes.APPLY)
  def apply_sparse(self, fused_params: Dict[str, jax.Array],
                   layouts: Dict[str, PackedLayout],
                   d_z: Dict[tuple, jax.Array],
                   residuals: SparseResiduals,
                   rule: SparseRule, step: jax.Array,
                   exact: bool = False) -> Dict[str, jax.Array]:
    """Apply the sparse update: one fused scatter-add per sparse class.

    The IndexedSlices backward + optimizer apply of the reference
    (`embedding_lookup_ops.py:105-122` + TF sparse applies) collapsed into a
    single indexed op per class: per-occurrence cotangent rows are combined
    with the forward-saved optimizer-state rows by ``rule.delta`` and
    scatter-added (table delta | state delta) into the packed buffer.

    ``exact=True`` reproduces the reference's deduplicated semantics
    (sort + segment-sum, `embedding_lookup_kernels.cu:464-633`) at the cost
    of a sort and one extra gather.
    """
    from ..ops.sparse_grad import dedup_rows

    by_class, travelled = self._sparse_parts_by_class(d_z, residuals, rule,
                                                      layouts)
    if travelled and (exact or rule.summed):
      raise ValueError(
          f"classes {sorted(travelled)} were looked up on travelled tables "
          "(route_ids(..., layouts=...)), whose update is the sum of the "
          "ranks' per-occurrence deltas; exact=True and summed rules apply "
          "once a distinct row of the global batch. Route without layouts.")

    new_params = dict(fused_params)
    for name, parts in by_class.items():
      layout = layouts[name]
      w = layout.width
      buf = self._squeeze_local(fused_params[name])
      if name in travelled:
        delta = self._travelled_delta(layout, parts, rule, step)
        if rule.linear_scale is not None:
          delta = jnp.asarray(rule.linear_scale(step)).astype(
              delta.dtype) * delta
        new_params[name] = buf + delta.astype(buf.dtype)
        continue
      if exact:
        # class-level dedup (cross-bucket duplicates of shared tables must
        # merge) — the reference's sorted/unique semantics
        ids = jnp.concatenate([p[0].reshape(-1) for p in parts])
        g = jnp.concatenate([
            jnp.broadcast_to(dzb[:, :, None, :], idb.shape + (w,))
            .reshape(-1, w) if idb.ndim == 3 else dzb.reshape(-1, w)
            for idb, dzb, _, _ in parts])
        sr = dedup_rows(ids, g, layout.rows)
        ids, g = sr.ids, sr.rows
        fused_rows = gather_fused(layout, buf, ids)
        aux = fused_rows[..., w:].reshape(
            ids.shape + (rule.n_aux, w)) if rule.n_aux else None
        if rule.weight_decay:
          # decay once per unique touched row (dense-penalty semantics
          # restricted to touched rows)
          g = g + (2.0 * rule.weight_decay) * fused_rows[..., :w]
        delta = rule.delta(g, aux, step)
        # post-dedup ids are unique; below XLA's fast-path ratio the
        # Pallas RMW kernel wins (same static rule as the fast path)
        buf = scatter_add_fused(
            layout, buf, ids, delta,
            prefer_pallas=self._kernel_regime(ids.shape[0], layout))
      else:
        # fast path: ONE scatter-add for the whole class. Any chain of
        # scatters on the same buffer (lax.scan carry or unrolled
        # ``.at[].add`` links) defeats XLA's in-place buffer aliasing on
        # TPU: each link inserts a full copy of the multi-GiB class buffer
        # (measured: 5 copies x ~16 ms/step on the DLRM bench). A single
        # scatter aliases the donated buffer with zero copies, so all
        # buckets' ids/deltas are concatenated and applied at once.
        n_total = sum(int(np.prod(ids.shape)) for ids, _, _, _ in parts)
        if n_total <= self.apply_chunk:
          # stream build + regime-dispatched scatter: one code path shared
          # with the micro-batch mode (sparse_delta_streams /
          # apply_sparse_streams), so retunes of the barrier policy or
          # the 0.15 regime threshold cannot diverge between them
          ids_cat, rows_cat = self._stream_of_parts(layout, parts, rule,
                                                    step)
          new_params.update(self.apply_sparse_streams(
              {name: fused_params[name]}, layouts,
              {name: (ids_cat, rows_cat)}, rule, step))
          continue
        else:
          # memory escape hatch for extreme occurrence counts (hotness
          # 200-500 models): compute the delta per chunk (never holding
          # the full per-occurrence delta) and scatter chunk-wise, at the
          # cost of one buffer copy per extra link.
          for ids, dzb, aux, h in parts:
            n = int(np.prod(ids.shape))
            ids_f = ids.reshape(-1)
            dz_f = dzb.reshape(-1, w)
            aux_f = self._aux_occ(aux, layout, rule)
            res_f = (aux.reshape(-1, aux.shape[-1])
                     if rule.weight_decay and aux is not None else None)
            hh = max(1, h)  # h == 0: ragged parts arrive pre-expanded
            chunk = max(hh, (self.apply_chunk // hh) * hh)
            for c0 in range(0, n, chunk):
              cn = min(chunk, n - c0)
              g_c = dz_f[c0 // hh:(c0 + cn) // hh]
              if h > 1:
                g_c = jnp.broadcast_to(g_c[:, None, :],
                                       (cn // h, h, w)).reshape(cn, w)
              aux_c = None if aux_f is None else aux_f[c0:c0 + cn]
              if res_f is not None:
                g_c = self._decayed(g_c, res_f[c0:c0 + cn], layout, rule)
              buf = scatter_add_fused(
                  layout, buf, ids_f[c0:c0 + cn],
                  rule.delta(g_c, aux_c, step),
                  prefer_pallas=self._kernel_regime(cn, layout))
      new_params[name] = buf
    return new_params

  # ---- tiered storage: hot/cold routing + staging buffers ----------------
  @jax.named_scope(scopes.ROUTE)
  def translate_tiered_ids(self, ids_all: Dict[tuple, jax.Array],
                           tier_specs: Dict[str, "TierSpec"],
                           resident: Dict[str, jax.Array],
                           staged_grps: Dict[str, jax.Array]):
    """Rewrite routed LOGICAL ids of host-tiered classes to compact
    device-buffer ids (hot-cache slot or staging slot).

    The routing tensors stay in the logical vocabulary (so routing,
    bucketing, sentinel and mean-count semantics are untouched); this
    pass — run after :meth:`route_ids`, before the fused gather — maps
    each valid id's physical row through the rank's resident map (cold
    rows: a searchsorted over this step's sorted staged row ids) and
    rebuilds the id at the compact slot, preserving the sub-row index so
    gather/scatter arithmetic is unchanged. Ids in neither tier (a
    prefetch contract violation) map to the sentinel — counted in the
    returned metrics, never silently applied wrong.

    Args:
      tier_specs: class name -> :class:`TierSpec`.
      resident: class name -> [phys_rows] int32 per-rank map (cache slot
        or -1), the local block of a ``[world * phys_rows]`` array.
      staged_grps: class name -> [S] int32 per-rank SORTED staged
        physical-row ids, padded with ``TIER_PAD_GRP``.

    Returns:
      ``(ids_out, metrics)``: the translated routing dict, and per class
      name an int32 ``[4]`` vector ``[hot_hits, staged_hits, missed,
      valid_total]`` of this rank's occurrence counts.
    """
    out: Dict[tuple, jax.Array] = {}
    metrics: Dict[str, jax.Array] = {}
    for bk, ids in ids_all.items():
      name = class_param_name(*bk.class_key)
      spec = tier_specs.get(name)
      if spec is None:
        out[bk] = ids
        continue
      sentinel = padded_rows(self.plan, bk.class_key)
      if isinstance(ids, DedupRouted):
        # dedup'd bucket: translate the unique blocks (the only ids the
        # gather sees); the dp-side inverse map and local unique blocks
        # stay in the LOGICAL vocabulary — sentinel counting for the
        # mean combiner must not see compact slots. Hit counters then
        # count UNIQUE ids per (source, dest) block, not occurrences
        # (a miss still means dropped updates, so the trainer's
        # missed>0 contract is unchanged).
        tv, m = _translate_tier(ids.uniq, spec, sentinel, resident[name],
                                staged_grps[name])
        out[bk] = DedupRouted(uniq=tv, inv=ids.inv,
                              uniq_local=ids.uniq_local,
                              overflow=ids.overflow)
      elif isinstance(ids, tuple):  # ragged value stream (vals, lens)
        vals, lens = ids
        tv, m = _translate_tier(vals, spec, sentinel, resident[name],
                                staged_grps[name])
        out[bk] = (tv, lens)
      else:
        out[bk], m = _translate_tier(ids, spec, sentinel, resident[name],
                                     staged_grps[name])
      metrics[name] = metrics[name] + m if name in metrics else m
    return out, metrics

  # ---- dynamic vocabulary: raw-id translation (oov='allocate') -----------
  def translate_dynamic_ids(self, inputs: Sequence, translator):
    """Host-side dynamic-id translation pass (``plan.oov='allocate'``).

    Runs BETWEEN steps on the host — the :class:`TieredPrefetcher`
    pattern — never inside a trace: raw 64-bit ids are mapped through
    the translator's open-addressing tables (admitting new ids past the
    sketch threshold, recycling TTL-expired rows) and the TRANSLATED
    in-range ids feed :meth:`route_ids` unchanged, so the traced step is
    byte-identical to a static-vocab plan's and the one-scatter-add
    backward is untouched. All translation-STATE mutation lives in the
    ``dynvocab/`` host paths the translator owns (graftlint GL112 pins
    that this surface never appears in trace-reachable step code).

    Returns ``(translated_inputs, vocab_metrics, zero_work)`` — see
    :meth:`dynvocab.DynVocabTranslator.translate_batch`; apply
    ``zero_work`` to the fused buffers (``dynvocab.apply_zero_work``)
    BEFORE dispatching the step so recycled rows re-admit onto zeroed
    lanes."""
    if getattr(self.plan, "oov", "clip") != "allocate":
      raise ValueError(
          "translate_dynamic_ids needs a plan built with oov='allocate' "
          f"(got {getattr(self.plan, 'oov', 'clip')!r}): under "
          "'clip'/'error' the id space is static and raw ids feed "
          "route_ids directly.")
    return translator.translate_batch(inputs)

  @jax.named_scope(scopes.GATHER)
  def install_staging(self, fused_params: Dict[str, jax.Array],
                      tier_specs: Dict[str, "TierSpec"],
                      staged_rows: Dict[str, jax.Array]
                      ) -> Dict[str, jax.Array]:
    """Write this step's staged cold rows into each tiered buffer's
    staging region (physical rows ``[cache_grps, cache_grps + S)``).

    A dynamic-update-slice on the donated buffer — in place under XLA
    aliasing, so the persistent compact buffer doubles as the staging
    target and the one-scatter-add backward covers both tiers. ``S`` may
    exceed ``spec.staging_grps`` on spill steps (the step retraces; the
    effective :class:`PackedLayout` must be built from the same S)."""
    out = dict(fused_params)
    for name, spec in tier_specs.items():
      rows = staged_rows[name]
      buf = self._squeeze_local(fused_params[name])
      need = spec.cache_grps + rows.shape[0]
      if need > buf.shape[0]:
        # spill step: extend the buffer past its persistent staging
        # region (a copy — bounded by the spill being rare; the trailing
        # region is sliced back off by staged_regions)
        buf = jnp.concatenate(
            [buf, jnp.zeros((need - buf.shape[0], buf.shape[1]),
                            buf.dtype)])
      out[name] = jax.lax.dynamic_update_slice(
          buf, rows.astype(buf.dtype), (spec.cache_grps, 0))
    return out

  @jax.named_scope(scopes.APPLY)
  def staged_regions(self, fused_params: Dict[str, jax.Array],
                     tier_specs: Dict[str, "TierSpec"],
                     staged_rows: Dict[str, jax.Array]
                     ) -> Dict[str, jax.Array]:
    """Slice the (post-scatter) staging regions back out, sized to this
    step's staged row count — the rows the host writes back to the cold
    store."""
    out = {}
    for name, spec in tier_specs.items():
      s = staged_rows[name].shape[0]
      buf = self._squeeze_local(fused_params[name])
      out[name] = jax.lax.dynamic_slice(
          buf, (spec.cache_grps, 0), (s, buf.shape[1]))
    return out

  @jax.named_scope(scopes.APPLY)
  def trim_spill(self, fused_params: Dict[str, jax.Array],
                 tier_specs: Dict[str, "TierSpec"]
                 ) -> Dict[str, jax.Array]:
    """Restore each tiered buffer to its persistent compact shape after a
    spill step extended it (no-op slices are free)."""
    out = dict(fused_params)
    for name, spec in tier_specs.items():
      buf = self._squeeze_local(fused_params[name])
      keep = spec.cache_grps + spec.staging_grps
      if buf.shape[0] > keep:
        out[name] = buf[:keep]
    return out

  # ---- model-parallel input mode -----------------------------------------
  def forward_mp(self, class_params: Dict[str, jax.Array],
                 packed_inputs: Dict[str, jax.Array],
                 hotness: Optional[Sequence[int]] = None) -> List[jax.Array]:
    """Distributed lookup for model-parallel inputs (dp_input=False).

    ``packed_inputs`` comes from :func:`pack_mp_inputs`: per bucket, the
    local block ``[1, n_b, G, h]`` of pre-offset ids for this rank's tables
    over the *global* batch. Skips the dp->mp exchange; the output exchange
    still runs (reference semantics, `dist_model_parallel.py:449-459`).
    """
    plan = self.plan
    world = plan.world_size
    if any(sh.row_sliced for shards in plan.rank_shards for sh in shards):
      raise NotImplementedError(
          "row-sliced tables are not supported with model-parallel inputs "
          "(dp_input=False): every rank holding a row slice needs the full "
          "id stream, which contradicts the mp-input contract")
    if hotness is not None and any(h < 0 for h in hotness):
      raise ValueError(
          "negative hotness entries (the planner's ragged-input hint) are "
          "not valid in model-parallel input mode: ragged value streams "
          "only exist for the dp-input exchange. Convert the input with "
          "ragged_to_padded and pass its static max hotness instead.")
    hotness_of = (lambda i: 1) if hotness is None else \
        (lambda i: hotness[i])  # noqa: E731
    z = {}
    g = None
    for key in plan.class_keys:
      table_local = self._squeeze_local(class_params[class_param_name(*key)])
      for bucket in self._buckets(key, hotness_of):
        name = _packed_input_name(key, bucket)
        if name not in packed_inputs:
          raise ValueError(
              f"packed input {name!r} missing; pass the same `hotness` to "
              "pack_mp_inputs and forward_mp")
        ids_all = packed_inputs[name]
        if (ids_all.ndim != 4 or ids_all.shape[0] != 1
            or ids_all.shape[1] != bucket.n_b
            or ids_all.shape[3] != bucket.h):
          raise ValueError(
              f"packed input {name!r} has shape {ids_all.shape}, expected "
              f"[1, {bucket.n_b}, G, {bucket.h}] — was it packed with a "
              "different plan or hotness?")
        ids_all = ids_all[0]
        g = ids_all.shape[1]
        if g % world:
          raise ValueError(f"Global batch {g} not divisible by world {world}")
        bk = bucket_key(key, bucket.h, bucket.vcap, bucket.rs)
        if plan.classes[key].kind == "dense":
          with jax.named_scope(scopes.COMBINE):
            z[bk] = self._z_dense(key, bucket, table_local, ids_all)
        else:
          with jax.named_scope(scopes.GATHER):
            z[bk] = self._z_sparse_simple(key, table_local, ids_all)
    with jax.named_scope(scopes.COMBINE):
      received = self.exchange(z, g // world)
      return self.assemble(received, hotness_of)


def _packed_input_name(key, bucket: Bucket) -> str:
  name = f"{class_param_name(*key)}_h{bucket.h}"
  if bucket.vcap:
    name += f"_v{bucket.vcap}"
  return name


def pack_mp_inputs(plan: DistEmbeddingStrategy,
                   per_rank_inputs: Sequence[Sequence[jax.Array]],
                   hotness: Optional[Sequence[int]] = None,
                   ) -> Dict[str, jax.Array]:
  """Build global packed arrays for dp_input=False mode.

  Args:
    plan: the strategy.
    per_rank_inputs: ``per_rank_inputs[r]`` lists rank r's local inputs in
      ``plan.input_ids_list[r]`` order, each [G] or [G, H] over the *global*
      batch (reference mp-input contract, `dist_model_parallel.py:344-346`).
    hotness: per global input id, its static hotness; pass the same value to
      :meth:`DistributedLookup.forward_mp`. Default all-1.

  Returns:
    packed-input name -> [world, n_b, G, h] arrays; shard axis 0 over the
    mesh, then pass the per-device blocks to ``forward_mp``.
  """
  world = plan.world_size
  if any(sh.row_sliced for shards in plan.rank_shards for sh in shards):
    raise NotImplementedError(
        "row-sliced tables are not supported with model-parallel inputs: "
        "per-rank id streams cannot cover a table split across ranks")
  if hotness is not None and any(h < 0 for h in hotness):
    raise ValueError(
        "negative hotness entries (the planner's ragged-input hint) are "
        "not valid for pack_mp_inputs: ragged value streams only exist "
        "for the dp-input exchange. Convert the input with "
        "ragged_to_padded and pass its static max hotness instead.")
  hotness_of = (lambda i: 1) if hotness is None else \
      (lambda i: hotness[i])  # noqa: E731
  # resolve each (rank, class, slot) to its normalized local input once
  slot_inputs = {}  # (key, rank, slot_idx) -> [G, H] array
  for rank in range(world):
    for pos, input_id in enumerate(plan.input_ids_list[rank]):
      piece = next(p for p in plan.output_pieces[input_id] if p.rank == rank)
      x = _normalize_input(per_rank_inputs[rank][pos])
      if isinstance(x, RaggedIds):
        raise TypeError(
            "model-parallel inputs (dp_input=False) do not support "
            "RaggedIds; convert with ragged_to_padded(ids, max_hot) — "
            "value-stream routing only exists for the dp-input exchange")
      if x.shape[1] != hotness_of(input_id):
        raise ValueError(
            f"input {input_id} has hotness {x.shape[1]}, `hotness` says "
            f"{hotness_of(input_id)}")
      slot_inputs[(piece.class_key, rank, piece.slot)] = x

  packed = {}
  for key in plan.class_keys:
    cp = plan.classes[key]
    sentinel = padded_rows(plan, key)
    g = next((x.shape[0] for x in slot_inputs.values()), 0)
    for bucket in class_buckets(plan, key, hotness_of):
      per_rank = []
      for rank in range(world):
        idxs = bucket.slot_idx_per_rank[rank]
        entries = []
        for k in range(bucket.n_b):
          if k < len(idxs):
            slot = cp.slots_per_rank[rank][idxs[k]]
            x = slot_inputs[(key, rank, idxs[k])]
            rows = slot.shard.input_dim
            # int32 wire format: bounded by clip to row_offset + rows <=
            # padded class rows, planner-capped under 2^31
            routed = jnp.where(x < 0, sentinel,  # graftlint: disable=GL106
                               jnp.clip(x, 0, rows - 1) + slot.row_offset
                               ).astype(jnp.int32)
          else:
            routed = jnp.full((g, bucket.h), sentinel, jnp.int32)
          entries.append(routed)
        per_rank.append(jnp.stack(entries))
      packed[_packed_input_name(key, bucket)] = jnp.stack(per_rank)
  return packed
