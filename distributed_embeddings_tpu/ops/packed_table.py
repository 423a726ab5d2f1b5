"""Lane-packed table storage with fused optimizer-state rows.

TPU performance foundation for the sparse embedding path. Measured on v5e,
every indexed row op (gather / scatter) costs ~8-23 ns **per row regardless
of row width** up to one 512-byte tile line — bytes are free, rows are
expensive. Narrow embedding rows (the reference's width 8..128 tables,
`/root/reference/examples/benchmarks/synthetic_models/config_v3.py:30-142`)
are therefore stored packed, several logical rows per 128-lane physical row,
and the optimizer's per-row state (e.g. the Adagrad accumulator the
reference keeps as a TF slot variable) is **interleaved into the same
physical row** as its table row:

    physical row (128 lanes, f32):
    [ t[4k] | acc[4k] | t[4k+1] | acc[4k+1] | ... ]   (width 16, 1 aux slot)

Consequences:
- the forward gather brings the optimizer state along *for free* (row-bound
  cost), so the backward needs **one** scatter-add of a fused
  (table-delta | state-delta) row — replacing the reference backward's
  sort/unique/segment-sum + separate accumulator and table scatter traffic
  (`embedding_lookup_kernels.cu:464-633` + TF sparse Adagrad apply) with a
  single indexed op;
- physical rows are always a multiple of 128 lanes, so XLA never inserts
  the tile-padding relayout copies that a raw ``[rows, 16]`` operand
  triggers (8x memory and an OOM at 70M rows).

All ops are jit/shard_map safe with static shapes; ids outside
``[0, rows)`` are padding sentinels (gather returns zero rows, scatter
drops).
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import scopes

LANES = 128

# Read ONCE at import (baking an os.environ.get into a jitted trace makes
# later flips silently ineffective — advisor finding, round 2). Overrides
# gather_fused_chunked's DEFAULT chunk size (never an explicit argument);
# 0/unset = the built-in default.
_GATHER_CHUNK_ENV = int(os.environ.get("DE_TPU_GATHER_CHUNK", "0") or "0")


@dataclasses.dataclass(frozen=True)
class PackedLayout:
  """Physical layout of one logical ``[rows, width]`` table with ``n_aux``
  interleaved per-row optimizer-state rows."""

  rows: int
  width: int
  n_aux: int = 0

  @property
  def stride(self) -> int:
    """Lanes per logical row: table row + its aux rows."""
    return self.width * (1 + self.n_aux)

  @property
  def rows_per_phys(self) -> int:
    return max(1, LANES // self.stride)

  @property
  def phys_width(self) -> int:
    return max(LANES, -(-self.stride // LANES) * LANES)

  @property
  def phys_rows(self) -> int:
    return -(-self.rows // self.rows_per_phys)

  @property
  def shape(self):
    return (self.phys_rows, self.phys_width)

  # ---- packing (host or device; pure reshapes) ---------------------------
  def pack(self, table, aux: Sequence = ()):
    """``[rows, width]`` table (+ per-aux ``[rows, width]``) -> packed buf."""
    xp = jnp if isinstance(table, jax.Array) else np
    parts = [table] + list(aux)
    if len(parts) != 1 + self.n_aux:
      raise ValueError(f"Expected {self.n_aux} aux arrays, got {len(aux)}")
    rpp = self.rows_per_phys
    pad_rows = self.phys_rows * rpp - self.rows
    stacked = xp.stack(parts, axis=1)  # [rows, 1+n_aux, width]
    if pad_rows:
      stacked = xp.concatenate(
          [stacked, xp.zeros((pad_rows,) + stacked.shape[1:], stacked.dtype)],
          axis=0)
    flat = stacked.reshape(self.phys_rows, rpp * self.stride)
    lane_pad = self.phys_width - rpp * self.stride
    if lane_pad:
      flat = xp.concatenate(
          [flat, xp.zeros((self.phys_rows, lane_pad), flat.dtype)], axis=1)
    return flat

  def pack_chunked(self, table: jax.Array, aux_values: Sequence[float],
                   chunk_rows: int = 1 << 18) -> jax.Array:
    """Device-side pack with bounded intermediates (constant-filled aux).

    A one-shot ``pack`` of a large narrow table materializes a tile-padded
    intermediate (XLA pads sub-128 minor dims to 128 lanes — 8x memory for
    width 16, an instant OOM at 70M rows). This variant streams logical-row
    chunks through small padded temps into the 128-lane output buffer via
    ``dynamic_update_slice``. Aux rows are constant fills (the optimizer
    initial state), so no aux source arrays are ever allocated.
    """
    rpp = self.rows_per_phys
    chunk_rows = max(rpp, (chunk_rows // rpp) * rpp)
    # lane template: aux lanes at their init constants, table lanes 0
    tmpl = np.zeros((self.phys_width,), np.float32)
    for j in range(rpp):
      for s, v in enumerate(aux_values):
        lo = j * self.stride + (1 + s) * self.width
        tmpl[lo:lo + self.width] = v
    buf = jnp.broadcast_to(jnp.asarray(tmpl, table.dtype),
                           (self.phys_rows, self.phys_width))
    if not aux_values:
      buf = jnp.zeros((self.phys_rows, self.phys_width), table.dtype)
    aux_fill = jnp.asarray(
        np.concatenate([np.full((self.width,), v, np.float32)
                        for v in aux_values]) if aux_values
        else np.zeros((0,), np.float32), table.dtype)
    for c0 in range(0, self.rows, chunk_rows):
      cr = min(chunk_rows, self.rows - c0)
      cr_pad = -(-cr // rpp) * rpp
      rows_c = table[c0:c0 + cr]
      if cr_pad != cr:
        rows_c = jnp.concatenate(
            [rows_c, jnp.zeros((cr_pad - cr, self.width), table.dtype)])
      rows_c = rows_c.reshape(cr_pad // rpp, rpp, self.width)
      if self.n_aux:
        af = jnp.broadcast_to(aux_fill,
                              (cr_pad // rpp, rpp, aux_fill.shape[0]))
        rows_c = jnp.concatenate([rows_c, af], axis=-1)
      chunk = rows_c.reshape(cr_pad // rpp, rpp * self.stride)
      lane_pad = self.phys_width - rpp * self.stride
      if lane_pad:
        chunk = jnp.concatenate(
            [chunk, jnp.zeros((chunk.shape[0], lane_pad), table.dtype)],
            axis=1)
      buf = jax.lax.dynamic_update_slice(buf, chunk, (c0 // rpp, 0))
    return buf

  def unpack_table_chunked(self, buf: jax.Array,
                           chunk_phys: int = 1 << 16) -> jax.Array:
    """Packed buf -> table ``[rows, width]`` with bounded intermediates."""
    rpp = self.rows_per_phys
    parts = []
    for p0 in range(0, self.phys_rows, chunk_phys):
      pc = min(chunk_phys, self.phys_rows - p0)
      blk = buf[p0:p0 + pc, :rpp * self.stride]
      blk = blk.reshape(pc * rpp, self.stride)[:, :self.width]
      parts.append(blk)
    table = jnp.concatenate(parts, axis=0)
    return table[:self.rows]

  def unpack(self, buf):
    """Packed buf -> ``(table [rows, width], [aux_0, aux_1, ...])``."""
    xp = jnp if isinstance(buf, jax.Array) else np
    del xp
    rpp = self.rows_per_phys
    flat = buf[:, :rpp * self.stride]
    stacked = flat.reshape(self.phys_rows * rpp, 1 + self.n_aux, self.width)
    stacked = stacked[:self.rows]
    table = stacked[:, 0, :]
    aux = [stacked[:, 1 + j, :] for j in range(self.n_aux)]
    return table, aux


def init_packed_uniform(layout: PackedLayout, key: jax.Array,
                        scale_rows: jax.Array, aux_values: Sequence[float],
                        dtype=jnp.float32, chunk_phys: int = 1 << 16
                        ) -> jax.Array:
  """Initialize a packed buffer directly in its physical layout.

  Table lanes get ``uniform(-1, 1) * scale_rows[row]`` (per-logical-row
  scale, e.g. the DLRM ``1/sqrt(rows)`` or Keras ``0.05``); aux lanes get
  their ``aux_values`` constants; rows with ``scale_rows == 0`` (padding /
  unused) are zero. The ``[rows, width]`` logical table is never
  materialized — the peak allocation is the buffer itself plus one
  ``chunk_phys``-row temporary, which is what lets a near-HBM-sized class
  initialize on chip (the generic ``pack_chunked`` path needs the simple
  table as input, a 1.5x transient).
  """
  rpp = layout.rows_per_phys
  stride = layout.stride
  w = layout.width
  # per-lane template: 1 where a table lane lives, aux constant elsewhere
  lane_is_table = np.zeros((layout.phys_width,), bool)
  aux_tmpl = np.zeros((layout.phys_width,), np.float32)
  for j in range(rpp):
    lo = j * stride
    lane_is_table[lo:lo + w] = True
    for s, v in enumerate(aux_values):
      aux_tmpl[lo + (1 + s) * w:lo + (2 + s) * w] = v
  lane_is_table = jnp.asarray(lane_is_table)
  aux_tmpl = jnp.asarray(aux_tmpl, dtype)

  pr = layout.phys_rows
  scale_p = jnp.zeros((pr * rpp,), dtype).at[:layout.rows].set(
      scale_rows.astype(dtype))
  scale_p = scale_p.reshape(pr, rpp)
  cp = min(chunk_phys, pr)

  def chunk_at(k, start):
    sub = jax.random.fold_in(key, k)
    u = jax.random.uniform(sub, (cp, rpp, stride), dtype,
                           minval=-1.0, maxval=1.0)
    sc = jax.lax.dynamic_slice(scale_p, (start, 0), (cp, rpp))
    vals = (u * sc[..., None]).reshape(cp, rpp * stride)
    pad = layout.phys_width - rpp * stride
    if pad:
      vals = jnp.concatenate([vals, jnp.zeros((cp, pad), dtype)], axis=1)
    # aux lanes: constant where the row is live (scale > 0 marks live rows)
    live = (sc > 0).any(axis=1)
    aux_part = jnp.where(live[:, None], aux_tmpl[None, :], 0)
    return jnp.where(lane_is_table[None, :], vals, aux_part)

  if cp == pr:
    return chunk_at(0, 0)
  # overlap-safe starts: the tail chunk re-draws a few rows with a different
  # subkey, which keeps every row's scale mapping exact without a copy
  nchunks = -(-pr // cp)
  # int64 product (numpy default), clamped to pr - cp < 2^31 (planner's
  # per-buffer element cap) before the narrowing
  starts = np.minimum(np.arange(nchunks) * cp,  # graftlint: disable=GL106
                      pr - cp).astype(np.int32)
  buf = jnp.zeros((pr, layout.phys_width), dtype)

  def body(b, xs):
    k, start = xs
    return jax.lax.dynamic_update_slice(b, chunk_at(k, start), (start, 0)), None

  buf, _ = jax.lax.scan(
      body, buf, (jnp.arange(nchunks), jnp.asarray(starts)))
  return buf


def _grp_sub(layout: PackedLayout, ids: jax.Array):
  """ids -> (physical row, sub-row) with OOB ids sent past the buffer."""
  valid = (ids >= 0) & (ids < layout.rows)
  ids = jnp.where(valid, ids, 0).astype(jnp.int32)
  rpp = layout.rows_per_phys
  grp = jnp.where(valid, ids // rpp, layout.phys_rows)
  sub = ids % rpp
  return grp, sub, valid


@jax.named_scope(scopes.GATHER)
def gather_fused(layout: PackedLayout, buf: jax.Array,
                 ids: jax.Array, masked_phys: bool = False) -> jax.Array:
  """Gather fused rows: ``[..., stride]`` = (table row | aux rows).

  One row-bound gather serves both the lookup and the optimizer-state read
  (the reference needs a separate accumulator read in its sparse Adagrad
  apply). OOB/sentinel ids return all-zero rows.
  """
  grp, sub, _ = _grp_sub(layout, ids)
  g = jnp.take(buf, grp, axis=0, mode="fill", fill_value=0)
  rpp = layout.rows_per_phys
  if masked_phys:
    # window-MASKED physical rows [..., rpp*stride]: every lane outside
    # the occurrence's sub-row window zeroed (one fused VPU select), no
    # per-occurrence extraction — callers fold the rpp windows at bag
    # granularity (the multi-hot fast path, lookup_engine._z_sparse_fused)
    stride = layout.stride
    g = g[..., :rpp * stride]
    if rpp == 1:
      return g
    win = jax.lax.broadcasted_iota(jnp.int32, (rpp * stride,), 0) // stride
    return jnp.where(win == sub[..., None], g, 0)
  if rpp == 1:
    return g[..., :layout.stride]
  # sub-row extraction as unrolled static-lane-window selects: exactly one
  # window is live per occurrence, so summing the masked windows extracts
  # it. Pure VPU ops on static lane slices — no one-hot einsum (matmul-
  # shaped contraction) and no cross-lane reshape (relayout copy).
  stride = layout.stride
  out = None
  for s in range(rpp):
    part = jnp.where((sub == s)[..., None],
                     g[..., s * stride:(s + 1) * stride], 0)
    out = part if out is None else out + part
  return out


@jax.named_scope(scopes.GATHER)
def gather_fused_chunked(layout: PackedLayout, buf: jax.Array,
                         ids: jax.Array,
                         chunk: Optional[int] = None,
                         masked_phys: bool = False) -> jax.Array:
  """:func:`gather_fused` with bounded temporaries.

  When ``rows_per_phys == 1`` (stride >= 128 lanes — e.g. the width-128
  DLRM tables) a fused gather is a single XLA row gather with no staging
  beyond its own output, so it runs one-shot regardless of size. Narrow
  rows (``rpp > 1``) stage ``[N, phys_width]`` (512 B per id) for the
  lane-window selects — over a GiB at benchmark batch sizes — so large
  streams run as a ``lax.map`` over fixed-size id chunks, which bounds
  live temporaries to one chunk at identical row-op cost (indexed ops are
  row-bound, not launch-bound). The ``lax.map`` does add a sequential
  dynamic-update-slice per chunk (~10 ms at Tiny scale, traced), so the
  default chunk keeps typical per-bucket streams (<= 2M ids) one-shot;
  ``DE_TPU_GATHER_CHUNK`` overrides. (Round 3: default 2M -> 4M after
  tracing Small's chunked w32 gather — the lax.map's per-chunk
  dynamic-update-slice cost ~16 ms/step; one 4M chunk stages 2.1 GB
  transiently and saved 10 ms end-to-end.)
  """
  if chunk is None:  # env overrides the DEFAULT only, never an explicit arg
    chunk = _GATHER_CHUNK_ENV or (1 << 22)
  width = (layout.rows_per_phys * layout.stride if masked_phys
           else layout.stride)
  flat = ids.reshape(-1)
  n = flat.shape[0]
  if (layout.rows_per_phys == 1 and not masked_phys) or n <= chunk:
    return gather_fused(layout, buf, ids, masked_phys=masked_phys)
  nchunks = -(-n // chunk)
  pad = nchunks * chunk - n
  if pad:
    flat = jnp.concatenate([flat, jnp.full((pad,), -1, flat.dtype)])
  out = jax.lax.map(
      lambda c: gather_fused(layout, buf, c, masked_phys=masked_phys),
      flat.reshape(nchunks, chunk))
  out = out.reshape(nchunks * chunk, width)[:n]
  return out.reshape(ids.shape + (width,))


def mxu_operand_dtype(dtype):
  """bf16 on TPU under DEFAULT matmul precision, pass-through elsewhere.

  Under JAX's DEFAULT matmul precision the TPU MXU multiplies f32
  operands as one bf16 pass anyway, so storing a matmul operand in bf16
  changes no product bits on TPU — it only halves the operand's HBM
  traffic and any relayout copies XLA schedules around the dot. The cast
  is skipped when the user raised ``jax_default_matmul_precision`` (they
  asked for true multi-pass f32) and on CPU (tests), where f32 dots are
  real f32. Keyed on the default backend: a computation explicitly
  placed off the default TPU still gets the cast — accepted limitation
  of trace-time backend detection."""
  if dtype != jnp.float32:
    return dtype
  if jax.default_backend() != "tpu":
    return dtype
  prec = jax.config.jax_default_matmul_precision
  if prec not in (None, "default", "bfloat16", "fastest"):
    return dtype  # user explicitly asked for multi-pass f32 fidelity
  return jnp.bfloat16


def _use_pallas_apply() -> bool:
  """True when the Pallas RMW apply kernel can run (real TPU backend)."""
  return jax.default_backend() == "tpu"


@jax.named_scope(scopes.APPLY)
def scatter_add_fused(layout: PackedLayout, buf: jax.Array, ids: jax.Array,
                      fused_delta: jax.Array,
                      prefer_pallas: bool = False,
                      delta_scale: Optional[jax.Array] = None,
                      head_starts: Optional[jax.Array] = None) -> jax.Array:
  """``buf[ids] += fused_delta`` (one indexed RMW for table + all aux).

  ``fused_delta``: ``[..., stride]`` additive deltas in gather_fused's lane
  order. Duplicate ids accumulate; OOB ids are dropped. Donate ``buf`` at
  the jit boundary for an in-place update.

  ``delta_scale``: optional scalar multiplier for the whole delta (the
  scale-only rule fast path, e.g. SGD's ``-lr``). On the Pallas path the
  scale is applied in-kernel, so the caller passes raw cotangent rows and
  no staged delta array ever exists in HBM; on the XLA path the scale is
  applied (behind an optimization_barrier — fusing elementwise work into
  the scatter de-optimizes its update loop) before the scatter.

  Lowering (measured on v5e, `docs/BENCHMARKS.md`): XLA's scatter has a
  fast sorted/locality path at ~16-25 ns/row that it only picks when the
  id stream is >= ~0.15x the buffer's rows, and a ~75 ns/row serial path
  otherwise; the Pallas RMW cache kernel (`ops/pallas_apply.py`) is
  ~47-60 ns/row in every regime. Callers that know the stream sits below
  XLA's fast-path ratio pass ``prefer_pallas=True`` (the engine computes
  this statically per class, `lookup_engine.apply_sparse`); the default
  keeps XLA. ``DE_TPU_PALLAS_APPLY=0/1`` force-overrides.

  ``head_starts``: optional ``[K]`` int32 starts, in PHYSICAL rows of
  ``buf``, of the blocks the Pallas kernel keeps resident in VMEM (each
  table's hot first rows; `pallas_apply.head_block_starts`). A caller that
  knows where its tables start passes them; the XLA path has no use for
  them and every result is the same with or without.
  """
  grp, sub, valid = _grp_sub(layout, ids)
  fused_delta = jnp.where(valid[..., None], fused_delta, 0)
  rpp = layout.rows_per_phys
  if fused_delta.shape[-1] == layout.phys_width:
    # pre-expanded physical rows (ops/pallas_delta.py): window placement
    # and lane padding already done in-kernel
    upd = fused_delta
  elif rpp == 1:
    lane_pad = layout.phys_width - layout.stride
    if lane_pad:
      fused_delta = jnp.concatenate(
          [fused_delta,
           jnp.zeros(fused_delta.shape[:-1] + (lane_pad,), fused_delta.dtype)],
          axis=-1)
    upd = fused_delta
  else:
    # narrow rows: expand the sub-row delta to the full physical row (the
    # RMW below is per PHYSICAL row either way); duplicates on the same
    # physical row still accumulate. Keep the one-hot einsum form: its
    # [.., rpp, stride] output costs a lane-merging relayout copy
    # (~8 ms/step on Tiny, traced) but a tile+where form fuses the select
    # INTO the scatter's update loop and de-optimizes it ~40x (5.7 s/step
    # measured round 3) — the same fusion hazard the apply's
    # optimization_barrier guards against.
    oh = jax.nn.one_hot(sub, rpp, dtype=fused_delta.dtype)
    upd = jnp.einsum("...s,...r->...rs", fused_delta, oh)
    upd = upd.reshape(ids.shape + (rpp * layout.stride,))
    lane_pad = layout.phys_width - rpp * layout.stride
    if lane_pad:
      upd = jnp.concatenate(
          [upd, jnp.zeros(upd.shape[:-1] + (lane_pad,), upd.dtype)], axis=-1)
  flat_grp = grp.reshape(-1)
  flat_upd = upd.reshape(-1, layout.phys_width).astype(buf.dtype)
  import os
  forced = os.environ.get("DE_TPU_PALLAS_APPLY", "auto")
  # Narrow classes (rpp > 1) use the SAME kernel at physical-row
  # granularity: the lane expansion above places each sub-row delta in its
  # window, two logical rows sharing a physical row accumulate exactly
  # (disjoint windows add disjointly, same-window duplicates add like any
  # duplicate), and the kernel's cache is keyed by physical row. The
  # expansion stays outside the kernel by measurement: fused into either
  # backend it costs ~1.7 ns/occ (docs/BENCHMARKS.md).
  # Mosaic rejects 1-row dynamic HBM slices of tiled memrefs wider than
  # one 128-lane tile ("slice along dim 0 must be aligned to (8)" at
  # phys_width 256 — w128 tables + interleaved aux), so the RMW kernel
  # serves exactly the 128-lane physical layouts; wider classes keep
  # XLA's scatter (smoke covers the fallback's correctness).
  use_pallas = (prefer_pallas if forced == "auto" else forced == "1") \
      and _use_pallas_apply() and buf.dtype == jnp.float32 \
      and buf.shape[1] == LANES
  if use_pallas:
    from .pallas_apply import apply_rows_cached
    return apply_rows_cached(buf, flat_grp, flat_upd, scale=delta_scale,
                             head_starts=head_starts)
  if delta_scale is not None:
    # asarray first: a custom rule's linear_scale may return a Python
    # float outside jit (the Pallas path's jnp.reshape already accepts it)
    flat_upd = jax.lax.optimization_barrier(
        jnp.asarray(delta_scale).astype(flat_upd.dtype) * flat_upd)
  return buf.at[flat_grp].add(flat_upd, mode="drop")


# ---------------------------------------------------------------------------
# Host cold-store blocks (tiering subsystem)
# ---------------------------------------------------------------------------
#
# The host tier stores a class's FULL packed image — same physical layout
# as the device buffer (physical rows of phys_width lanes, optimizer state
# interleaved) — as one numpy array per rank in host RAM. Moving rows
# between tiers is therefore a pure block copy at PHYSICAL-row granularity:
# no repacking, no lane shuffling, and the staging buffer a step uploads is
# bit-identical to what a fully device-resident run would have held at
# those rows. All three helpers operate on physical-row ids (``grp`` in
# gather/scatter terms), the granularity the hot/cold split classifies at.


def host_gather_rows(layout: PackedLayout, store: np.ndarray,
                     grps: np.ndarray) -> np.ndarray:
  """Cold-block gather: ``store[grps]`` with bounds validation.

  ``store``: the rank's host image ``[phys_rows, phys_width]``;
  ``grps``: int physical-row ids (must be unique and in range — the
  prefetcher dedups before gathering, and a silent clamp here would turn
  a routing bug into wrong training)."""
  grps = np.asarray(grps)
  if grps.size and (grps.min() < 0 or grps.max() >= layout.phys_rows):
    raise IndexError(
        f"cold gather out of range: grps in [{grps.min()}, {grps.max()}] "
        f"for a {layout.phys_rows}-physical-row store")
  if store.shape != (layout.phys_rows, layout.phys_width):
    raise ValueError(
        f"host store shape {store.shape} does not match layout "
        f"{(layout.phys_rows, layout.phys_width)}")
  return np.ascontiguousarray(store[grps])


def host_scatter_rows(layout: PackedLayout, store: np.ndarray,
                      grps: np.ndarray, rows: np.ndarray) -> None:
  """Cold-block write-back: ``store[grps] = rows`` in place.

  Overwrite (not add) semantics: the device staging region accumulated
  every occurrence's scatter-add delta during the step, so its rows ARE
  the new authoritative values. ``grps`` must be unique — duplicate ids
  would make the result depend on numpy's assignment order."""
  grps = np.asarray(grps)
  if grps.size and (grps.min() < 0 or grps.max() >= layout.phys_rows):
    raise IndexError(
        f"cold scatter out of range: grps in [{grps.min()}, {grps.max()}] "
        f"for a {layout.phys_rows}-physical-row store")
  if rows.shape != (grps.shape[0], layout.phys_width):
    raise ValueError(
        f"cold scatter rows shape {rows.shape}, expected "
        f"{(grps.shape[0], layout.phys_width)}")
  store[grps] = rows


def init_host_store(layout: PackedLayout, rng: np.random.Generator,
                    scale_rows: np.ndarray, aux_values: Sequence[float],
                    dtype=np.float32) -> np.ndarray:
  """Build one rank's host image directly in the packed physical layout.

  Host-RAM counterpart of :func:`init_packed_uniform`: table lanes get
  ``uniform(-1, 1) * scale_rows[row]``, aux lanes their init constants
  (zeroed on dead rows, ``scale_rows == 0``), lane padding zero. numpy
  RNG (not jax.random) — the host tier exists precisely for tables too
  big to materialize on device, so the draw must not stage anything
  there. Not bit-identical to init_packed_uniform's draws; for parity
  with a device-initialized run, pack that run's initial table instead.
  """
  rpp, stride, w = layout.rows_per_phys, layout.stride, layout.width
  scale_rows = np.asarray(scale_rows, dtype)
  if scale_rows.shape != (layout.rows,):
    raise ValueError(
        f"scale_rows shape {scale_rows.shape}, expected ({layout.rows},)")
  store = np.zeros((layout.phys_rows, layout.phys_width), dtype)
  scale_p = np.zeros((layout.phys_rows * rpp,), dtype)
  scale_p[:layout.rows] = scale_rows
  # draw per logical row, place into the interleaved lane windows
  vals = rng.uniform(-1.0, 1.0,
                     (layout.phys_rows * rpp, w)).astype(dtype)
  vals *= scale_p[:, None]
  live = scale_p > 0
  for j in range(rpp):
    lo = j * stride
    store[:, lo:lo + w] = vals[j::rpp]
    for s, v in enumerate(aux_values):
      store[:, lo + (1 + s) * w:lo + (2 + s) * w] = np.where(
          live[j::rpp, None], dtype(v) if np.isscalar(v) else v, 0)
  return store


# ---------------------------------------------------------------------------
# Sparse update rules (fused-delta form)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SparseRule:
  """Per-occurrence sparse update rule in additive (scatter-add) form.

  ``n_aux`` per-row state slots ride in the packed layout; ``aux_init``
  gives their fill values; ``delta(g, aux_rows, step)`` maps an occurrence's
  cotangent row ``g [..., W]`` and its *pre-step* aux rows
  ``[..., n_aux, W]`` to the fused additive delta ``[..., stride]``.

  With duplicate ids in a batch, each occurrence computes its delta from the
  forward-time state — the semantics of stock TF sparse optimizer applies
  (scatter_add on slot + param), which the reference relies on outside its
  fused op. Exact deduplicated semantics (the reference fused backward,
  `embedding_lookup_kernels.cu:464-633`) are available via the engine's
  ``exact=True`` path.

  ``weight_decay`` (λ of a Keras-style ``l2(λ)`` penalty, reference
  `embedding.py:64-70`): when nonzero the engine adds ``2*λ*row`` to each
  occurrence's cotangent before ``delta`` — l2 decay on TOUCHED rows, per
  occurrence (under ``exact=True``: once per unique touched row). This is
  the sparse-path counterpart of the reference's full-table penalty: rows
  never looked up are not decayed (a dense sweep over terabyte tables is
  exactly what the sparse path exists to avoid), and the reported loss
  carries the data term only. Set via ``dataclasses.replace`` or the
  training builder, which folds a uniform table ``regularizer='l2'`` in."""

  name: str
  n_aux: int
  aux_init: Sequence[float]
  delta: callable
  weight_decay: float = 0.0
  # for rules whose delta is a pure scalar multiple of the cotangent
  # (SGD: -lr * g), ``linear_scale(step)`` returns that multiplier; the
  # engine then skips the delta materialization entirely and the Pallas
  # RMW kernel applies the scale in-VMEM (`pallas_apply.apply_rows_cached`)
  linear_scale: Optional[callable] = None
  # flat-lanes twin of ``delta`` for the Pallas delta-build kernel
  # (`ops/pallas_delta.py`): ``delta_lanes(g, [aux_0, ..], step)`` returns
  # the delta as a LIST of [..., W] lane groups (table first) — Mosaic
  # cannot build the [..., n_aux, W] aux view in-kernel. Must compute
  # exactly what ``delta`` computes (tests/test_pallas_delta.py pins it)
  delta_lanes: Optional[callable] = None
  # a rule that is only sound applied ONCE per distinct row, from the sum of
  # the row's occurrence gradients (Adam on a table whose hot rows are read
  # hundreds of times a step: per occurrence the first moment is multiplied
  # by 1 - (1 - b1) k for a row read k times). The step builders then take
  # the deduplicated path (``exact=True``) whatever their ``exact=`` says
  summed: bool = False

  def init_aux(self, rows: int, width: int, dtype=jnp.float32) -> List:
    return [np.full((rows, width), v, dtype) for v in self.aux_init]


def _lr_at(lr, step):
  return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


def sgd_rule(learning_rate) -> SparseRule:
  """Row-sparse SGD: table[id] -= lr * g (exact even with duplicates)."""

  def delta(g, aux_rows, step):
    del aux_rows
    return -_lr_at(learning_rate, step) * g

  return SparseRule("sgd", 0, (), delta,
                    linear_scale=lambda step: -_lr_at(learning_rate, step))


def adagrad_rule(learning_rate, initial_accumulator_value: float = 0.1,
                 eps: float = 1e-7) -> SparseRule:
  """Row-sparse Adagrad matching ``optax.adagrad``'s update rule.

  acc' = acc + g^2; table -= lr * g * rsqrt(acc' + eps) (with optax's
  ``acc' > 0`` guard). acc rides in the fused row, so the whole update is
  one scatter-add of ``[-lr*scaled | g^2]``.
  """

  def delta(g, aux_rows, step):
    acc = aux_rows[..., 0, :]
    g2 = g * g
    acc_new = acc + g2
    scaled = jnp.where(acc_new > 0, g * jax.lax.rsqrt(acc_new + eps), 0.0)
    lr = _lr_at(learning_rate, step)
    return jnp.concatenate([-lr * scaled, g2], axis=-1)

  def delta_lanes(g, aux_list, step):
    (acc,) = aux_list
    g2 = g * g
    acc_new = acc + g2
    scaled = jnp.where(acc_new > 0, g * jax.lax.rsqrt(acc_new + eps), 0.0)
    lr = _lr_at(learning_rate, step)
    return [-lr * scaled, g2]

  return SparseRule("adagrad", 1, (initial_accumulator_value,), delta,
                    delta_lanes=delta_lanes)


def momentum_rule(learning_rate, momentum: float = 0.9,
                  nesterov: bool = False) -> SparseRule:
  """Row-sparse SGD with momentum matching ``optax.sgd(lr, momentum)``.

  m' = momentum * m + g; table -= lr * m' (nesterov: lr * (g + momentum *
  m')). The momentum buffer rides in the fused row, so the whole update is
  one scatter-add of ``[-lr*upd | (momentum-1)*m + g]``. With duplicate
  ids each occurrence reads the forward-time m (per-occurrence semantics,
  see :class:`SparseRule`); the reference gets the same rule from TF's
  ``SGD(momentum=...)`` sparse apply.
  """

  def delta(g, aux_rows, step):
    m = aux_rows[..., 0, :]
    m_new = momentum * m + g
    upd = (g + momentum * m_new) if nesterov else m_new
    lr = _lr_at(learning_rate, step)
    return jnp.concatenate([-lr * upd, m_new - m], axis=-1)

  def delta_lanes(g, aux_list, step):
    (m,) = aux_list
    m_new = momentum * m + g
    upd = (g + momentum * m_new) if nesterov else m_new
    lr = _lr_at(learning_rate, step)
    return [-lr * upd, m_new - m]

  return SparseRule("momentum", 1, (0.0,), delta, delta_lanes=delta_lanes)


def adam_rule(learning_rate, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8, summed: bool = False) -> SparseRule:
  """Row-sparse Adam matching ``optax.adam``'s update rule.

  ``summed=True`` asks the step builders for one update per distinct row
  from the row's summed gradient, which on the rows a batch touches IS
  ``optax.adam`` on the dense table (:attr:`SparseRule.summed`); the default
  keeps the per-occurrence semantics below.

  m' = b1*m + (1-b1)*g; v' = b2*v + (1-b2)*g^2; bias-corrected with
  ``t = step + 1``; table -= lr * m_hat / (sqrt(v_hat) + eps). Both
  moments ride in the fused row (``n_aux=2``), so the whole update is one
  scatter-add of ``[-lr*upd | dm | dv]``. Note Adam's bias correction
  uses the GLOBAL step count as t for every row (optax/TF semantics for
  dense Adam); TF's sparse Adam does the same — rows touched rarely are
  still corrected by the global t.
  """

  def delta(g, aux_rows, step):
    m = aux_rows[..., 0, :]
    v = aux_rows[..., 1, :]
    dm = (1.0 - b1) * (g - m)
    dv = (1.0 - b2) * (g * g - v)
    m_new = m + dm
    v_new = v + dv
    t = (step + 1).astype(jnp.float32)
    m_hat = m_new / (1.0 - jnp.power(b1, t))
    v_hat = v_new / (1.0 - jnp.power(b2, t))
    lr = _lr_at(learning_rate, step)
    upd = m_hat / (jnp.sqrt(v_hat) + eps)
    return jnp.concatenate([-lr * upd, dm, dv], axis=-1)

  def delta_lanes(g, aux_list, step):
    m, v = aux_list
    dm = (1.0 - b1) * (g - m)
    dv = (1.0 - b2) * (g * g - v)
    m_new = m + dm
    v_new = v + dv
    t = (step + 1).astype(jnp.float32)
    m_hat = m_new / (1.0 - jnp.power(b1, t))
    v_hat = v_new / (1.0 - jnp.power(b2, t))
    lr = _lr_at(learning_rate, step)
    upd = m_hat / (jnp.sqrt(v_hat) + eps)
    return [-lr * upd, dm, dv]

  return SparseRule("adam", 2, (0.0, 0.0), delta, delta_lanes=delta_lanes,
                    summed=summed)


_RULES = {"sgd": sgd_rule, "adagrad": adagrad_rule,
          "momentum": momentum_rule, "adam": adam_rule}


def sparse_rule(name: str, learning_rate, **kwargs) -> SparseRule:
  if name not in _RULES:
    raise ValueError(f"Unknown sparse rule {name!r}; have {sorted(_RULES)}")
  return _RULES[name](learning_rate, **kwargs)
