"""Pallas TPU kernel for the sparse-apply scatter: ``buf[ids] += delta``.

The apply phase is the single most expensive op of sparse embedding
training on TPU: XLA's scatter-add runs a conservative serial update loop
measured at ~75 ns/row on v5e regardless of uniqueness, sortedness, or
buffer size (controlled sweeps, docs/BENCHMARKS.md), while XLA's *gather*
pipelines to ~10 ns/row. This kernel replaces the scatter's role of the reference's
fused-backward + sparse-optimizer-apply pipeline
(`/root/reference/distributed_embeddings/cc/kernels/embedding_lookup_kernels.cu:464-633`
plus TF sparse applies) with a DMA read-modify-write pipeline:

- per occurrence, the target row is fetched HBM->VMEM, the delta added on
  the VPU, and the row written back — with reads, adds, and writes of
  different rows deeply overlapped (the scalar core's DMA-issue rate is
  the bound, ~50 ns/row, 1.5x faster than XLA's scatter);
- a **direct-mapped write-back row cache** (``slots`` rows of VMEM, tag =
  row id, one slot per row via ``row % slots``) makes the kernel exact for
  duplicate ids AND fast on power-law id streams: repeated hot ids combine
  in VMEM at ~10 ns (no DMA at all) instead of serializing HBM
  round-trips — the skew-robustness the reference gets from its
  sort/unique dedup, without the sort (measured ~200 ns/element here);
- **VMEM-resident heads** (optional, ``head_starts``): blocks of
  ``HEAD_ROWS`` physical rows, one at the start of each table of the class,
  where a frequency-sorted vocabulary (rank = id) keeps its hot rows. Each
  block is loaded with ONE contiguous DMA when the kernel starts, its
  occurrences' deltas are summed in VMEM beside it (``hacc[h] += scale *
  delta[j]``: no tag, no semaphore, no per-row DMA; summed apart from the
  row and added to it once, as the row cache does with a slot's, so a hot
  row's thousand small deltas are not each rounded at the weight's
  magnitude) and it goes back with ONE contiguous DMA at the end. The row cache is 128 rows deep because every slot costs two DMA
  semaphores; a head row costs none, so the 60-70% of a power-law stream
  that lands on a table's first few thousand rows stops paying two row
  DMAs each. The block starts are DATA (a small int32 operand): under
  ``shard_map`` every rank holds other tables. Which occurrence is a head
  occurrence is decided outside the kernel, in XLA: the wrapper rewrites
  the id of a row inside block ``k`` to ``HEAD_ID_BASE + k * HEAD_ROWS +
  offset`` (same stream, same length), so the kernel's test is one compare
  (``idx >= HEAD_ID_BASE``) however many blocks there are.

Correctness argument for duplicates: every operation on physical row ``r``
(refill read, delta accumulation, eviction write) goes through the single
cache slot ``r % slots``, and a slot's claim sequence waits the slot's
previous write and read semaphores before reusing its buffers — so all
HBM accesses to one row are totally ordered, and concurrent in-flight DMA
only ever touches distinct rows. Additive per-occurrence semantics match
``jnp.ndarray.at[].add`` up to f32 summation order.

Ordering argument for the heads: the blocks are disjoint, and the rewrite
sends EVERY occurrence of a block's row to the head, so no occurrence of
such a row ever claims a cache slot. The one way a head row still reaches
the row cache is the warm start, which pre-claims slot ``s`` with row
``s``: rows ``[0, slots)`` are head rows of the class's first table. Such
a slot holds ``rbuf`` = the row as read at kernel start and ``wbuf`` = 0,
so whenever it is evicted or flushed it writes the row's ORIGINAL value.
Those writes are harmless while they precede the head's own: the heads are
therefore written back only after the flush has waited every cache write
(``_flush``: start all, wait all, then the blocks). Written the other way
round, a stream too short to claim every warm slot loses the step's update
of rows ``[0, slots)`` (`tests/test_pallas_apply_sim.py` keeps that case).
The head's load reads rows the warm start is rewriting with their own
values, which is the same read-while-rewritten-unchanged the warm start
already does to itself.

Used by the lookup engine for every packed layout: wide classes
(``rows_per_phys == 1``) pass their updates straight through; narrow
classes (rpp > 1) pass lane-EXPANDED updates so the kernel works at
physical-row granularity (disjoint sub-row windows accumulate exactly;
``packed_table.scatter_add_fused``). Dispatch is the static scatter-regime
rule in ``lookup_engine.apply_sparse``; ``DE_TPU_PALLAS_APPLY=0/1``
force-overrides (kernel requires a real TPU).
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

# the kernel's name in HLO (the Mosaic custom call) and in device traces
KERNEL_NAME = "de_apply_rows_cached"

# H: physical rows of one VMEM-resident head block (512 B a row at the
# kernel's 128 lanes). Chosen on the v5e in `dlrm_train_1chip` from
# {1024, 2048, 4096, 8192} (PERF.md, PR 27)
HEAD_ROWS = 8192
# all resident blocks of one call together (each row twice: the row and the
# sum of its deltas): VMEM the heads may take of the chip's 128 MiB, next to
# the 8 MiB delta block and the row cache
HEAD_VMEM_BYTES = 64 << 20
# a block start that marks padding (a rank with fewer blocks than another)
HEAD_PAD = np.iinfo(np.int32).max
# In the kernel's id stream ``HEAD_ID_BASE + h`` names row ``h`` of the head
# scratch: a constant far past any buffer (2^31 elements at 128 lanes are
# 2^24 rows) and NOT the buffer's own row count. With ``rows + h`` the
# kernel hung the v5e for every buffer of 29,576 to 262,144 rows tried and
# ran for 1.6 M rows and more, the same program but for that constant
# (PERF.md, PR 27; the cause is not known)
HEAD_ID_BASE = 1 << 30


def head_block_starts(intervals, rows: int, head_rows: int = HEAD_ROWS,
                      row_bytes: int = 512) -> List[int]:
  """Disjoint resident blocks that cover the given head intervals.

  ``intervals``: ``(lo, hi)`` physical-row ranges worth keeping resident
  (each table's first rows). Returns the starts of blocks of ``head_rows``
  rows each, ascending, disjoint, multiples of 8 (the HBM tiling) and
  inside ``[0, rows)``: one block per table whose interval is a full
  ``head_rows``; a shorter table's block runs on into its neighbour, whose
  own block then starts where that one ends. A row in no block takes the
  row cache, so a block that cannot be placed (the buffer's last rows, the
  VMEM budget) costs time and never correctness.
  """
  starts: List[int] = []
  end = 0
  last = (rows - head_rows) // 8 * 8
  for lo, hi in sorted(intervals):
    pos = max(lo // 8 * 8, end)
    # a remainder under one tile (a block that had to start up to 7 rows
    # before its table) is left to the row cache, not given a block
    while hi - pos >= 8 and (len(starts) + 1) * head_rows * 2 * row_bytes \
        <= HEAD_VMEM_BYTES:
      start = min(pos, last)
      if start < end:
        break
      starts.append(start)
      pos = end = start + head_rows
  return starts


def head_slots(ids: jax.Array, head_starts: jax.Array, rows: int,
               head_rows: int = HEAD_ROWS) -> jax.Array:
  """Per id its row of the head scratch (block ``k`` holds rows
  ``[k * head_rows, (k + 1) * head_rows)``), ``-1`` for an id in no block
  or outside ``[0, rows)``. A start past ``rows - head_rows`` (``HEAD_PAD``)
  is no block: the kernel moves nothing there. Elementwise over the
  stream: no gather."""
  slot = jnp.full_like(ids, -1)
  for k in range(head_starts.shape[0]):
    off = ids - head_starts[k]
    inside = (off >= 0) & (off < head_rows) \
        & (head_starts[k] <= rows - head_rows)
    slot = jnp.where(inside, k * head_rows + off, slot)
  return jnp.where((ids >= 0) & (ids < rows), slot, -1)


def _apply_kernel(slots, chunk, scaled, warm, unroll, head_blocks,
                  head_rows, *refs):
  refs = list(refs)
  ids_ref, buf_in, delta_ref = refs[:3]
  del refs[:3]
  # delta = scale * g computed in-kernel (the SGD fast path): skips the
  # HBM materialization of a separate delta array AND the
  # optimization_barrier staging the XLA path needs
  scale_ref = refs.pop(0) if scaled else None
  hstart_ref = refs.pop(0) if head_blocks else None
  buf_out, tags, wrote, rbuf, wbuf, ebuf, rsem, wsem = refs[:8]
  head, hacc, hsem = refs[8:] if head_blocks else (None, None, None)
  c = pl.program_id(0)
  nc = pl.num_programs(0)
  rows = buf_in.shape[0]

  def head_dmas(write_back, then):
    # one contiguous DMA per resident block; a start past
    # ``rows - head_rows`` is padding (another rank holds more tables)
    for k in range(head_blocks):
      start = hstart_ref[k]

      @pl.when(start <= rows - head_rows)
      def _(k=k, start=start):
        vmem = head.at[pl.ds(k * head_rows, head_rows), :]
        hbm = (buf_out if write_back else buf_in).at[
            pl.ds(pl.multiple_of(start, 8), head_rows), :]
        then(pltpu.make_async_copy(vmem, hbm, hsem.at[k]) if write_back
             else pltpu.make_async_copy(hbm, vmem, hsem.at[k]))

  def head_tiles(body):
    # the head scratches in pieces of 256 rows (a block is a multiple of 8)
    step = math.gcd(256, head_rows)

    def piece(i, _):
      body(pl.ds(pl.multiple_of(i * step, 8), step))
      return 0
    jax.lax.fori_loop(0, head_blocks * head_rows // step, piece, 0)

  @pl.when(c == 0)
  def _init():
    head_dmas(False, lambda dma: dma.start())
    if head_blocks:
      def clear(rows_):
        hacc[rows_, :] = jnp.zeros_like(hacc[rows_, :])
      head_tiles(clear)
    if warm:
      # pre-claim slot s with physical row s (row s maps to slot s):
      # every slot then holds a valid tag with a write in flight, so the
      # steady-state claim path needs NO cold-slot branches — the row is
      # written back unchanged (wbuf = 0), which is harmless and ordered
      # with any later update of row s through the same slot
      def body(s, _):
        tags[s] = s
        wrote[s] = 1
        wbuf[pl.ds(s, 1), :] = jnp.zeros_like(wbuf[pl.ds(s, 1), :])
        pltpu.make_async_copy(
            buf_in.at[pl.ds(s, 1), :], rbuf.at[pl.ds(s, 1), :],
            rsem.at[s]).start()
        return 0
      jax.lax.fori_loop(0, slots, body, 0)

      def body2(s, _):
        pltpu.make_async_copy(
            buf_in.at[pl.ds(0, 1), :], rbuf.at[pl.ds(s, 1), :],
            rsem.at[s]).wait()
        ebuf[pl.ds(s, 1), :] = rbuf[pl.ds(s, 1), :]
        pltpu.make_async_copy(
            ebuf.at[pl.ds(s, 1), :], buf_out.at[pl.ds(s, 1), :],
            wsem.at[s]).start()
        # leave a fresh read in flight so the steady-state rsem.wait pairs
        # with exactly one outstanding read per slot
        pltpu.make_async_copy(
            buf_in.at[pl.ds(s, 1), :], rbuf.at[pl.ds(s, 1), :],
            rsem.at[s]).start()
        return 0
      jax.lax.fori_loop(0, slots, body2, 0)
    else:
      def body(s, _):
        tags[s] = -1
        wrote[s] = 0
        return 0
      jax.lax.fori_loop(0, slots, body, 0)
    head_dmas(False, lambda dma: dma.wait())

  def row_delta(j):
    d = delta_ref[pl.ds(j, 1), :]
    return scale_ref[0] * d if scaled else d

  def occurrence(j, _):
    idx = ids_ref[j]
    valid = jnp.logical_and(idx >= 0, idx < rows)

    def row_cache(guarded):
      # ``guarded``: the caller's branch already holds ``valid``
      if_valid = (lambda x: x) if guarded else (
          lambda x: jnp.logical_and(valid, x))
      # slots is a power of two: AND beats the scalar-core's rem/div by ~10
      # cycles on a path that runs once per occurrence
      slot = jnp.bitwise_and(idx, slots - 1)
      slot = slot if guarded else jnp.where(valid, slot, 0)
      tag = tags[slot]
      hit = if_valid(tag == idx)

      def _hit():
        wbuf[pl.ds(slot, 1), :] = wbuf[pl.ds(slot, 1), :] + row_delta(j)

      def _claim():
        if warm:
          # warm slots always hold a valid tag with one read and one write
          # outstanding — evict unconditionally, no cold branches
          pltpu.make_async_copy(
              buf_in.at[pl.ds(0, 1), :], rbuf.at[pl.ds(slot, 1), :],
              rsem.at[slot]).wait()
          pltpu.make_async_copy(
              ebuf.at[pl.ds(slot, 1), :], buf_out.at[pl.ds(0, 1), :],
              wsem.at[slot]).wait()
          ebuf[pl.ds(slot, 1), :] = rbuf[pl.ds(slot, 1), :] \
              + wbuf[pl.ds(slot, 1), :]
          pltpu.make_async_copy(
              ebuf.at[pl.ds(slot, 1), :], buf_out.at[pl.ds(tag, 1), :],
              wsem.at[slot]).start()
        else:
          # previous refill read of this slot must have landed before rbuf
          # is summed into the eviction staging
          @pl.when(tag >= 0)
          def _evict():
            pltpu.make_async_copy(
                buf_in.at[pl.ds(0, 1), :], rbuf.at[pl.ds(slot, 1), :],
                rsem.at[slot]).wait()
            # the slot's previous eviction write must be done before ebuf
            # is overwritten (also orders all HBM writes of one row)
            @pl.when(wrote[slot] == 1)
            def _():
              pltpu.make_async_copy(
                  ebuf.at[pl.ds(slot, 1), :], buf_out.at[pl.ds(0, 1), :],
                  wsem.at[slot]).wait()
            ebuf[pl.ds(slot, 1), :] = rbuf[pl.ds(slot, 1), :] \
                + wbuf[pl.ds(slot, 1), :]
            pltpu.make_async_copy(
                ebuf.at[pl.ds(slot, 1), :], buf_out.at[pl.ds(tag, 1), :],
                wsem.at[slot]).start()
            wrote[slot] = 1

        pltpu.make_async_copy(
            buf_in.at[pl.ds(idx, 1), :], rbuf.at[pl.ds(slot, 1), :],
            rsem.at[slot]).start()
        wbuf[pl.ds(slot, 1), :] = row_delta(j)
        tags[slot] = idx

      if guarded:
        # a valid id either hits or claims: one branch with two arms
        jax.lax.cond(hit, _hit, _claim)
      else:
        pl.when(hit)(_hit)
        pl.when(if_valid(jnp.logical_not(hit)))(_claim)

    def head_row():
      # the wrapper rewrote an id that lies in a resident block to
      # ``HEAD_ID_BASE + its row of the head scratch``: no tag, no DMA, no
      # semaphore
      h = jnp.bitwise_and(idx, HEAD_ID_BASE - 1)
      hacc[pl.ds(h, 1), :] = hacc[pl.ds(h, 1), :] + row_delta(j)

    if head_blocks:
      # two branches side by side; under the first the row cache's hit and
      # claim as the two arms of one more. The forms were measured on the
      # v5e (PERF.md PR 27): every extra ``if`` on an occurrence's path
      # costs the scalar core 1-2 ns, and a head occurrence that skips the
      # tag load and the cache's branches costs 19-20 ns against a hit's 27
      pl.when(valid)(lambda: row_cache(True))
      pl.when(idx >= HEAD_ID_BASE)(head_row)
    else:
      row_cache(False)
    return 0

  def group(p, _):  # manual unroll cuts the fori_loop bookkeeping
    for u in range(unroll):
      occurrence(unroll * p + u, 0)
    return 0

  jax.lax.fori_loop(0, chunk // unroll, group, 0)

  @pl.when(c == nc - 1)
  def _flush():
    # two passes: start every slot's eviction write first (the per-slot
    # rsem/wsem waits there are for long-finished ops), then wait them
    # all — the writes overlap instead of serializing on HBM latency
    def start_one(s, _):
      @pl.when(tags[s] >= 0)
      def _():
        pltpu.make_async_copy(
            buf_in.at[pl.ds(0, 1), :], rbuf.at[pl.ds(s, 1), :],
            rsem.at[s]).wait()
        @pl.when(wrote[s] == 1)
        def _():
          pltpu.make_async_copy(
              ebuf.at[pl.ds(s, 1), :], buf_out.at[pl.ds(0, 1), :],
              wsem.at[s]).wait()
        ebuf[pl.ds(s, 1), :] = rbuf[pl.ds(s, 1), :] + wbuf[pl.ds(s, 1), :]
        pltpu.make_async_copy(
            ebuf.at[pl.ds(s, 1), :], buf_out.at[pl.ds(tags[s], 1), :],
            wsem.at[s]).start()
        wrote[s] = 1
      return 0

    def wait_one(s, _):
      @pl.when(jnp.logical_and(tags[s] >= 0, wrote[s] == 1))
      def _():
        pltpu.make_async_copy(
            ebuf.at[pl.ds(s, 1), :], buf_out.at[pl.ds(0, 1), :],
            wsem.at[s]).wait()
      return 0

    jax.lax.fori_loop(0, slots, start_one, 0)
    jax.lax.fori_loop(0, slots, wait_one, 0)
    # the heads go back only now, when every write of the row cache has
    # landed: a warm slot no tail id claimed has just rewritten its row
    # (a head row of the first table) with the value read at kernel start
    if head_blocks:
      # the deltas of a head row were summed apart from the row, as the
      # row cache sums a slot's: ONE add to the weight, whatever the count
      def add(rows_):
        head[rows_, :] = head[rows_, :] + hacc[rows_, :]
      head_tiles(add)
    head_dmas(True, lambda dma: dma.start())
    head_dmas(True, lambda dma: dma.wait())


def head_stream(ids: jax.Array, head_starts: jax.Array, rows: int,
                head_rows: int = HEAD_ROWS) -> jax.Array:
  """The id stream as the kernel with heads reads it: ``HEAD_ID_BASE + h``
  for an id whose row is row ``h`` of the head scratch, the id itself for
  any other row of the buffer, ``-1`` (dropped) for everything else."""
  slot = head_slots(ids, head_starts, rows, head_rows)
  return jnp.where(slot >= 0, HEAD_ID_BASE + slot,
                   jnp.where((ids >= 0) & (ids < rows), ids, -1))


def apply_rows_cached(buf: jax.Array, ids: jax.Array, delta: jax.Array,
                      slots: int = 128, chunk: Optional[int] = None,
                      scale: Optional[jax.Array] = None,
                      warm: Optional[bool] = None,
                      unroll: int = 8,
                      interpret: bool = False,
                      head_starts: Optional[jax.Array] = None,
                      head_rows: int = HEAD_ROWS) -> jax.Array:
  """``buf[ids[i]] += scale * delta[i]`` (rows), exact for duplicates.

  Args:
    buf: [rows, width] f32, width a multiple of 128 lanes. Donated.
    ids: [n] int32 physical row ids; out-of-range ids are dropped.
    delta: [n, width] additive updates.
    scale: optional scalar multiplier computed in-kernel (``None`` = 1).
      Lets scale-only update rules (SGD: delta = -lr * g) pass the raw
      cotangent straight in, skipping the HBM delta materialization and
      its optimization_barrier staging.
    warm: pre-claim every cache slot with its same-numbered physical row
      at startup, which removes the two cold-slot branches from the
      steady-state claim path (scalar-core cycles on the per-occurrence
      critical path). Default: on when the buffer has at least ``slots``
      rows (the init touches rows ``[0, slots)``), off otherwise.
    unroll: occurrences per fori_loop body (loop-bookkeeping amortization).
    slots: cache slots (VMEM use = 3 * slots * width * 4 bytes; DMA
      semaphore use = 2 * slots of the chip's ~512-semaphore budget).
    chunk: ids per grid step. Default scales with row width so the
      double-buffered delta block stays ~8 MiB of VMEM. Note small inputs
      (n <= 8192) always run as ONE grid block covering the whole padded
      array regardless of this argument — XLA lays out small 1-D int
      arrays as a single tile, which a partial block would mismatch.
    head_starts: optional ``[K]`` int32 starts (data, so each rank of a
      ``shard_map`` passes its own) of disjoint blocks of ``head_rows``
      physical rows that stay resident in VMEM for the whole call
      (:func:`head_block_starts`; ``HEAD_PAD`` marks an unused entry).
      ``None``: the kernel without heads, argument for argument.

  Returns:
    The updated buffer (aliases ``buf``). Call under ``jit`` with ``buf``
    donated for a true in-place update.
  """
  n = ids.shape[0]
  w = buf.shape[1]
  if slots & (slots - 1):
    raise ValueError(f"slots must be a power of two, got {slots}")
  if chunk is not None and chunk % 128:
    # multiple of 128 for the SMEM block layout (unroll divisibility is
    # checked separately below)
    raise ValueError(f"chunk must be a multiple of 128, got {chunk}")
  if delta.shape != (n, w):
    raise ValueError(f"delta shape {delta.shape} != ({n}, {w})")
  if buf.dtype != jnp.float32:
    raise ValueError(f"buf must be float32 (got {buf.dtype}): the kernel's "
                     "VMEM row cache is f32")
  if chunk is None:
    # keep the double-buffered delta block ~8 MiB regardless of row width
    chunk = min(8192, max(128, ((1 << 20) // w) // 128 * 128))
  # XLA lays out small 1-D int arrays as one tile T(n); a partial SMEM
  # block then mismatches Mosaic's T(chunk) expectation. Small inputs
  # (tests) therefore run as ONE block covering the whole padded array;
  # production sizes (n >= 64k) use `chunk`-sized blocks, whose T(128)-
  # aligned layouts agree.
  if n <= 8192:
    chunk = max(128, -(-n // 128) * 128)
  pad = (-n) % chunk
  if pad:
    ids = jnp.concatenate([ids, jnp.full((pad,), -1, ids.dtype)])
    delta = jnp.concatenate(
        [delta, jnp.zeros((pad, w), delta.dtype)])
  if unroll < 1:
    raise ValueError(f"unroll must be >= 1, got {unroll}")
  if chunk % unroll:
    raise ValueError(f"chunk {chunk} not divisible by unroll {unroll}")
  if warm is None:
    warm = buf.shape[0] >= slots
  elif warm and buf.shape[0] < slots:
    raise ValueError(f"warm init touches rows [0, {slots}) but the buffer "
                     f"has only {buf.shape[0]} rows")
  scaled = scale is not None
  head_blocks = 0 if head_starts is None else head_starts.shape[0]
  if head_blocks and (head_rows % 8 or head_rows > buf.shape[0]):
    raise ValueError(f"head_rows {head_rows} must be a multiple of 8 and at "
                     f"most the buffer's {buf.shape[0]} rows")
  kernel = functools.partial(_apply_kernel, slots, chunk, scaled, warm,
                             unroll, head_blocks, head_rows)
  in_specs = [
      pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.SMEM),
      pl.BlockSpec(memory_space=pl.ANY),  # buf (aliased)
      pl.BlockSpec((chunk, w), lambda i: (i, 0)),
  ]
  operands = [ids, buf, delta]
  if scaled:
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(jnp.reshape(scale, (1,)).astype(jnp.float32))
  scratch = [
      pltpu.SMEM((slots,), jnp.int32),
      pltpu.SMEM((slots,), jnp.int32),
      pltpu.VMEM((slots, w), jnp.float32),
      pltpu.VMEM((slots, w), jnp.float32),
      pltpu.VMEM((slots, w), jnp.float32),
      pltpu.SemaphoreType.DMA((slots,)),
      pltpu.SemaphoreType.DMA((slots,)),
  ]
  params = {}
  if head_blocks:
    # an id inside a block becomes ``HEAD_ID_BASE + its row of the head
    # scratch``: past any buffer for the row cache's test, one compare and
    # one AND for the head's (so every other id outside the buffer becomes
    # -1). Same length, same operand: the stream is only rewritten
    head_starts = head_starts.astype(jnp.int32)
    operands[0] = head_stream(ids, head_starts, buf.shape[0], head_rows)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(head_starts)
    scratch += [pltpu.VMEM((head_blocks * head_rows, w), jnp.float32),
                pltpu.VMEM((head_blocks * head_rows, w), jnp.float32),
                pltpu.SemaphoreType.DMA((head_blocks,))]
    params["vmem_limit_bytes"] = 4 * w * (
        2 * head_blocks * head_rows + 3 * slots + 2 * chunk) + (4 << 20)
  return pl.pallas_call(
      kernel,
      grid=((n + pad) // chunk,),
      in_specs=in_specs,
      out_specs=pl.BlockSpec(memory_space=pl.ANY),
      out_shape=out_struct(buf.shape, buf.dtype, *operands),
      scratch_shapes=scratch,
      input_output_aliases={1: 0},
      compiler_params=pltpu.CompilerParams(has_side_effects=True, **params),
      interpret=interpret,
      name=KERNEL_NAME,
  )(*operands)
