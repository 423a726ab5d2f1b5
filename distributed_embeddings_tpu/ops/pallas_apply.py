"""Pallas TPU kernel for the sparse-apply scatter: ``buf[ids] += delta``.

The apply phase is the single most expensive op of sparse embedding
training on TPU: XLA's scatter-add runs a conservative serial update loop
measured at ~75 ns/row on v5e regardless of uniqueness, sortedness, or
buffer size (`tools/profile_scatter2.py`), while XLA's *gather* pipelines
to ~10 ns/row. This kernel replaces the scatter's role of the reference's
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
  sort/unique dedup, without the sort (measured ~200 ns/element here).

Correctness argument for duplicates: every operation on physical row ``r``
(refill read, delta accumulation, eviction write) goes through the single
cache slot ``r % slots``, and a slot's claim sequence waits the slot's
previous write and read semaphores before reusing its buffers — so all
HBM accesses to one row are totally ordered, and concurrent in-flight DMA
only ever touches distinct rows. Additive per-occurrence semantics match
``jnp.ndarray.at[].add`` up to f32 summation order.

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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

# the kernel's name in HLO (the Mosaic custom call) and in device traces
KERNEL_NAME = "de_apply_rows_cached"


def _apply_kernel(slots, chunk, scaled, warm, unroll,
                  *refs):
  if scaled:
    # delta = scale * g computed in-kernel (the SGD fast path): skips the
    # HBM materialization of a separate delta array AND the
    # optimization_barrier staging the XLA path needs
    (ids_ref, buf_in, delta_ref, scale_ref, buf_out,
     tags, wrote, rbuf, wbuf, ebuf, rsem, wsem) = refs
  else:
    (ids_ref, buf_in, delta_ref, buf_out,
     tags, wrote, rbuf, wbuf, ebuf, rsem, wsem) = refs
  c = pl.program_id(0)
  nc = pl.num_programs(0)
  rows = buf_in.shape[0]

  @pl.when(c == 0)
  def _init():
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

  def row_delta(j):
    d = delta_ref[pl.ds(j, 1), :]
    return scale_ref[0] * d if scaled else d

  def occurrence(j, _):
    idx = ids_ref[j]
    valid = jnp.logical_and(idx >= 0, idx < rows)
    # slots is a power of two: AND beats the scalar-core's rem/div by ~10
    # cycles on a path that runs once per occurrence
    slot = jnp.where(valid, jnp.bitwise_and(idx, slots - 1), 0)
    tag = tags[slot]
    hit = jnp.logical_and(valid, tag == idx)

    @pl.when(hit)
    def _hit():
      wbuf[pl.ds(slot, 1), :] = wbuf[pl.ds(slot, 1), :] + row_delta(j)

    @pl.when(jnp.logical_and(valid, jnp.logical_not(hit)))
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
          # the slot's previous eviction write must be done before ebuf is
          # overwritten (also orders all HBM writes of one row)
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


def apply_rows_cached(buf: jax.Array, ids: jax.Array, delta: jax.Array,
                      slots: int = 128, chunk: Optional[int] = None,
                      scale: Optional[jax.Array] = None,
                      warm: Optional[bool] = None,
                      unroll: int = 8,
                      interpret: bool = False) -> jax.Array:
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
  kernel = functools.partial(_apply_kernel, slots, chunk, scaled, warm,
                             unroll)
  in_specs = [
      pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.SMEM),
      pl.BlockSpec(memory_space=pl.ANY),  # buf (aliased)
      pl.BlockSpec((chunk, w), lambda i: (i, 0)),
  ]
  operands = [ids, buf, delta]
  if scaled:
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(jnp.reshape(scale, (1,)).astype(jnp.float32))
  return pl.pallas_call(
      kernel,
      grid=((n + pad) // chunk,),
      in_specs=in_specs,
      out_specs=pl.BlockSpec(memory_space=pl.ANY),
      out_shape=out_struct(buf.shape, buf.dtype, *operands),
      scratch_shapes=[
          pltpu.SMEM((slots,), jnp.int32),
          pltpu.SMEM((slots,), jnp.int32),
          pltpu.VMEM((slots, w), jnp.float32),
          pltpu.VMEM((slots, w), jnp.float32),
          pltpu.VMEM((slots, w), jnp.float32),
          pltpu.SemaphoreType.DMA((slots,)),
          pltpu.SemaphoreType.DMA((slots,)),
      ],
      input_output_aliases={1: 0},
      compiler_params=pltpu.CompilerParams(has_side_effects=True),
      interpret=interpret,
      name=KERNEL_NAME,
  )(*operands)
