"""Pure-numpy simulator of the Pallas RMW apply kernel's cache algorithm.

`ops/pallas_apply.py` is hardware-only: interpret mode cannot model its
input/output aliasing (an RMW kernel reads stale data there), so its
correctness on duplicates/evictions/flush ordering cannot run in CI. This
module re-implements the EXACT claim/evict/flush state machine of
``_apply_kernel`` in sequential numpy, statement for statement:

  per occurrence j (2x-unrolled pair loop in the kernel — order preserved):
    idx   = ids[j]; valid = 0 <= idx < rows
    slot  = idx & (slots - 1)              (power-of-two direct mapping)
    hit   = valid and tags[slot] == idx
    hit   -> wbuf[slot] += delta[j]
    miss  -> if tags[slot] >= 0:  (evict)
               buf[tags[slot]] = rbuf[slot] + wbuf[slot]  (absolute write)
             rbuf[slot] = buf[idx]                        (refill read)
             wbuf[slot] = delta[j]
             tags[slot] = idx
  flush: every live slot writes buf[tags[slot]] = rbuf[slot] + wbuf[slot]

and, where the call names VMEM-resident head blocks, the wrapper's rewrite
of the id stream (an id inside block k becomes HEAD_ID_BASE + k*head_rows
+ its offset), the blocks' load at start-up, ``hacc[idx & (HEAD_ID_BASE - 1)] += delta[j]``
for such an id (the deltas summed apart from the row, one add at the end),
and the blocks' write-back AFTER the flush. The warm
start (slot s pre-claimed with row s) is modelled too, because the two
meet: rows [0, slots) are head rows of the first table, a warm slot that
no tail id claims is flushed with the value read at start-up, and the
head's write must land after that one (``head_writeback``).

Sequential simulation is faithful BECAUSE of the kernel's ordering
invariant (``pallas_apply.py`` module docstring): every HBM access to one
physical row goes through that row's unique slot, and a slot's claim
sequence waits its previous read and write semaphores — so all accesses
to one row are totally ordered exactly as this loop orders them, and
in-flight DMA only ever touches distinct rows. Any divergence between
this simulator and ``np.add.at`` is therefore a real state-machine bug,
not a timing artifact (the semaphore/pipelining layer is validated on
hardware by ``make chip-smoke``).

The eviction in the kernel writes ``ebuf`` to ``buf_out`` ABSOLUTELY (not
add) — correct because rbuf captured the row's pre-accumulation value and
every intermediate delta for that row accumulated into wbuf. The
simulator mirrors that: write-back REPLACES the row with rbuf + wbuf.
"""

from __future__ import annotations

import numpy as np

HEAD_ID_BASE = 1 << 30  # `pallas_apply.HEAD_ID_BASE` (the twin imports no jax)


def head_slots_sim(ids: np.ndarray, head_starts, rows: int,
                   head_rows: int) -> np.ndarray:
  """numpy twin of ``pallas_apply.head_slots``: per id its row of the head
  scratch, ``-1`` for an id in no block or outside ``[0, rows)``."""
  ids = np.asarray(ids, np.int64)
  slot = np.full(ids.shape, -1, np.int64)
  for k, start in enumerate(np.asarray(head_starts, np.int64)):
    if start > rows - head_rows:
      continue  # HEAD_PAD: the kernel moves no block here
    m = (ids >= start) & (ids < start + head_rows)
    slot[m] = k * head_rows + ids[m] - start
  slot[(ids < 0) | (ids >= rows)] = -1
  return slot


def head_stream_sim(ids: np.ndarray, head_starts, rows: int,
                    head_rows: int) -> np.ndarray:
  """numpy twin of ``pallas_apply.head_stream`` (the wrapper's rewrite)."""
  ids = np.asarray(ids, np.int64)
  slot = head_slots_sim(ids, head_starts, rows, head_rows)
  return np.where(slot >= 0, HEAD_ID_BASE + slot,
                  np.where((ids >= 0) & (ids < rows), ids, -1))


def apply_rows_cached_sim(buf: np.ndarray, ids: np.ndarray,
                          delta: np.ndarray, slots: int = 128,
                          scale=None, warm=None, chunk=None,
                          head_starts=None, head_rows: int = 8,
                          head_writeback: str = "after_flush"
                          ) -> np.ndarray:
  """Sequential-semantics simulation of ``apply_rows_cached``.

  Args:
    buf: [rows, width] float array (copied, not mutated).
    ids: [n] int ids; out-of-range (negative or >= rows) are dropped.
    delta: [n, width] additive updates.
    slots: cache slots, power of two.
    scale: optional scalar multiplier, applied per occurrence as the
      kernel does (``scale * delta[j]``).
    warm: pre-claim slot ``s`` with physical row ``s`` at start-up, as the
      kernel does (default: on when the buffer has at least ``slots``
      rows).
    chunk: ids per grid step (the stream is padded with ``-1`` to a
      multiple of it; the init runs in the first step only, the flush in
      the last). ``None``: one step.
    head_starts: optional starts of the VMEM-resident blocks of
      ``head_rows`` rows each (disjoint; a start past ``rows - head_rows``
      is padding). Their rows are loaded once, accumulate in the head
      scratch and are written back once.
    head_writeback: ``"after_flush"`` is the kernel's order. The naive
      ``"before_flush"`` is kept so a test can show what it loses: a warm
      slot that no tail id claimed rewrites its row after the head did.

  Returns:
    The updated buffer; must equal ``np.add.at(buf, valid_ids, deltas)``
    up to f32 summation order.
  """
  if slots & (slots - 1):
    raise ValueError(f"slots must be a power of two, got {slots}")
  if head_writeback not in ("after_flush", "before_flush"):
    raise ValueError(head_writeback)
  buf = np.array(buf, dtype=np.float64 if buf.dtype == np.float64
                 else np.float32)
  rows, width = buf.shape
  if warm is None:
    warm = rows >= slots
  ids = np.asarray(ids, np.int64)
  delta = np.asarray(delta, buf.dtype)
  n = ids.shape[0]
  chunk = max(n, 1) if chunk is None else chunk
  pad = (-n) % chunk
  ids = np.concatenate([ids, np.full((pad,), -1, np.int64)])
  delta = np.concatenate([delta, np.zeros((pad, width), buf.dtype)])

  # the wrapper: an id inside a resident block becomes HEAD_ID_BASE + its
  # row of the head scratch; every other id outside the buffer becomes -1
  starts = [] if head_starts is None else [int(s) for s in head_starts]
  live = [k for k, s in enumerate(starts) if s <= rows - head_rows]
  if starts:
    ids = head_stream_sim(ids, starts, rows, head_rows)

  tags = np.full((slots,), -1, np.int64)
  rbuf = np.zeros((slots, width), buf.dtype)
  wbuf = np.zeros((slots, width), buf.dtype)
  head = np.zeros((len(starts) * head_rows, width), buf.dtype)
  hacc = np.zeros_like(head)  # a head row's deltas, summed apart from it

  def row_delta(j):
    return delta[j] if scale is None else buf.dtype.type(scale) * delta[j]

  def write_heads():
    head[:] = head + hacc
    for k in live:
      buf[starts[k]:starts[k] + head_rows] = \
          head[k * head_rows:(k + 1) * head_rows]

  for c in range(ids.shape[0] // chunk):
    if c == 0:  # _init
      for k in live:
        head[k * head_rows:(k + 1) * head_rows] = \
            buf[starts[k]:starts[k] + head_rows]
      if warm:
        for s in range(slots):
          tags[s] = s
          rbuf[s] = buf[s]  # and the row is written back unchanged

    for j in range(c * chunk, (c + 1) * chunk):
      idx = int(ids[j])
      if starts and idx >= HEAD_ID_BASE:  # a head row: no tag, no DMA
        hacc[idx & (HEAD_ID_BASE - 1)] += row_delta(j)
        continue
      if not 0 <= idx < rows:
        continue
      slot = idx & (slots - 1)
      if tags[slot] == idx:  # hit
        wbuf[slot] += row_delta(j)
        continue
      # miss: evict the previous occupant (if any), then claim
      if tags[slot] >= 0:
        buf[tags[slot]] = rbuf[slot] + wbuf[slot]
      rbuf[slot] = buf[idx]
      wbuf[slot] = row_delta(j)
      tags[slot] = idx

  # _flush, in the last grid step
  if head_writeback == "before_flush":
    write_heads()
  for slot in range(slots):
    if tags[slot] >= 0:
      buf[tags[slot]] = rbuf[slot] + wbuf[slot]
  if head_writeback == "after_flush":
    write_heads()
  return buf
