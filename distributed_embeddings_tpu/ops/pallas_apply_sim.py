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


def apply_rows_cached_sim(buf: np.ndarray, ids: np.ndarray,
                          delta: np.ndarray, slots: int = 128) -> np.ndarray:
  """Sequential-semantics simulation of ``apply_rows_cached``.

  Args:
    buf: [rows, width] float array (copied, not mutated).
    ids: [n] int ids; out-of-range (negative or >= rows) are dropped.
    delta: [n, width] additive updates.
    slots: cache slots, power of two.

  Returns:
    The updated buffer; must equal ``np.add.at(buf, valid_ids, deltas)``
    up to f32 summation order.
  """
  if slots & (slots - 1):
    raise ValueError(f"slots must be a power of two, got {slots}")
  buf = np.array(buf, dtype=np.float64 if buf.dtype == np.float64
                 else np.float32)
  rows, width = buf.shape
  n = ids.shape[0]
  tags = np.full((slots,), -1, np.int64)
  rbuf = np.zeros((slots, width), buf.dtype)
  wbuf = np.zeros((slots, width), buf.dtype)

  for j in range(n):
    idx = int(ids[j])
    valid = 0 <= idx < rows
    if not valid:
      continue
    slot = idx & (slots - 1)
    if tags[slot] == idx:  # hit
      wbuf[slot] += delta[j]
      continue
    # miss: evict the previous occupant (if any), then claim
    if tags[slot] >= 0:
      buf[tags[slot]] = rbuf[slot] + wbuf[slot]
    rbuf[slot] = buf[idx]
    wbuf[slot] = delta[j]
    tags[slot] = idx

  for slot in range(slots):  # flush
    if tags[slot] >= 0:
      buf[tags[slot]] = rbuf[slot] + wbuf[slot]
  return buf
