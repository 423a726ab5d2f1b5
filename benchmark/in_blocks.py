"""The harness's host arithmetic on a large leaf, a block of it at a time.

`weights.rows_np`, `reference.update` and `reference.stored_change` compute a
whole leaf at once, every intermediate a fresh array of the leaf's size (170
to 390 MB at a 3840 x 11008 leaf). On the one-chip machine every page of
every such array is faulted in anew, and at the 7.2e8 dense values of
``olmo_hybrid_train_1chip`` that was most of a run: the weights hashed twice
(reference and fill), 75 s each, and the reference's float64 Adam and rounding,
95 s, of a run of 410-450 s that the driver cuts at 360 (PERF.md, PR 33).

All three are elementwise over rows, so the same function on blocks of rows
gives the same bits. :func:`install` puts in their place wrappers that hand a
leaf of more than ``BLOCK`` values to the harness's own function a block at a
time, on a few threads (numpy releases the interpreter in its loops), and
anything smaller to the function as it is. Nothing compared changes by a bit
(`tests/benchmark/test_bench_olmo_family.py`); what changes is the time and
the peak of host memory. A family that needs it calls :func:`install` from
its ``model_spec``; the others run the harness as it was. The next
``benchmark`` issue should block the three functions where they live and
delete this file (PERF.md section 7).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import reference, weights

BLOCK = 1 << 20   # values a block: intermediates of 4-8 MB, reused by malloc
WORKERS = min(8, os.cpu_count() or 1)


def _each_block(fill, n: int, step: int) -> None:
  with ThreadPoolExecutor(WORKERS) as pool:
    list(pool.map(fill, range(0, n, step)))   # list(): a block's error is raised


def _flatwise(fn, arrays):
  """``fn(*arrays) -> tuple of arrays``, elementwise over equal shapes: the
  same values, computed ``BLOCK`` of them at a time."""
  shape = arrays[0].shape
  flat = [np.ascontiguousarray(a).reshape(-1) for a in arrays]
  cut = lambda a: [f[a:a + BLOCK] for f in flat]
  first = fn(*cut(0))
  outs = [np.empty(flat[0].shape, x.dtype) for x in first]

  def fill(a):
    for out, x in zip(outs, first if a == 0 else fn(*cut(a))):
      out[a:a + BLOCK] = x
  _each_block(fill, flat[0].size, BLOCK)
  return [out.reshape(shape) for out in outs]


def install() -> None:
  """Idempotent; for the rest of the process."""
  if hasattr(weights.rows_np, "whole"):
    return
  rows_np, update, stored_change = (
      weights.rows_np, reference.update, reference.stored_change)

  def rows_in_blocks(key, scale, rows, width):
    rows = np.asarray(rows)
    step = max(1, BLOCK // max(int(width), 1))
    if len(rows) <= step:
      return rows_np(key, scale, rows, width)
    out = np.empty((len(rows), width), np.float32)

    def fill(a):
      out[a:a + step] = rows_np(key, scale, rows[a:a + step], width)
    _each_block(fill, len(rows), step)
    return out

  def update_in_blocks(opt, g):
    if np.size(g) <= BLOCK:
      return update(opt, g)

    def flat(x):
      change, acc = update(opt, x)
      return (change, *acc)
    change, *acc = _flatwise(flat, [np.asarray(g)])
    return change, tuple(acc)

  def stored_change_in_blocks(before, change):
    if np.size(change) <= BLOCK or np.shape(before) != np.shape(change):
      return stored_change(before, change)
    return _flatwise(lambda b, c: (stored_change(b, c),),
                     [np.asarray(before), np.asarray(change)])[0]

  for fn, whole in ((rows_in_blocks, rows_np), (update_in_blocks, update),
                    (stored_change_in_blocks, stored_change)):
    fn.whole = whole
  weights.rows_np = rows_in_blocks
  reference.update = update_in_blocks
  reference.stored_change = stored_change_in_blocks
