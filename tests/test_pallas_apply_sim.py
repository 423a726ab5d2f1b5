"""Property tests: the Pallas apply cache algorithm vs np.add.at.

The hardware kernel (`ops/pallas_apply.py`) cannot run in CI (interpret
mode breaks its input/output aliasing), so its claim/evict/flush state
machine is validated here through the statement-for-statement numpy
simulator (`ops/pallas_apply_sim.py`). Any divergence from np.add.at on
these streams is a real logic bug in the shared algorithm.

The DIRECTED state-machine corners (duplicate hits, slot-collision
chains, OOB drops, alternating evictions, full sweeps) live in the
shared golden vectors (`tests/pallas_goldens.py`, run by
`tests/test_pallas_goldens.py` and replayed on hardware by
`tools/smoke_pallas_apply.py`); this file keeps the RANDOMIZED property
sweeps that would bloat a fixed vector list.
"""

import numpy as np
import pytest

from distributed_embeddings_tpu.ops.pallas_apply import (
    HEAD_PAD,
    head_block_starts,
    head_slots,
    head_stream,
)
from distributed_embeddings_tpu.ops.pallas_apply_sim import (
    apply_rows_cached_sim,
    head_slots_sim,
    head_stream_sim,
)


def reference(buf, ids, delta):
  out = np.array(buf, np.float32)
  ok = (ids >= 0) & (ids < buf.shape[0])
  np.add.at(out, ids[ok], delta[ok])
  return out


def check(buf, ids, delta, slots=16):
  got = apply_rows_cached_sim(buf, ids, delta, slots=slots)
  want = reference(buf, ids, delta)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", range(25))
@pytest.mark.parametrize("slots", [1, 2, 16, 128])
def test_random_duplicate_streams(seed, slots):
  rng = np.random.default_rng(seed)
  rows, width = 64, 8
  n = int(rng.integers(1, 400))
  buf = rng.standard_normal((rows, width)).astype(np.float32)
  # heavy duplication: ids drawn from a tiny range so slots collide a lot
  ids = rng.integers(0, rows, n).astype(np.int64)
  delta = rng.standard_normal((n, width)).astype(np.float32)
  check(buf, ids, delta, slots=slots)


@pytest.mark.parametrize("seed", range(10))
def test_power_law_streams(seed):
  rng = np.random.default_rng(100 + seed)
  rows, width = 256, 4
  n = 2000
  buf = rng.standard_normal((rows, width)).astype(np.float32)
  r = rng.random(n)
  gamma = -0.05
  ids = ((r * (float(rows + 1) ** gamma - 1.0) + 1.0) ** (1.0 / gamma)
         ).astype(np.int64) - 1
  ids = np.clip(ids, 0, rows - 1)
  delta = rng.standard_normal((n, width)).astype(np.float32)
  check(buf, ids, delta, slots=8)


def test_chunk_edge_equivalence():
  """The kernel processes ids in chunk-sized grid steps with a persistent
  cache; the simulator has no chunk boundary at all. Running the stream
  split at an arbitrary point with the SAME live cache must equal one
  pass — the simulator is sequential so this is trivially true; what we
  pin here is that the reference semantics do not depend on split points
  (guards future chunked-simulator refactors)."""
  rng = np.random.default_rng(11)
  buf = rng.standard_normal((64, 4)).astype(np.float32)
  ids = rng.integers(0, 64, 333).astype(np.int64)
  delta = rng.standard_normal((333, 4)).astype(np.float32)
  whole = reference(buf, ids, delta)
  part = reference(reference(buf, ids[:100], delta[:100]),
                   ids[100:], delta[100:])
  np.testing.assert_allclose(whole, part, rtol=1e-5, atol=1e-5)
  check(buf, ids, delta, slots=8)


def test_fuzz_big():
  """Thousands of mixed cases: random sizes, slots, OOB rates, dup rates."""
  rng = np.random.default_rng(12)
  for _ in range(60):
    rows = int(rng.integers(1, 200))
    width = int(rng.choice([1, 3, 8]))
    slots = int(rng.choice([1, 2, 4, 32]))
    n = int(rng.integers(0, 600))
    buf = rng.standard_normal((rows, width)).astype(np.float32)
    span = int(rng.integers(1, 2 * rows + 2))
    ids = rng.integers(-3, span, n).astype(np.int64)
    delta = rng.standard_normal((n, width)).astype(np.float32)
    check(buf, ids, delta, slots=slots)


# ---- VMEM-resident heads -----------------------------------------------------
# Small numbers stand in for the kernel's (H = 8 rows a block, multiples of 8
# as the HBM tiling wants); the state machine is the same at any size.

H = 8
# two tables of 40 rows each, [0, 40) and [40, 80), in a buffer of 96 rows
ROWS, T0, T1 = 96, 0, 40


def _alternating():
  return np.array([3, 50, 3, 50, 3, 18, 3, 50] * 6)


def _short_table():
  # a 5-row table at [40, 45) before one at [45, 96): one block from 40
  return np.array([40, 44, 45, 47, 48, 44, 40, 60, 61, 47] * 5)


HEAD_CASES = {
    # name: (ids, head intervals)
    "duplicates_inside_a_head": (np.array([2, 2, 2, 5, 2, 5, 41, 41, 2] * 7),
                                 [(T0, T0 + H), (T1, T1 + H)]),
    "one_id_alternating_head_and_tail": (_alternating(),
                                         [(T0, T0 + H), (T1, T1 + H)]),
    "last_head_row_and_first_tail_row": (
        np.array([T0 + H - 1, T0 + H, T1 + H - 1, T1 + H, T1 - 1, T1] * 9),
        [(T0, T0 + H), (T1, T1 + H)]),
    "table_shorter_than_a_block": (_short_table(), [(40, 45), (45, 45 + H)]),
    "two_heads_that_abut": (np.arange(0, 24).repeat(3),
                            [(0, 8), (8, 16)]),
    "out_of_range_ids": (np.array([-1, 96, 3, 97, -5, 41, 10**6, 3, 95]),
                         [(T0, T0 + H), (T1, T1 + H)]),
    # the hazard: only head ids, so no warm slot is ever claimed and every
    # one of them flushes the value it read at start-up over rows [0, 16)
    "warm_slots_never_claimed": (np.array([1, 1, 3, 41]),
                                 [(T0, T0 + H), (T1, T1 + H)]),
    "head_at_the_buffers_end": (np.array([90, 95, 88, 87, 95, 3] * 4),
                                [(T0, T0 + H), (90, 96)]),
}


def _head_check(ids, intervals, seed=0, **kw):
  rng = np.random.default_rng(seed)
  buf = rng.standard_normal((ROWS, 4)).astype(np.float32)
  delta = rng.standard_normal((len(ids), 4)).astype(np.float32)
  starts = head_block_starts(intervals, ROWS, head_rows=H)
  assert starts, "the case must place a block"
  got = apply_rows_cached_sim(buf, ids, delta, slots=16, head_starts=starts,
                              head_rows=H, **kw)
  scale = kw.get("scale")
  want = reference(buf, ids, delta if scale is None
                   else np.float32(scale) * delta)
  return got, want


@pytest.mark.parametrize("chunk", [None, 8])
@pytest.mark.parametrize("scale", [None, -0.25])
@pytest.mark.parametrize("name", sorted(HEAD_CASES))
def test_head_cases_match_add_at(name, scale, chunk):
  ids, intervals = HEAD_CASES[name]
  got, want = _head_check(ids, intervals, scale=scale, chunk=chunk)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_naive_head_writeback_order_loses_the_update():
  """Heads written back BEFORE the flush: the warm slots' stale rows land
  last and the step's update of rows [0, 8) is gone. The kernel's order
  (after the flush's waits) is what `test_head_cases_match_add_at` holds."""
  ids, intervals = HEAD_CASES["warm_slots_never_claimed"]
  got, want = _head_check(ids, intervals, head_writeback="before_flush")
  assert not np.allclose(got[:H], want[:H], atol=1e-5)
  np.testing.assert_allclose(got[T1:], want[T1:], rtol=1e-5, atol=1e-5)
  # a cold start has nothing to flush there, so only the order is at fault
  got, want = _head_check(ids, intervals, head_writeback="before_flush",
                          warm=False)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", range(20))
def test_random_streams_with_heads(seed):
  rng = np.random.default_rng(200 + seed)
  rows = int(rng.integers(16, 300))
  n_tables = int(rng.integers(1, 5))
  cuts = np.sort(rng.integers(0, rows, n_tables - 1)) if n_tables > 1 else []
  edges = [0, *[int(c) for c in cuts], rows]
  h = int(rng.choice([8, 16]))
  intervals = [(a, min(a + h, b)) for a, b in zip(edges[:-1], edges[1:])
               if b > a]
  starts = head_block_starts(intervals, rows, head_rows=h)
  pad = starts + [HEAD_PAD] * int(rng.integers(0, 3))
  n = int(rng.integers(1, 500))
  # power-law inside a random table, so heads and tails both see traffic
  t = rng.integers(0, len(edges) - 1, n)
  lo, hi = np.array(edges)[t], np.array(edges)[t + 1]
  ids = lo + (rng.random(n) ** 4 * np.maximum(hi - lo, 1)).astype(np.int64)
  ids[rng.random(n) < 0.05] = rng.integers(-3, rows + 5)
  buf = rng.standard_normal((rows, 3)).astype(np.float32)
  delta = rng.standard_normal((n, 3)).astype(np.float32)
  slots = int(rng.choice([1, 4, 16]))
  got = apply_rows_cached_sim(buf, ids, delta, slots=slots, head_starts=pad,
                              head_rows=h, chunk=int(rng.choice([16, 64])))
  np.testing.assert_allclose(got, reference(buf, ids, delta),
                             rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", range(8))
def test_head_blocks_are_disjoint_aligned_and_cover(seed):
  rng = np.random.default_rng(300 + seed)
  rows = int(rng.integers(64, 4000)) // 8 * 8
  h = int(rng.choice([8, 64, 256]))
  edges = sorted({0, rows, *[int(x) for x in rng.integers(0, rows, 6)]})
  intervals = [(a, min(a + h, b)) for a, b in zip(edges[:-1], edges[1:])]
  starts = head_block_starts(intervals, rows, head_rows=h)
  assert starts == sorted(starts) and all(s % 8 == 0 for s in starts)
  assert all(0 <= s and s + h <= rows for s in starts)
  assert all(b - a >= h for a, b in zip(starts[:-1], starts[1:]))
  covered = np.zeros(rows, bool)
  for s in starts:
    covered[s:s + h] = True
  for a, b in intervals:
    if b <= rows - h:  # what can be placed without touching a neighbour
      assert covered[a:b - 7].all(), (a, b, starts)


def test_head_blocks_leave_out_what_cannot_be_placed():
  assert head_block_starts([(0, 8)], rows=4, head_rows=8) == []
  # the VMEM budget (64 MiB: 8 blocks of 8,192 rows, each held twice) caps
  # the count: the rest takes the row cache
  many = [(i * 10000, i * 10000 + 8192) for i in range(40)]
  assert len(head_block_starts(many, rows=400000)) == 8
  # a table that starts inside its neighbour's block begins where that ends
  assert head_block_starts([(0, 3), (3, 19)], rows=64, head_rows=8) == [0, 8]
  # a table that starts off the 8-row tiling gets ONE block, from the tile
  # before it; its last few head rows take the row cache
  assert head_block_starts([(0, 64), (1003, 1067)], rows=4096,
                           head_rows=64) == [0, 1000]


@pytest.mark.parametrize("seed", range(4))
def test_head_slots_jax_equals_numpy(seed):
  rng = np.random.default_rng(400 + seed)
  rows, h = 512, 32
  # the last two are no blocks: padding, and a start the buffer's end cuts
  starts = head_block_starts([(0, 32), (100, 110), (110, 142), (500, 512)],
                             rows, head_rows=h) + [HEAD_PAD, rows - h + 8]
  ids = rng.integers(-4, rows + 4, 2000).astype(np.int32)
  got = np.asarray(head_slots(ids, np.asarray(starts, np.int32), rows, h))
  np.testing.assert_array_equal(got, head_slots_sim(ids, starts, rows, h))
  assert (got >= 0).any() and (got < 0).any()
  # the stream the kernel reads: a raw negative id is dropped (-1), never
  # read as a head row, and an id past the buffer is dropped too
  stream = np.asarray(head_stream(ids, np.asarray(starts, np.int32), rows, h))
  np.testing.assert_array_equal(stream,
                                head_stream_sim(ids, starts, rows, h))
  assert (stream[(ids < 0) | (ids >= rows)] == -1).all()
  assert (stream[got >= 0] == (1 << 30) + got[got >= 0]).all()
