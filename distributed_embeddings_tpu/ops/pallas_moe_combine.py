"""The expert layer's combine, token-major, as a Mosaic kernel.

``layers/moe.py`` sorts a layer's (token, slot) assignments by expert and
computes the head of that sorted stream, ``R`` rows of ``d`` float32. Two
things then add rows of the stream up by token: the layer's output
(``out[tok[r]] += p[r] * y[r]``) and, in the backward, the cotangent of ``h``
through the gather that made the stream (``dh[tok[r]] += dx[r]``). XLA runs
both as a scatter-add, a dependent read-modify-write a row because its
indices may repeat: 81-94 ns a row of 8 KiB on a v5e, a sixth of what the
bytes cost (PERF.md, PR 43). But every token owns exactly ``top_k`` positions
of the stream, so the same sum can be read from the token's side:

  ``combine(rows [R, d], pos [T, k], scale [T, k]) -> out [T, d]``
  ``out[t] = sum over j with pos[t, j] < R of scale[t, j] * rows[pos[t, j]]``

``pos`` is the inverse of the sorted order (where the assignment of token
``t``'s slot ``j`` lies in the stream); a position at or past ``R`` is in the
stream's tail and adds nothing here. No index repeats on the output's side:
a token's rows are fetched, summed in VMEM and written once.

The kernel (``de_moe_combine`` in HLO and in a device trace): a grid over
blocks of tokens; a block's ``pos`` in SMEM (flat, one dimension: two would
pad every token's ``k`` to 128 words) and its ``scale`` in VMEM; ``rows``
stays in HBM, handed over as its ``(8, 128)`` tiles (``[R / 8, d / 128, 8,
128]``, a bitcast of what XLA holds: Mosaic takes no one-row slice of the
two-dimensional array). Eight tokens at a time (one sublane each), every
position below ``R`` starts one row DMA into a ring in VMEM, laid out so that
slot ``j`` of the eight tokens is one ``[8, d]`` tile row: the sum over ``j``
is then ``k`` full-width multiply-adds. ``depth`` groups of eight are in
flight (as many row DMAs as ``ops/pallas_apply.py`` keeps), one DMA semaphore
a group; a group's count of started copies is kept in SMEM and their bytes
are waited a power of two of rows at a time. A slot whose position is past
``R`` holds what an earlier group left there: its scale is zero and the row
is selected away, never multiplied.

What the body costs a process before its first step is part of the design
(PERF.md, PR 44): a step's trace meets the kernel sixteen times, and a
``pl.when`` written out in Python is a closure traced every time. So what
repeats is a ``lax.fori_loop`` (a body traced once; the copies' loop unrolled
where it is lowered, which the chip's scalar core wants), and
``layers/moe.py`` enters the kernel through ``jax.jit``.

The work is fixed by ``R`` and ``T * k``: every position below ``R`` is
fetched whether its scale is zero or not, so a step's time does not follow a
router's load (``layers/moe.py`` says why that matters here). float32 in,
float32 sums in slot order, float32 out.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

# the kernel's name in HLO (the Mosaic custom call) and in device traces
KERNEL_NAME = "de_moe_combine"

NUM_LANES = 128
GROUP = 8             # tokens a group: the sublanes of a float32 tile
# tokens a grid step at most, and the bytes its output block may take (twice,
# the pipeline's two buffers): with the ring the kernel stays inside the 16 MiB
# of scoped VMEM a kernel has by default. It asks for no more: a larger
# `vmem_limit_bytes` moved XLA's own placement of other ops' buffers, and the
# SDAR cell's step read 1.3 ms longer (PERF.md, PR 43)
BLOCK_TOKENS = 512
BLOCK_BYTES = 4 << 20
# VMEM the ring of fetched rows may take: at d = 2048 and k = 8 eight groups
# of 8 * k rows, 448 positions ahead of the sum, half of them below R in the
# cells' streams
RING_BYTES = 4 << 20
# one SMEM block of `pos`: XLA lays a small 1-D int32 array out as ONE tile,
# which a partial block would mismatch (ops/pallas_apply.py found that);
# up to this many entries the kernel takes the whole array as one block. A
# longer array lies in tiles of 1,024 entries (`T(1024)` in the compiled HLO),
# and Mosaic refuses a block that is not whole tiles (a block of 512 entries
# was, on the chip: PERF.md, PR 43)
ONE_BLOCK_ENTRIES = 8192
SMEM_TILE = 1024
MAX_TOP_K = 16


def _block_tokens(tokens: int, top_k: int, d: int) -> int:
  """Tokens a grid step, 0 where no block both fits VMEM and has a ``pos``
  block SMEM's layout allows: all of them while ``pos`` is one block, else
  ``BLOCK_TOKENS`` or what ``BLOCK_BYTES`` hold, in whole SMEM tiles of
  positions."""
  if tokens * top_k <= ONE_BLOCK_ENTRIES:
    block = -(-tokens // GROUP) * GROUP
    return block if block * d * 4 <= 2 * BLOCK_BYTES else 0
  block = min(BLOCK_TOKENS, BLOCK_BYTES // (4 * d) // GROUP * GROUP)
  return block if block and (block * top_k) % SMEM_TILE == 0 else 0


def fits(rows: int, tokens: int, top_k: int, d: int) -> bool:
  """Whether the kernel takes ``combine(rows [rows, d], pos [tokens,
  top_k], ...)``: whole lanes, a ring of at least two groups within its
  budget, and a block of tokens (:func:`_block_tokens`)."""
  return (rows >= 1 and tokens >= 1 and 1 <= top_k <= MAX_TOP_K
          and d % NUM_LANES == 0
          and 2 * GROUP * top_k * d * 4 <= RING_BYTES
          and _block_tokens(tokens, top_k, d) > 0)


def _ring_depth(top_k: int, d: int, groups: int) -> int:
  """Groups in flight: the largest power of two whose rows fit the ring's
  budget, at most 8 (more bought nothing on the v5e) and at most the
  block's groups."""
  depth = 2
  while (depth < 8 and 2 * depth * GROUP * top_k * d * 4 <= RING_BYTES
         and 2 * depth <= groups):
    depth *= 2
  return depth


def _combine_kernel(k, block, depth, n_rows, tiles, pos_ref, scale_ref,
                    rows_ref, out_ref, ring, started, sems):
  """``rows_ref [R / 8, d / 128, 8, 128]`` in HBM, ``out_ref [block / 8,
  d / 128, 8, 128]``, ``ring [depth * k, d / 128, 8, 128]``: the tiles of
  the two-dimensional arrays as XLA lays them out, so that ONE row (sublane
  ``r % 8`` of every lane tile of tile row ``r // 8``) is a slice a DMA can
  name. ``tiles``: lane tiles summed at a time (the accumulator's vregs)."""
  groups = block // GROUP
  lane_tiles = out_ref.shape[1]

  def issue(g):
    """Start the row copies of the eight tokens of group ``g``. A loop, so
    that its body is TRACED once (a conditional a position written out in
    Python was 90% of what a step's trace cost, on every start of a process:
    PERF.md, PR 44), unrolled whole where it is LOWERED: the scalar core pays
    every instruction of a row's issue, and with a loop's index in the
    addresses a call read 1.5 to 2.3 ms where this reads 1.05."""
    slot = jnp.bitwise_and(g, depth - 1)
    first = g * (GROUP * k)

    def one(i, n):
      p = pos_ref[first + i]
      below = p < n_rows
      tt = lax.div(i, k)

      @pl.when(below)
      def _():
        pltpu.make_async_copy(
            rows_ref.at[jnp.right_shift(p, 3), :,
                        pl.ds(jnp.bitwise_and(p, GROUP - 1), 1), :],
            ring.at[slot * k + (i - tt * k), :, pl.ds(tt, 1), :],
            sems.at[slot]).start()
      return n + below.astype(jnp.int32)
    started[slot] = lax.fori_loop(0, GROUP * k, one, jnp.int32(0),
                                  unroll=True)

  def consume(g):
    """Wait group ``g``'s copies, sum its tokens' rows, write them."""
    slot = jnp.bitwise_and(g, depth - 1)

    # every copy of the group signals one semaphore with its bytes: wait them
    # as the binary digits of their count, a power of two of rows at a time
    # (a wait a copy cost 6 ns a row here: PERF.md, PR 43)
    n = started[slot]
    rows_at_once = 1 << (GROUP * k).bit_length() - 1
    while rows_at_once:
      if rows_at_once >= GROUP:
        done = ring.at[pl.ds(0, rows_at_once // GROUP)]
      else:
        done = ring.at[0, :, pl.ds(0, rows_at_once), :]

      @pl.when(jnp.bitwise_and(n, rows_at_once) != 0)
      def _(done=done):
        pltpu.make_async_copy(done, done, sems.at[slot]).wait()
      rows_at_once //= 2
    s = scale_ref[pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP), :]
    for c0 in range(0, lane_tiles, tiles):
      width = min(tiles, lane_tiles - c0)
      acc = None
      for j in range(k):
        sj = s[:, j:j + 1][None]
        r = ring[slot * k + j, pl.ds(c0, width), :, :]
        # a slot no copy filled holds an earlier group's row, or nothing
        term = jnp.where(sj != 0, r, 0.0) * sj
        acc = term if acc is None else acc + term
      out_ref[g, pl.ds(c0, width), :, :] = acc

  # ONE loop, so that the copies' loop is lowered at one site (8 * k bodies,
  # a millisecond each): group ``g``'s copies are started ``ahead`` turns
  # before they are summed, the first turns only start and the last only sum
  ahead = depth - 1

  def turn(g, c):
    @pl.when(g < groups)
    def _():
      issue(g)

    @pl.when(g >= ahead)
    def _():
      consume(g - ahead)
    return c
  lax.fori_loop(0, groups + ahead, turn, 0)


def _tiles(x: jax.Array) -> jax.Array:
  """``[n, d]`` -> ``[n / 8, d / 128, 8, 128]``, tile by tile: what the
  array's bytes already are under XLA's ``(8, 128)`` tiling, so no copy."""
  n, d = x.shape
  return x.reshape(n // GROUP, GROUP, d // NUM_LANES, NUM_LANES).transpose(
      0, 2, 1, 3)


def combine(rows: jax.Array, pos: jax.Array, scale: jax.Array,
            block: Optional[int] = None, depth: Optional[int] = None,
            interpret: bool = False) -> jax.Array:
  """``out[t] = sum over j with pos[t, j] < R of scale[t, j] * rows[pos[t,
  j]]``: ``rows [R, d]`` float32, ``pos [T, k]`` int32 (>= 0), ``scale [T,
  k]`` float32 -> float32 ``[T, d]``.

  ``block``: tokens a grid step (a multiple of 8; default ``BLOCK_TOKENS``,
  or all of them while ``pos`` is one SMEM block); ``depth``: groups of
  eight tokens whose copies are in flight (a power of two; default by the
  ring's budget). Both are the kernel's tuning, not a caller's choice of
  path."""
  n_rows, d = rows.shape
  t, k = pos.shape
  if rows.dtype != jnp.float32 or scale.dtype != jnp.float32:
    raise ValueError(f"rows {rows.dtype}, scale {scale.dtype}: the kernel "
                     "sums float32")
  if scale.shape != (t, k) or not fits(n_rows, t, k, d):
    raise ValueError(f"rows {rows.shape}, pos {pos.shape}, scale "
                     f"{scale.shape}")
  if block is None:
    block = _block_tokens(t, k, d)
  if block % GROUP:
    raise ValueError(f"block {block} is no multiple of {GROUP}")
  groups = block // GROUP
  if depth is None:
    depth = _ring_depth(k, d, groups)
  if depth & (depth - 1) or depth < 2 or depth - 1 > groups:
    raise ValueError(f"depth {depth}: a power of two, at least 2, at most "
                     f"one more than the block's {groups} groups")
  # a position past R starts no copy, so its slot is never read: its scale is 0
  pos = pos.astype(jnp.int32)
  scale = jnp.where(pos < n_rows, scale, 0.0)
  pad = (-t) % block
  if pad:
    pos = jnp.concatenate([pos, jnp.full((pad, k), n_rows, jnp.int32)])
    scale = jnp.concatenate([scale, jnp.zeros((pad, k), jnp.float32)])
  if n_rows % GROUP:
    rows = jnp.pad(rows, ((0, (-n_rows) % GROUP), (0, 0)))
  operands = (pos.reshape((t + pad) * k), scale, _tiles(rows))
  lane_tiles = d // NUM_LANES
  tile = (lane_tiles, GROUP, NUM_LANES)
  out = pl.pallas_call(
      functools.partial(_combine_kernel, k, block, depth, n_rows,
                        min(lane_tiles, 16)),
      grid=((t + pad) // block,),
      in_specs=[
          pl.BlockSpec((block * k,), lambda i: (i,), memory_space=pltpu.SMEM),
          pl.BlockSpec((block, k), lambda i: (i, 0)),
          pl.BlockSpec(memory_space=pl.ANY),
      ],
      out_specs=pl.BlockSpec((groups,) + tile, lambda i: (i, 0, 0, 0)),
      out_shape=out_struct(((t + pad) // GROUP,) + tile, jnp.float32,
                           *operands),
      scratch_shapes=[
          pltpu.VMEM((depth * k,) + tile, jnp.float32),
          pltpu.SMEM((depth,), jnp.int32),
          pltpu.SemaphoreType.DMA((depth,)),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary",)),
      cost_estimate=pl.CostEstimate(
          flops=2 * min(n_rows, t * k) * d, transcendentals=0,
          bytes_accessed=4 * d * (min(n_rows, t * k) + t + pad)
          + 8 * (t + pad) * k),
      interpret=interpret,
      name=KERNEL_NAME,
  )(*operands)
  return out.transpose(0, 2, 1, 3).reshape(t + pad, d)[:t]
