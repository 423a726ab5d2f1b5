"""The expert layer's grouped matmuls as Mosaic kernels tiled to their shape.

``layers/dense.py::grouped_dots_rounded`` forms three kinds of product over
the sorted stream's rows, a group of rows (one held expert's) at a time:

  :func:`grouped_dot`   ``x [m, k]`` by ``w [groups, k, n]`` -> ``[m, n]``
                        (``lax.ragged_dot``), and with ``transposed`` ``dy
                        [m, k]`` by ``w [groups, n, k]`` as it lies: no
                        ``w^T`` is written
  :func:`grouped_dw`    ``x [m, k]`` against ``dy [m, n]`` -> ``[groups, k,
                        n]`` (``lax.ragged_dot_general`` with the rows ragged)

XLA's ``lax.ragged_dot`` kernel walks every shape under one tiling (512 x 512
x 256): 15 TF/s of a v5e's 197 at a hidden size of 4,096 with groups of 205
live rows, 93 at 2,048 x 1,536 (PERF.md, PR 51, 53). Here the tiles are read
off the call: :func:`tiles` is a pure function of ``(m, k, n, groups)`` and
the operands' width.

**The walk.** The rows are cut into tiles of ``tm``; a VISIT is one (group,
row tile) pair that share rows, in row order (:func:`visits`, a dozen array
ops on the device inside the jitted call). The rows that no group owns (at or
past ``sum(sizes)``: the expert layer's tail has them) are one more group, with
no weight: its visits write zeros and multiply nothing, so those rows come
back as ZEROS in ``grouped_dot`` and enter no ``dw``, which is what
``lax.ragged_dot`` promises and what a kernel that leaves them unwritten does
not (PR 49 was refused for a NaN there). A group of no rows is visited once:
nothing to do in ``grouped_dot``, a block of zeros in ``grouped_dw``.

**A visit.** ``k`` is never cut: a group's weight block ``[k, tn]`` stays in
VMEM while its row tiles pass, the product's sums stay in the MXU's result
buffer, and a tile that lies inside one group is multiplied whole and stored as
it comes (no accumulator, no mask; ``tc`` columns a ``dot``, in a loop the
compiler keeps rolled: :func:`tiles` says why). Only a tile that a group
boundary cuts is walked ``sub`` rows at a time, the pieces that hold a row of
the group alone, each stored under a row mask: a boundary costs at most ``sub``
rows of wasted product, whatever ``tm`` is. ``grouped_dw`` keeps a float32
block ``[tk, tn]`` of a group's result while the group's rows pass and adds
every visit's product to it; a cut tile it multiplies whole, the other groups'
rows masked to zero.

bfloat16 operands, float32 sums, float32 out. ``m`` need not be whole tiles:
the last tile's rows past ``m`` are in no group, read as whatever the buffer
holds, masked before they are stored or (``grouped_dw``) multiplied.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

# the kernels' names in HLO (the Mosaic custom calls) and in device traces
DOT_KERNEL = "de_grouped_dot"
DW_KERNEL = "de_grouped_dw"

NUM_LANES = 128
# bytes of VMEM a kernel's blocks may take (a v5e has 128 MiB; a kernel's
# default scope is 16), and what the compiler is asked for beyond them: the
# product of a visit before it is stored, the masked copies of a cut tile
VMEM_BLOCKS = 56 << 20
VMEM_BESIDE = 24 << 20
# bytes of `grouped_dw`'s float32 result block at most
DW_BLOCK = 16 << 20
# columns of a block that one product in the kernel forms, at most
CHUNK = 256


class Tiles(NamedTuple):
  tm: int     # rows a tile
  sub: int    # rows a piece of a tile that a group boundary cuts
  tn: int     # columns of the result a block (``grouped_dw``: of ``dy``)
  tk: int     # columns of ``x`` a block (``grouped_dot``: all of them, ``k``)
  tc: int = NUM_LANES   # columns of a block one product in the kernel forms


def _lane_divisors(width: int):
  """The divisors of ``width`` that are whole lane tiles, largest first."""
  return [width // q for q in range(1, width // NUM_LANES + 1)
          if width % q == 0 and (width // q) % NUM_LANES == 0]


def block_bytes(t: Tiles, dw: bool, itemsize: int = 2) -> int:
  """VMEM the pipeline holds for a kernel's blocks: two buffers of each
  operand block and of the float32 result block."""
  if dw:
    return 2 * (itemsize * t.tm * (t.tk + t.tn) + 4 * t.tk * t.tn)
  return 2 * (itemsize * t.tk * (t.tm + t.tn) + 4 * t.tm * t.tn)


def tiles(m: int, k: int, n: int, groups: int, dw: bool = False,
          itemsize: int = 2) -> Optional[Tiles]:
  """The tiling of a grouped product of ``m`` rows in ``groups`` groups,
  contraction ``k`` (``dw``: the rows' width), result width ``n``; ``None``
  where no tiling fits VMEM, and the caller keeps ``lax.ragged_dot``. Held
  against a sweep at the cells' shapes (``tools/bench_ragged_dot.py
  --sweep``; PERF.md, PR 53, has the readings):

  Rows: tiles of 512, cut pieces of 128 (fewer where ``m`` is fewer, in
  whole sublane tiles of the operand type). Pieces of 128 read up to 9%
  faster than 256 at groups of 205 rows and no slower at groups of 512; rows
  of 512 and 256 read within 1.5% of each other once only the cut tiles are
  walked piece by piece, rows of 1,024 up to 12% slower.

  Columns: the widest lane-tile divisor of ``n`` whose blocks fit (the rows
  are read once a column block; half the width read 2-3% slower), formed
  ``CHUNK`` columns a product by a rolled loop: a step keeps the code of
  every CALL of a kernel in HBM, and a block's product written out whole
  made GLM's step 0.2 GB larger; chunks of 256 cost the products 4-7% of
  their rate and a step a twentieth of that. ``dw``
  keeps a block of the float32 result ``[tk, tn]`` while a group's rows pass
  and adds every visit's product to it: a block past ``DW_BLOCK`` bytes read
  half the rate of one under (Solar's whole ``[4096, 1280]``), so the widest
  ``tn``, ``tk`` under it that read the operands least often."""
  if k % NUM_LANES or n % NUM_LANES:
    return None
  sublanes = 32 // itemsize
  if m > 128:
    tm = min(512, -(-m // 128) * 128)
  else:
    tm = -(-m // sublanes) * sublanes
  sub = min(tm, 128)
  chunk = lambda tn: max(c for c in _lane_divisors(tn) if c <= CHUNK)
  if dw:
    options = [Tiles(tm, sub, tn, tk, chunk(tn))
               for tk in _lane_divisors(k) for tn in _lane_divisors(n)
               if 4 * tk * tn <= DW_BLOCK]
    # the operands' reads: x once a block of n, dy once a block of k
    options.sort(key=lambda t: (k * (n // t.tn) + n * (k // t.tk), -t.tk))
  else:
    options = [Tiles(tm, sub, tn, k, chunk(tn)) for tn in _lane_divisors(n)]
  for t in options:
    if block_bytes(t, dw, itemsize) <= VMEM_BLOCKS:
      return t
  return None


def visits(sizes: jax.Array, m: int, tm: int):
  """The walk over ``m`` rows in tiles of ``tm``: int32 ``starts``, ``ends``
  ``[groups + 2]`` (the groups' row ranges; then the rows no group owns, up
  to the last tile's end; then an empty range that the visits left over name)
  and ``group``, ``tile`` ``[tiles + groups]``, a visit's group and row
  tile. Tiles never decrease along the walk, groups never either."""
  groups = sizes.shape[0]
  n_tiles = -(-m // tm)
  top = jnp.full((2,), n_tiles * tm, jnp.int32)
  ends = jnp.concatenate([jnp.cumsum(sizes.astype(jnp.int32)), top])
  starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
  first = jnp.minimum(starts // tm, n_tiles - 1)
  last = jnp.where(ends > starts, (ends - 1) // tm, first)
  upto = jnp.cumsum(last - first + 1)[:-1]      # less the left-over range
  visit = jnp.arange(n_tiles + groups, dtype=jnp.int32)
  group = jnp.sum(visit[:, None] >= upto[None, :], axis=1, dtype=jnp.int32)
  before = jnp.concatenate([jnp.zeros((1,), jnp.int32), upto])
  tile = jnp.minimum(jnp.take(first, group) + visit - jnp.take(before, group),
                     n_tiles - 1)
  return starts, ends, group, tile


def _rows_here(tm, starts, ends, group, tile, v):
  """A visit's group and the rows ``[lo, hi)`` of its tile that are the
  group's."""
  g, base = group[v], tile[v] * tm
  lo = jnp.maximum(starts[g], base) - base
  hi = jnp.minimum(ends[g], base + tm) - base
  return g, lo, jnp.maximum(hi, lo)


def _column_chunks(t: Tiles, body):
  """``body(at)`` for every chunk of ``t.tc`` of a block's ``t.tn`` result
  columns, ``at`` its first: a loop the compiler keeps rolled, so that a
  kernel's code is one chunk's products and not the block's (a step holds a
  hundred of these kernels, and their code lies in HBM)."""
  def chunk(j, _):
    body(pl.multiple_of(j * t.tc, t.tc))
  lax.fori_loop(0, t.tn // t.tc, chunk, None)


def _dot_kernel(t: Tiles, groups, transposed, starts, ends, group, tile,
                x_ref, w_ref, o_ref):
  g, lo, hi = _rows_here(t.tm, starts, ends, group, tile, pl.program_id(1))
  owned = g < groups
  dims = (((1,), (1 if transposed else 0,)), ((), ()))

  def product(x, at):
    w = w_ref[pl.ds(at, t.tc), :] if transposed else w_ref[:, pl.ds(at, t.tc)]
    return lax.dot_general(x, w, dims, preferred_element_type=jnp.float32)

  @pl.when((hi - lo == t.tm) & owned)
  def _whole_tile():
    def store(at):
      o_ref[:, pl.ds(at, t.tc)] = product(x_ref[...], at)
    _column_chunks(t, store)

  @pl.when((hi - lo == t.tm) & jnp.logical_not(owned))
  def _no_groups_tile():
    o_ref[...] = jnp.zeros_like(o_ref)

  def piece(s, _):
    rows = pl.ds(pl.multiple_of(s * t.sub, t.sub), t.sub)
    row = s * t.sub + lax.broadcasted_iota(jnp.int32, (t.sub, t.tc), 0)
    mine = (row >= lo) & (row < hi)

    @pl.when(owned)
    def _multiplied():
      def store(at):
        cols = pl.ds(at, t.tc)
        o_ref[rows, cols] = jnp.where(mine, product(x_ref[rows, :], at),
                                      o_ref[rows, cols])
      _column_chunks(t, store)

    @pl.when(jnp.logical_not(owned))
    def _zeros():
      def store(at):
        cols = pl.ds(at, t.tc)
        o_ref[rows, cols] = jnp.where(mine, 0.0, o_ref[rows, cols])
      _column_chunks(t, store)

  @pl.when((hi - lo < t.tm) & (hi > lo))
  def _cut_tile():
    lax.fori_loop(lo // t.sub, (hi + t.sub - 1) // t.sub, piece, None)


def _dw_kernel(t: Tiles, groups, ragged, starts, ends, group, tile,
               x_ref, dy_ref, o_ref):
  v = pl.program_id(2)
  g, lo, hi = _rows_here(t.tm, starts, ends, group, tile, v)
  first = (v == 0) | (group[jnp.maximum(v, 1) - 1] != g)
  owned = g < groups

  def add(x, masked):
    xt = x.T     # once a visit, not once a chunk

    def product(at):
      dy = dy_ref[:, pl.ds(at, t.tc)]
      if masked:   # the rows past `m` hold anything, a NaN too
        dy = jnp.where(_rows_between(dy.shape, lo, hi), dy,
                       jnp.zeros_like(dy))
      return jnp.dot(xt, dy, preferred_element_type=jnp.float32)

    @pl.when(first)
    def _():
      def store(at):
        o_ref[:, pl.ds(at, t.tc)] = product(at)
      _column_chunks(t, store)

    @pl.when(jnp.logical_not(first))
    def _():
      def store(at):
        o_ref[:, pl.ds(at, t.tc)] += product(at)
      _column_chunks(t, store)

  @pl.when(owned & first & (hi == lo))
  def _no_rows():
    o_ref[...] = jnp.zeros_like(o_ref)

  @pl.when(owned & (hi - lo == t.tm))
  def _whole_tile():
    add(x_ref[...], False)

  @pl.when(owned & (hi - lo < t.tm) & (hi > lo))
  def _cut_tile():
    add(jnp.where(_rows_between(x_ref.shape, lo, hi), x_ref[...],
                  jnp.zeros_like(x_ref)), ragged)


def _rows_between(shape, lo, hi):
  row = lax.broadcasted_iota(jnp.int32, shape, 0)
  return (row >= lo) & (row < hi)


def _params(semantics, t: Tiles, dw: bool, itemsize: int):
  return pltpu.CompilerParams(
      dimension_semantics=semantics,
      vmem_limit_bytes=block_bytes(t, dw, itemsize) + VMEM_BESIDE)


@functools.partial(jax.jit, static_argnames=("transposed", "interpret", "t"))
def grouped_dot(x, w, sizes, transposed=False, interpret=False,
                t: Optional[Tiles] = None):
  """``lax.ragged_dot(x, w, sizes)`` in float32: ``x [m, k]``'s rows by their
  group's ``w [groups, k, n]`` (``transposed``: ``w [groups, n, k]``, by its
  transpose, which is not formed). A row at or past ``sum(sizes)`` comes back
  as zeros. ``t``: another tiling than :func:`tiles`'s (the sweep's)."""
  (m, k), groups = x.shape, w.shape[0]
  n = w.shape[1] if transposed else w.shape[2]
  t = (t or tiles(m, k, n, groups, False, x.dtype.itemsize))._replace(tk=k)
  meta = visits(sizes, m, t.tm)
  held = lambda g: jnp.minimum(g, groups - 1)
  w_block = (None, t.tn, k) if transposed else (None, k, t.tn)
  w_index = (lambda j, v, s, e, g, r: (held(g[v]), j, 0)) if transposed \
      else (lambda j, v, s, e, g, r: (held(g[v]), 0, j))
  return pl.pallas_call(
      functools.partial(_dot_kernel, t, groups, transposed),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=4,
          grid=(n // t.tn, meta[2].shape[0]),
          in_specs=[
              pl.BlockSpec((t.tm, k), lambda j, v, s, e, g, r: (r[v], 0)),
              pl.BlockSpec(w_block, w_index),
          ],
          out_specs=pl.BlockSpec((t.tm, t.tn),
                                 lambda j, v, s, e, g, r: (r[v], j)),
      ),
      out_shape=out_struct((m, n), jnp.float32, x, w, sizes),
      compiler_params=_params(("parallel", "arbitrary"), t, False,
                              x.dtype.itemsize),
      cost_estimate=pl.CostEstimate(
          flops=2 * m * k * n, transcendentals=0,
          bytes_accessed=x.dtype.itemsize * (m * k * (n // t.tn)
                                             + groups * k * n) + 4 * m * n),
      interpret=interpret,
      name=DOT_KERNEL,
  )(*meta, x, w)


@functools.partial(jax.jit, static_argnames=("interpret", "t"))
def grouped_dw(x, dy, sizes, interpret=False, t: Optional[Tiles] = None):
  """``[groups, k, n]`` float32: ``x[rows of g]^T dy[rows of g]`` a group
  ``g``, zeros for a group of no rows. ``x [m, k]``, ``dy [m, n]``; rows no
  group owns enter nothing."""
  (m, k), n, groups = x.shape, dy.shape[1], sizes.shape[0]
  t = t or tiles(m, k, n, groups, True, x.dtype.itemsize)
  meta = visits(sizes, m, t.tm)
  held = lambda g: jnp.minimum(g, groups - 1)
  return pl.pallas_call(
      functools.partial(_dw_kernel, t, groups, bool(m % t.tm)),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=4,
          grid=(k // t.tk, n // t.tn, meta[2].shape[0]),
          in_specs=[
              pl.BlockSpec((t.tm, t.tk),
                           lambda i, j, v, s, e, g, r: (r[v], i)),
              pl.BlockSpec((t.tm, t.tn),
                           lambda i, j, v, s, e, g, r: (r[v], j)),
          ],
          out_specs=pl.BlockSpec(
              (None, t.tk, t.tn),
              lambda i, j, v, s, e, g, r: (held(g[v]), i, j)),
      ),
      out_shape=out_struct((groups, k, n), jnp.float32, x, dy, sizes),
      compiler_params=_params(("parallel", "parallel", "arbitrary"), t, True,
                              x.dtype.itemsize),
      cost_estimate=pl.CostEstimate(
          flops=2 * m * k * n, transcendentals=0,
          bytes_accessed=x.dtype.itemsize * m * (k * (n // t.tn)
                                                 + n * (k // t.tk))
          + 4 * groups * k * n),
      interpret=interpret,
      name=DW_KERNEL,
  )(*meta, x, dy)
