"""Fused Pallas DLRM pairwise-interaction kernels.

TPU equivalent of the reference's dot-interaction
(`examples/dlrm/utils.py:92-113`), replacing the XLA matmul-form pair
(`models/dlrm.py:_tril_products`) on the hot path. XLA lowers the
per-sample product einsum "bpd,bqd->bpq" to a convolution that wants
BATCH-MINOR operand layouts, and the selection matmuls re-infect the
graph with row-major, so the XLA form pays [B,27,128]/[B,3456] layout
copies around the real work. These kernels take the f per-table [B, D]
slices in their natural row-major layout and keep every intermediate
(the pair products, the scattered selection cotangent) in VMEM.

How a block of S samples is laid out (PR 34). The MXU wants a sample's
parts on the rows of a tile; the parts arrive with samples on the rows.
R = `rows_per_sample(f)` is f padded to 8, 16 or 32 rows, and
T = `samples_per_tile(f)` = 128 // R samples share one 128-row MXU tile.

  * Words, not casts. A bfloat16 tile packs rows 2i and 2i + 1 into one
    32-bit word a lane, and a bfloat16 is the high half of its float32.
    So the kernels build such words in registers with shifts and ors and
    `pltpu.bitcast` a uint32 scratch to the bfloat16 block the MXU reads;
    they never store a float32 copy of a block and `astype` it back. (On
    the chip that cast, not the sublane shuffles of the `concatenate` it
    replaced, was most of the kernels' time: PERF.md section 6, PR 34.)
  * Assembly by strided stores. The word (parts 2i, 2i + 1) of every sample
    goes to row `s * R / 2 + i` of the scratch by ONE strided store a vreg
    (`ref[pl.ds(i, S, stride=R // 2)]`, Mosaic's
    `vector_store_slane_stride`); the scratch read whole IS the block,
    `[S // T, 128, D]` after a free leading-dimension split.
  * T samples a tile. ONE batched `dot_general` of the block with itself
    gives `[S // T, 128, 128]` whose row (j, p) and column (j', q) hold
    <x[Tg+j, p], x[Tg+j', q]>; the cross-sample blocks (j != j') are waste
    the MXU does not notice (it runs at a few percent here).
  * Selection from strided loads. The products are rounded to bfloat16 in
    registers and stored as words of two samples (rows (2m, p), (2m+1, p)),
    so row p of every sample comes back by one strided load that is
    already the `[S, 128]` bfloat16 left operand; a constant mask keeps
    each sample's own block (`lane // R == sample % T`), and the selection
    constant is `M[p]` tiled T times along K. Row p's pairs (p, q <= p) lie
    side by side in one or two of the output's 128-lane tiles, and only
    those tiles' matmuls are run (`fwd_select_np`).
  * The backward the same way round: `da @ Mt[p]` (tiled T times along N),
    masked, is row p of `blockdiag(d_sym)` of T samples and is stored as
    words of two rows; one batched `dot_general` with the assembled
    features gives rows (j, p) of `d_feats`; part p's cotangent is half of
    one strided load.

What the kernels cost a step before and after: PERF.md sections 5 and 6
(ledger, PR 33: `interact_ms` 7.974 of a 38.47 ms step on one chip, 1.826
of 12.553 on four; PR 34: 3.8 and 0.9).

Shapes (guarded by `use_pallas_interact`):
  * f parts of [B, D] bfloat16, D % 128 == 0, 2 <= f <= 32
  * B % block == 0 (block = 256 samples a grid step, forward and backward)

The selection tensor M is `models.dlrm._tril_select_np`'s half-weight
symmetric form: acts == einsum("bpd,bqd,pqn->bn", feats, feats, M) and
d_feats == 2 * einsum("bn,pqn,bqd->bpd", d_acts, M, feats) exactly (the
kernels run the same one-bf16-pass MXU products as the XLA form under
DEFAULT matmul precision, `inter` and the cotangent rounded to bfloat16
where the XLA form rounds them). The kernels take M as numpy and lay their
own constants out from it (`fwd_select_np`, `bwd_select_np`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

FWD_BLOCK = 256
BWD_BLOCK = 256
TILE = 128  # rows and columns of one MXU tile, lanes of one vreg
LOW_HALF, HIGH_HALF = 0x0000FFFF, 0xFFFF0000  # of a word of two bfloat16s

# the kernels' names in HLO (the Mosaic custom calls) and in device traces
PARTS_FWD_NAME = "de_interact_parts_fwd"
PARTS_BWD_NAME = "de_interact_parts_bwd"


def rows_per_sample(f: int) -> int:
  """Sublane rows one sample's f parts take in the assembled block: f
  padded to the next of 8, 16, 32 (a divisor of 128 and a multiple of the
  float32 tile's 8 sublanes)."""
  if not 1 <= f <= 32:
    raise ValueError(f"the interaction kernels hold 1..32 parts, got {f}")
  return next(r for r in (8, 16, 32) if f <= r)


def samples_per_tile(f: int) -> int:
  """Samples that share one 128-row MXU tile (f = 27 -> 4; f <= 16 -> 8;
  f <= 8 -> 16): read off the number of parts, no option sets it."""
  return TILE // rows_per_sample(f)


def use_pallas_interact(b: int, f: int, d: int, dtype) -> bool:
  """Static (trace-time) gate for the fused interaction kernels."""
  if os.environ.get("DE_TPU_PALLAS_INTERACT", "1") != "1":
    return False
  if dtype != jnp.bfloat16:
    return False  # jax_default_matmul_precision=float32 keeps the XLA form
  if f < 2 or f > 32 or d % 128 != 0 or f * d > 4096:
    return False  # f=1 with k=-1 has zero pairs: XLA handles the empty einsum
  if b % FWD_BLOCK != 0 or b % BWD_BLOCK != 0:
    return False
  return jax.default_backend() == "tpu"


def xla_reference(flat: jax.Array, m_np, f: int) -> jax.Array:
  """Explicit XLA einsum form of the interaction — the independent
  reference for the kernels (used by tests/test_pallas_interact.py and
  tools/smoke_pallas_interact.py), in the kernels' precision: bfloat16
  operands, float32 accumulation, `inter` rounded to bfloat16."""
  b = flat.shape[0]
  d = flat.shape[1] // f
  feats = flat.reshape(b, f, d)
  m = jnp.asarray(m_np, jnp.bfloat16)
  inter = jnp.einsum("bpd,bqd->bpq", feats, feats,
                     preferred_element_type=jnp.float32)
  return jnp.einsum("bpq,pqn->bn", inter.astype(jnp.bfloat16), m,
                    preferred_element_type=jnp.float32)


def _tiled_rows(m_np: np.ndarray) -> np.ndarray:
  """`M [f, f, P]` -> `[f, 128, P]`: row q of `M[p]` at rows `j * R + q` for
  each of the T samples j of a tile; the rows of padded parts are zero."""
  f, _, npair = m_np.shape
  r = rows_per_sample(f)
  padded = np.zeros((f, r, npair), m_np.dtype)
  padded[:, :f] = m_np
  return np.tile(padded, (1, TILE // r, 1))


def _live_tiles(m4: np.ndarray):
  """`m4 [f, 128, P]` cut into 128-column tiles, the all-zero ones left out:
  `(tiles [n, 128, 128], where)` with `where[p]` the `(column tile, index
  into tiles)` pairs of row p. A matmul against a zero tile is not run."""
  f, _, npair = m4.shape
  ntile = pl.cdiv(npair, TILE)
  wide = np.zeros((f, TILE, ntile * TILE), m4.dtype)
  wide[:, :, :npair] = m4
  tiles, where = [], []
  for p in range(f):
    where.append([])
    for t in range(ntile):
      tile = wide[p, :, t * TILE:(t + 1) * TILE]
      if tile.any():
        where[p].append((t, len(tiles)))
        tiles.append(tile)
  return np.stack(tiles), tuple(tuple(w) for w in where)


def fwd_select_np(m_np: np.ndarray):
  """The forward's selection constant. Row p of the products only has to
  reach the pairs (p, q <= p), at weight 1: `inter` is bitwise symmetric
  and 0.5 a + 0.5 a == a exactly in float32, so the lower triangle alone
  gives the half-weight form's activations to the bit, and row p's pairs
  lie side by side in one or two of the output's 128-lane tiles."""
  f = m_np.shape[0]
  lower = np.tril(np.ones((f, f), m_np.dtype))[:, :, None] * (m_np > 0)
  return _live_tiles(_tiled_rows(lower))


def bwd_select_np(m_np: np.ndarray):
  """The backward's: `Mt[p] [P, 128]` tiled T times along N, cut along K
  (the cotangent's 128-lane tiles) with the tiles row p never reads left
  out. The half-weight symmetric form stays: `d_sym` must be symmetric for
  the one product `2 * d_sym @ feats`."""
  tiles, where = _live_tiles(_tiled_rows(m_np))
  return np.ascontiguousarray(np.swapaxes(tiles, 1, 2)), where


def _own_block_mask(s: int, r: int):
  """[s, 128] mask: lane (j', q) of sample s's row belongs to s itself."""
  sample = jax.lax.broadcasted_iota(jnp.int32, (s, TILE), 0)
  lane = jax.lax.broadcasted_iota(jnp.int32, (s, TILE), 1)
  return lane // r == sample % (TILE // r)


def _own_block_words(s: int, r: int):
  """`_own_block_mask` for words of two samples: uint32 [s // 2, 128] whose
  low half is set where lane (j', q) belongs to sample 2m, whose high half
  where it belongs to sample 2m + 1."""
  t = TILE // r
  pair = jax.lax.broadcasted_iota(jnp.int32, (s // 2, TILE), 0)
  block = jax.lax.broadcasted_iota(jnp.int32, (s // 2, TILE), 1) // r
  zero = jnp.uint32(0)
  return (jnp.where(block == (2 * pair) % t, jnp.uint32(LOW_HALF), zero)
          | jnp.where(block == (2 * pair + 1) % t, jnp.uint32(HIGH_HALF),
                      zero))


def _bf16_bits(x):
  """uint32 whose high half is float32 `x` rounded to bfloat16 (to nearest,
  ties to even, as `astype` rounds) and whose low half is zero: three
  integer ops a vreg, where `astype` packs two vregs' rows into one. An
  infinity stays one; so does a NaN whose payload reaches the high half,
  as every NaN arithmetic makes does."""
  b = pltpu.bitcast(x, jnp.uint32)
  b = b + (jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1)))
  return b & jnp.uint32(HIGH_HALF)


def _words(low, high=None):
  """One word of two bfloat16s from uint32s that hold each in their high
  half (a float32's bits, `_bf16_bits`): `low` moves down, `high` stays."""
  return low >> 16 if high is None else (low >> 16) | high


def _assemble(part_refs, xs_ref, s: int, r: int):
  """Parts -> the block as bfloat16 MXU tiles `[s // T, 128, D]`, through
  the sample-major scratch `xs_ref`, uint32 `[s * R // 2, D]`: the word at
  row `s * R // 2 + i` holds parts 2i (low half) and 2i + 1 (high half) of
  sample s, which is how a bfloat16 tile packs rows 2i and 2i + 1, so the
  scratch read back and `pltpu.bitcast` to bfloat16 IS the block: no
  float32 copy of it is stored, loaded or rounded. A word is made in
  registers from the two parts' float32 bits (a bfloat16 is the high half
  of its float32). The words of padded parts are zeroed on EVERY grid step:
  the scratch is uninitialised memory and 0 * NaN is NaN."""
  f = len(part_refs)
  half = r // 2
  d = xs_ref.shape[-1]

  def bits(ref):
    return pltpu.bitcast(ref[...].astype(jnp.float32), jnp.uint32)

  for i in range(half):
    if 2 * i >= f:
      words = jnp.zeros((s, d), jnp.uint32)
    else:
      words = _words(bits(part_refs[2 * i]),
                     bits(part_refs[2 * i + 1]) if 2 * i + 1 < f else None)
    xs_ref[pl.ds(i, s, stride=half), :] = words
  x = pltpu.bitcast(xs_ref[...], jnp.bfloat16)  # [s * R, D]
  return x.reshape(s * r // TILE, TILE, d)


def _parts_fwd_kernel(f, where, m_ref, *refs):
  # refs = f part refs, acts_ref, then the scratches xs, gs
  part_refs, acts_ref, xs_ref, gs_ref = refs[:f], refs[f], refs[-2], refs[-1]
  s, npair = acts_ref.shape
  r = rows_per_sample(f)
  x4 = _assemble(part_refs, xs_ref, s, r)
  gram = jax.lax.dot_general(
      x4, x4, (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)  # [s // T, 128, 128]
  # two samples' rows p as one word: row p of every sample then comes back
  # by one strided load as the selection's left operand in the MXU's own
  # packing (rows 2m and 2m + 1 of a bfloat16 [s, 128] are samples 2m and
  # 2m + 1), with no float32 -> bfloat16 `astype` of it
  per_tile = TILE // r
  pairs = gram.reshape(s // per_tile, per_tile // 2, 2, r, TILE)
  words = _words(_bf16_bits(pairs[:, :, 0]), _bf16_bits(pairs[:, :, 1]))
  gs_ref[...] = words.reshape(s // 2 * r, TILE)
  own = _own_block_words(s, r)
  acc = [jnp.zeros((s, TILE), jnp.float32)] * pl.cdiv(npair, TILE)
  for p in range(f):
    if not where[p]:
      continue  # row 0 without self-interaction: no pair of its own
    row = pltpu.bitcast(gs_ref[pl.ds(p, s // 2, stride=r), :] & own,
                        jnp.bfloat16)  # [s, 128]
    for t, i in where[p]:
      acc[t] = acc[t] + jnp.dot(row, m_ref[i],
                                preferred_element_type=jnp.float32)
  for t, a in enumerate(acc):
    lo = t * TILE
    hi = min(lo + TILE, npair)
    acts_ref[:, lo:hi] = a[:, :hi - lo]


def _parts_bwd_kernel(f, where, mt_ref, dacts_ref, *refs):
  # refs = f part refs, f cotangent out refs, then the scratches xs, ds, dx
  part_refs, out_refs = refs[:f], refs[f:2 * f]
  xs_ref, ds_ref, dx_ref = refs[-3:]
  s, npair = dacts_ref.shape
  r = rows_per_sample(f)
  x4 = _assemble(part_refs, xs_ref, s, r)
  da = [dacts_ref[:, lo:min(lo + TILE, npair)].astype(jnp.bfloat16)
        for lo in range(0, npair, TILE)]
  own = _own_block_mask(s, r)
  half = r // 2

  def dsym_row(p):  # row p of every sample's d_sym, [s, 128] float32
    row = jnp.zeros((s, TILE), jnp.float32)
    for t, i in where[p]:
      row = row + jnp.dot(da[t], mt_ref[i, :da[t].shape[1], :],
                          preferred_element_type=jnp.float32)
    return row

  # blockdiag(d_sym) is assembled as the features are: rows 2i and 2i + 1
  # rounded to bfloat16 in registers and stored as one word. Words (j, i)
  # past the parts are never written: a product's row depends on the same
  # row of its left operand alone, and those rows of dx are never read
  for i in range(pl.cdiv(f, 2)):
    words = _words(
        _bf16_bits(dsym_row(2 * i)),
        _bf16_bits(dsym_row(2 * i + 1)) if 2 * i + 1 < f else None)
    ds_ref[pl.ds(i, s, stride=half), :] = jnp.where(own, words,
                                                    jnp.uint32(0))
  ds4 = pltpu.bitcast(ds_ref[...], jnp.bfloat16).reshape(
      s * r // TILE, TILE, TILE)
  dx = jax.lax.dot_general(
      ds4, x4, (((2,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)  # [s // T, 128, D]
  # the way out is `_assemble`'s way in, backwards: round to the cotangent's
  # bfloat16, keep the tile's packed rows as words of two parts, and split
  # a word after the strided load
  dx16 = (2.0 * dx).astype(jnp.bfloat16).reshape(s * r, dx.shape[-1])
  dx_ref[...] = pltpu.bitcast(dx16, jnp.uint32)
  for i in range(pl.cdiv(f, 2)):
    words = dx_ref[pl.ds(i, s, stride=half), :]
    out_refs[2 * i][...] = pltpu.bitcast(
        words << 16, jnp.float32).astype(out_refs[2 * i].dtype)
    if 2 * i + 1 < f:
      out_refs[2 * i + 1][...] = pltpu.bitcast(
          words & jnp.uint32(HIGH_HALF),
          jnp.float32).astype(out_refs[2 * i + 1].dtype)


def _check_block(b: int, block: int):
  # 16: a word pairs two samples and a vreg holds 8 words' sublanes; it also
  # makes the block a whole number of tiles for every samples_per_tile
  if block % 16 or b % block:
    raise ValueError(f"a block of {block} samples must be a multiple of 16 "
                     f"and divide the batch {b}")


def interact_parts_fwd(parts, m_np: np.ndarray, *, block: int = FWD_BLOCK,
                       interpret: bool = False) -> jax.Array:
  """f x [B, D] bf16 parts -> [B, P] f32 pair activations; `m_np` is the
  selection tensor `M [f, f, P]` (numpy: its zero tiles are skipped)."""
  f = len(parts)
  b, d = parts[0].shape
  npair = m_np.shape[-1]
  r = rows_per_sample(f)
  _check_block(b, block)
  tiles, where = fwd_select_np(m_np)
  return pl.pallas_call(
      functools.partial(_parts_fwd_kernel, f, where),
      grid=(b // block,),
      in_specs=[pl.BlockSpec(tiles.shape, lambda i: (0, 0, 0))] + [
          pl.BlockSpec((block, d), lambda i: (i, 0)) for _ in range(f)
      ],
      out_specs=pl.BlockSpec((block, npair), lambda i: (i, 0)),
      out_shape=out_struct((b, npair), jnp.float32, parts),
      scratch_shapes=[pltpu.VMEM((block * r // 2, d), jnp.uint32),
                      pltpu.VMEM((block * r // 2, TILE), jnp.uint32)],
      interpret=interpret,
      name=PARTS_FWD_NAME,
  )(jnp.asarray(tiles, jnp.bfloat16), *parts)


def interact_parts_bwd(d_acts: jax.Array, parts, m_np: np.ndarray, *,
                       block: int = BWD_BLOCK, interpret: bool = False):
  """[B, P] cotangent -> per-part [B, D] bf16 cotangents; `m_np` as the
  forward's."""
  f = len(parts)
  b, d = parts[0].shape
  npair = m_np.shape[-1]
  r = rows_per_sample(f)
  _check_block(b, block)
  tiles, where = bwd_select_np(m_np)
  outs = pl.pallas_call(
      functools.partial(_parts_bwd_kernel, f, where),
      grid=(b // block,),
      in_specs=[
          pl.BlockSpec(tiles.shape, lambda i: (0, 0, 0)),
          pl.BlockSpec((block, npair), lambda i: (i, 0)),
      ] + [
          pl.BlockSpec((block, d), lambda i: (i, 0)) for _ in range(f)
      ],
      out_specs=[
          pl.BlockSpec((block, d), lambda i: (i, 0)) for _ in range(f)
      ],
      out_shape=[out_struct((b, d), jnp.bfloat16, d_acts, parts)
                 for _ in range(f)],
      scratch_shapes=[pltpu.VMEM((block * r // 2, d), jnp.uint32),
                      pltpu.VMEM((block * r // 2, TILE), jnp.uint32),
                      pltpu.VMEM((block * r // 2, d), jnp.uint32)],
      interpret=interpret,
      name=PARTS_BWD_NAME,
  )(jnp.asarray(tiles, jnp.bfloat16), d_acts, *parts)
  return tuple(outs)
