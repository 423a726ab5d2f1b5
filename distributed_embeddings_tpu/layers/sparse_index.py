"""A learned sparse-attention indexer, and attention under the selection it
makes (DeepSeek-Sparse-Attention's form, as ``sa_config`` of a published
model sizes it).

Beside the main attention of a layer sits a small scorer. From the layer's
normalised input, DETACHED, it makes per position ``qI [Hi, di]`` (``Hi``
index heads), one shared key ``kI [di]`` and a weight a head ``w [Hi]``, and
scores every earlier key of the query's document:

  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``

in float32 with the product at ``highest`` precision (a selection flips on
rounding, as an expert's choice does). ``visible(t)`` is ``{s <= t, seg[s] ==
seg[t]}``; ``S(t)`` is its ``topk`` members of largest ``I[t, s]``, all of
them where there are fewer, ties to the lower ``s`` (``lax.top_k``'s rule).
The main attention then runs over ``S(t)`` alone:

  ``o[t] = sum_{s in S(t)} softmax_{S(t)}(q[t] . k[s]) v[s]``   per head,

and the indexer is trained by a loss of its own, which pulls its softmax over
``S(t)`` towards what the main attention did there, the target detached:

  ``p[t, s]`` = the heads' mean of the main attention's probabilities,
  ``KL[t] = sum_{s in S(t)} p[t, s] (log p[t, s] - log softmax_{S(t)}(I[t, :])[s])``.

So the language-model loss reaches ``q, k, v`` and never the indexer (a
selection has no gradient), and the KL reaches ``qI, kI, w`` and nothing else.

:func:`sparse_attention` is all of that from the projected operands. The
selection is made a tile of ``tile`` queries at a time against the keys up to
the end of the tile's run, in XLA on every backend: index scores, the k-th
value, the mask, packed to bits. What runs the attention under that mask is
read off the backend (:func:`attention_kernels`; no option names it):

*On a TPU* the four Mosaic kernels of ``ops/pallas_sparse_attn.py``, one call
a pass a sequence: the mask goes in as data (int8 ``[T, T]``, assembled from
the tiles' selections and alive for the layer only), a block of ``tile``
queries against a block of ``tile`` keys at a time, all ``G`` heads of a
key-value head under one read of the mask block, scores and probabilities in
VMEM only, and a block with no selected pair (above the diagonal, another
document's) neither fetched nor multiplied. The forward kernel leaves ``o``
and the log-sum-exp; a second kernel the heads' mean probabilities ``[T, T]``
(float32, summed from float32), which is the KL's target: the KL itself and
its gradient stay XLA over ``[tile, extent]`` arrays. The backward
(``jax.custom_vjp``) unpacks the kept bits to the same mask, runs ``dq`` and
``dk, dv`` kernels that rebuild a block's probabilities from the kept
log-sum-exp; the ``dq`` kernel sums them over the heads as it goes, so the
KL's target costs the backward no pass of its own.

*Elsewhere* (and for shapes the kernels do not take) the tile loop: a tile's
scores against ``k[:extent]`` whole in XLA, no ``[T, T]`` array of a head
alive (at 16,384 positions one float32 head of it is 1.07 GB), forward and
backward written out: JAX's own transpose of a loop over tiles keeps every
tile's probabilities at once, and a ``jax.checkpoint`` a tile runs each
tile's forward three times under a rematerialised layer. This is the CPU's
path, every test's and counting tool's, and the kernels' oracle
(``tests/test_pallas_sparse_attn.py``, ``tools/smoke_pallas_sparse_attn.py``).

What outlives the forward on either path, under names a rematerialised layer
keeps (``layers/remat.py``): the selection, bit-packed (``T x extent / 8``
bytes: 21 MB a layer at 16,384 positions), so that the top-k runs ONCE a
layer a step; and the attention's output and log-sum-exp, so that the rebuilt
layer runs no attention at all. The heads' mean probabilities are rebuilt,
never kept (268 MB a layer at 8,192 positions).

Selecting (:func:`select_topk`) is a k-th value and a comparison, not a sort:
the floats are mapped to integers of the same order and the k-th largest is
found bit by bit, 32 counts over the tile; equal scores at the threshold are
taken from the lowest index by a running count. The set is exactly
``lax.top_k``'s (``tests/test_sparse_index.py``).

The main attention's products are handed what the MXU multiplies at default
precision (``ops.packed_table.mxu_operand_dtype``: bfloat16 on a TPU, the
operands' own type elsewhere), float32 out, in the kernels as in the tile loop;
so are the three products of the indexer's BACKWARD. Only the forward score,
which decides, is at ``highest``. Max, exp, sums, the log-sum-exp and the
heads' mean are float32 on both paths.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import pallas_sparse_attn as psa
from ..ops.packed_table import mxu_operand_dtype
from ..telemetry import scopes
from .remat import SPARSE_ATTN_RESIDUALS, SPARSE_SELECTION

# Runs of tiles that share a key extent (the end of the run's last tile): a
# tile's keys are a static slice, so each run is one loop body to compile.
# Four leave a quarter more pairs than the causal triangle; one would leave
# twice as many
MAX_TILE_RUNS = 4


def tile_runs(length: int, tile: int) -> List[Tuple[int, int, int]]:
  """``[(first query, tiles, key extent)]``: the ``length / tile`` tiles in at
  most ``MAX_TILE_RUNS`` consecutive runs of nearly equal size."""
  if length % tile:
    raise ValueError(f"{length} positions in tiles of {tile}")
  n = length // tile
  runs = min(MAX_TILE_RUNS, n)
  out, first = [], 0
  for r in range(runs):
    count = n // runs + (r < n % runs)
    out.append((first * tile, count, (first + count) * tile))
    first += count
  return out


def select_topk(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
  """``scores [..., n]`` float32, ``visible [..., n]`` bool -> bool
  ``[..., n]``: the ``k`` visible entries of largest score in each row, every
  visible entry where a row has no more than ``k``; of equal scores the
  lower index first. No NaN among the visible scores."""
  bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
  # a signed integer ordered as the float is, then the same order unsigned;
  # 0 (which no float but one NaN maps to) for what cannot be chosen
  ordered = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
  key = lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(1 << 31)
  key = jnp.where(visible, key, jnp.uint32(0))

  def one_bit(i, kth):
    """The largest value that at least ``k`` keys reach, a bit a time."""
    trial = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
    reach = jnp.sum(key >= trial, axis=-1, keepdims=True, dtype=jnp.int32)
    return jnp.where(reach >= k, trial, kth)

  kth = lax.fori_loop(0, 32, one_bit,
                      jnp.zeros(key.shape[:-1] + (1,), jnp.uint32))
  above, level = key > kth, key == kth
  room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
  first = jnp.cumsum(level, axis=-1, dtype=jnp.int32) <= room
  return (above | (level & first)) & visible


def pack_bits(mask: jax.Array) -> jax.Array:
  """bool ``[rows, n]`` -> uint8 ``[rows, ceil(n / 8)]``: bit ``b`` of byte
  ``j`` is ``mask[:, b * ceil(n / 8) + j]`` (eight contiguous slices side by
  side, so the minor dimension is never split)."""
  rows, n = mask.shape
  width = -(-n // 8)
  planes = jnp.pad(mask, ((0, 0), (0, 8 * width - n))).reshape(rows, 8, width)
  shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
  return jnp.sum(planes.astype(jnp.uint8) << shifts, axis=1, dtype=jnp.uint8)


def unpack_bits(packed: jax.Array, n: int) -> jax.Array:
  """:func:`pack_bits` undone: uint8 ``[rows, ceil(n / 8)]`` -> bool
  ``[rows, n]``."""
  rows, width = packed.shape
  shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
  planes = (packed[:, None, :] >> shifts) & jnp.uint8(1)
  return planes.astype(bool).reshape(rows, 8 * width)[:, :n]


def index_scores(qi, wi, ki, precision=lax.Precision.HIGHEST):
  """``qi [q, Hi, di]``, ``wi [q, Hi]``, ``ki [s, di]`` -> (``I [q, s]``,
  the heads' products ``[Hi, q, s]`` before the ReLU), float32."""
  raw = jnp.einsum("qhd,sd->hqs", qi, ki, precision=precision,
                   preferred_element_type=jnp.float32)
  return jnp.sum(wi.T[:, :, None] * jax.nn.relu(raw), axis=0), raw


def _visible(seg_q, seg_k, first_query: int):
  """``[q, s]`` bool: key ``s`` is at or before query ``first_query + i`` and
  of its document."""
  at = first_query + jnp.arange(seg_q.shape[0])
  return (jnp.arange(seg_k.shape[0])[None, :] <= at[:, None]) \
      & (seg_q[:, None] == seg_k[None, :])


def _masked_softmax(x, mask):
  """Over the last axis where ``mask``: -> (probabilities, 0 outside the
  mask; log-sum-exp). Every row has a member."""
  x = jnp.where(mask, x, -jnp.inf)
  top = jnp.max(x, axis=-1, keepdims=True)
  ex = jnp.exp(x - top)
  total = jnp.sum(ex, axis=-1, keepdims=True)
  return ex / total, (top + jnp.log(total))[..., 0]


def _kl(target, scores, selected):
  """``sum_s target (log target - log softmax_selected(scores))`` a row."""
  _, lse = _masked_softmax(scores, selected)
  live = selected & (target > 0)
  return jnp.sum(jnp.where(
      live, target * (jnp.log(jnp.where(live, target, 1.0))
                      - (scores - lse[:, None])), 0.0), axis=-1)


def _tiles(x, first: int, count: int, tile: int):
  """Rows ``first .. first + count * tile`` of ``x`` as ``[count, tile, ...]``."""
  return x[first:first + count * tile].reshape((count, tile) + x.shape[1:])


def attention_kernels(length: int, head_dim: int, tile: int):
  """What runs the attention under the selection here: ``None`` for the tile
  loop in XLA (any backend but a TPU, and shapes the kernels do not take),
  else the kernels of ``ops/pallas_sparse_attn.py`` with this for their
  ``interpret`` (``False``: on the chip). It reads what it can observe; no
  option names a path. A test that wants the kernels in Pallas's interpreter
  replaces this function."""
  if jax.default_backend() == "tpu" and psa.fits(length, head_dim, tile, tile):
    return False
  return None


def _select_tile(topk, tile, at, qi_t, wi_t, seg_t, ki_e, seg_e):
  """The tile of queries that starts at position ``at`` against the keys of
  its run -> (the index score ``[tile, extent]``, the selection, its bits,
  int32 ``[4]``: selected pairs, visible pairs, queries with more than
  ``topk`` visible keys, ``tile x tile`` blocks with a selected pair)."""
  seen = _visible(seg_t, seg_e, at)
  with jax.named_scope(scopes.SPARSE_INDEX):
    with jax.named_scope(scopes.INDEX_SCORES):
      score, _ = index_scores(qi_t, wi_t, ki_e)
    with jax.named_scope(scopes.INDEX_SELECT):
      chosen = select_topk(score, seen, topk)
      bits = pack_bits(chosen)
  n_seen = jnp.sum(seen, axis=-1, dtype=jnp.int32)
  attended = jnp.any(chosen.reshape(tile, -1, tile), axis=(0, 2))
  counts = jnp.stack([jnp.sum(chosen, dtype=jnp.int32), jnp.sum(n_seen),
                      jnp.sum(n_seen > topk, dtype=jnp.int32),
                      jnp.sum(attended, dtype=jnp.int32)])
  return score, chosen, bits, counts


def _index_backward_tile(dkl, target, chosen, qi_t, wi_t, ki_e):
  """The KL's gradient through one tile's index score, rebuilt in one pass
  of the operands' type (``ki_e`` comes cast): ``target [tile, extent]`` the
  heads' mean probabilities -> (``dqi``, ``dwi`` of the tile, what the tile
  adds to ``dki`` of its run's keys)."""
  cd, f32 = ki_e.dtype, jnp.float32
  with jax.named_scope(scopes.SPARSE_INDEX):
    with jax.named_scope(scopes.INDEX_SCORES):
      score, raw = index_scores(qi_t.astype(cd), wi_t, ki_e, precision=None)
    with jax.named_scope(scopes.INDEX_LOSS):
      p_index, _ = _masked_softmax(score, chosen)
      dscore = dkl * (p_index * jnp.sum(target, axis=-1, keepdims=True)
                      - target)
    with jax.named_scope(scopes.INDEX_SCORES):
      dwi_t = jnp.sum(dscore[None] * jax.nn.relu(raw), axis=-1).T
      draw = jnp.where(raw > 0, dscore[None] * wi_t.T[:, :, None],
                       0.0).astype(cd)
      dqi_t = jnp.einsum("hqs,sd->qhd", draw, ki_e,
                         preferred_element_type=f32)
      dki_t = jnp.einsum("hqs,qhd->sd", draw, qi_t.astype(cd),
                         preferred_element_type=f32)
  return dqi_t, dwi_t, dki_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sparse_attention(topk, tile, q, k, v, qi, ki, wi, seg):
  return _forward(topk, tile, q, k, v, qi, ki, wi, seg)[0]


def _forward(topk, tile, q, k, v, qi, ki, wi, seg):
  """One sequence: ``q [T, Hkv, G, hd]`` (scaled), ``k, v [T, Hkv, hd]``,
  ``qi [T, Hi, di]``, ``ki [T, di]``, ``wi [T, Hi]``, ``seg [T]`` ->
  ((``o`` like ``q``, the KLs summed over queries, int32 ``[4]``: selected
  pairs, visible pairs, queries with more than ``topk`` visible keys,
  ``tile x tile`` blocks with a selected pair), residuals)."""
  interpret = attention_kernels(q.shape[0], q.shape[-1], tile)
  if interpret is None:
    return _forward_tiles(topk, tile, q, k, v, qi, ki, wi, seg)
  return _forward_kernels(topk, tile, interpret, q, k, v, qi, ki, wi, seg)


def _backward(topk, tile, residuals, cotangents):
  del topk
  q = residuals[0]
  interpret = attention_kernels(q.shape[0], q.shape[-1], tile)
  if interpret is None:
    return _backward_tiles(tile, residuals, cotangents)
  return _backward_kernels(tile, interpret, residuals, cotangents)


def _named(operands, packed, o, lse, kls, counts):
  """What a forward returns, ``(outputs, residuals)``, with what outlives a
  rematerialised layer named (``layers/remat.py``): the runs' packed
  selections, the attention's output and its log-sum-exp."""
  packed = tuple(checkpoint_name(bits, SPARSE_SELECTION) for bits in packed)
  o = checkpoint_name(o, SPARSE_ATTN_RESIDUALS)
  lse = checkpoint_name(lse, SPARSE_ATTN_RESIDUALS)
  return (o, kls, counts), (*operands, packed, o, lse)


def _forward_tiles(topk, tile, q, k, v, qi, ki, wi, seg):
  """The forward a tile of queries at a time, every product XLA's."""
  cd = mxu_operand_dtype(q.dtype)
  outs, packed, lses, kls, counts = [], [], [], 0.0, 0

  for first, count, extent in tile_runs(q.shape[0], tile):
    k_e, v_e = k[:extent].astype(cd), v[:extent].astype(cd)
    ki_e, seg_e = ki[:extent], seg[:extent]

    def one_tile(xs, first=first, k_e=k_e, v_e=v_e, ki_e=ki_e, seg_e=seg_e):
      i, q_t, qi_t, wi_t, seg_t = xs
      score, chosen, bits, count_t = _select_tile(
          topk, tile, first + i * tile, qi_t, wi_t, seg_t, ki_e, seg_e)
      with jax.named_scope(scopes.ATTN_CORE):
        s = jnp.einsum("qkgd,skd->kgqs", q_t.astype(cd), k_e,
                       preferred_element_type=jnp.float32)
        s = jnp.where(chosen[None, None], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        ex = jnp.exp(s - top)
        total = jnp.sum(ex, axis=-1, keepdims=True)
        o_t = jnp.einsum("kgqs,skd->qkgd", ex.astype(cd), v_e,
                         preferred_element_type=jnp.float32) \
            / jnp.moveaxis(total[..., 0], -1, 0)[..., None]
        lse_t = (top + jnp.log(total))[..., 0]                  # [Hkv, G, q]
      with jax.named_scope(scopes.SPARSE_INDEX), \
          jax.named_scope(scopes.INDEX_LOSS):
        target = jnp.mean(ex / total, axis=(0, 1))              # [q, s]
        kl_t = jnp.sum(_kl(target, score, chosen))
      return o_t.astype(q.dtype), bits, lse_t, kl_t, count_t

    o_r, bits_r, lse_r, kl_r, count_r = lax.map(one_tile, (
        jnp.arange(count), *(_tiles(x, first, count, tile)
                             for x in (q, qi, wi, seg))))
    outs.append(o_r.reshape((count * tile,) + q.shape[1:]))
    packed.append(bits_r)
    lses.append(lse_r)
    kls, counts = kls + jnp.sum(kl_r), counts + jnp.sum(count_r, axis=0)

  return _named((q, k, v, qi, ki, wi, seg), packed, jnp.concatenate(outs),
                jnp.concatenate(lses), kls, counts)


def _backward_tiles(tile, residuals, cotangents):
  """A tile at a time: the scores again from the kept log-sum-exp, then the
  attention's four products; the target again from those probabilities, the
  indexer's score again, and the KL's gradient through it."""
  q, k, v, qi, ki, wi, seg, packed, o, lse = residuals
  do, dkl, _ = cotangents
  cd = mxu_operand_dtype(q.dtype)
  f32 = jnp.float32
  dk, dv, dki = (jnp.zeros(x.shape, f32) for x in (k, v, ki))
  dqs, dqis, dwis = [], [], []

  for (first, count, extent), bits_r in zip(tile_runs(q.shape[0], tile),
                                            packed):
    k_e, v_e = k[:extent].astype(cd), v[:extent].astype(cd)
    ki_e = ki[:extent].astype(cd)

    def one_tile(carry, xs, extent=extent, k_e=k_e, v_e=v_e, ki_e=ki_e):
      dk_e, dv_e, dki_e = carry
      q_t, qi_t, wi_t, do_t, o_t, lse_t, bits = xs
      chosen = unpack_bits(bits, extent)
      with jax.named_scope(scopes.ATTN_CORE):
        q_c, do_c = q_t.astype(cd), do_t.astype(cd)
        s = jnp.einsum("qkgd,skd->kgqs", q_c, k_e, preferred_element_type=f32)
        p = jnp.where(chosen[None, None], jnp.exp(s - lse_t[..., None]), 0.0)
        dp = jnp.einsum("qkgd,skd->kgqs", do_c, v_e,
                        preferred_element_type=f32)
        delta = jnp.moveaxis(jnp.sum(do_t.astype(f32) * o_t.astype(f32),
                                     axis=-1), 0, -1)           # [Hkv, G, q]
        ds = (p * (dp - delta[..., None])).astype(cd)
        p_c = p.astype(cd)
        dv_e = dv_e + jnp.einsum("kgqs,qkgd->skd", p_c, do_c,
                                 preferred_element_type=f32)
        dk_e = dk_e + jnp.einsum("kgqs,qkgd->skd", ds, q_c,
                                 preferred_element_type=f32)
        dq_t = jnp.einsum("kgqs,skd->qkgd", ds, k_e,
                          preferred_element_type=f32)
      with jax.named_scope(scopes.SPARSE_INDEX), \
          jax.named_scope(scopes.INDEX_LOSS):
        target = jnp.mean(p, axis=(0, 1))
      dqi_t, dwi_t, dki_t = _index_backward_tile(dkl, target, chosen, qi_t,
                                                 wi_t, ki_e)
      return (dk_e, dv_e, dki_e + dki_t), (dq_t, dqi_t, dwi_t)

    rows = slice(first // tile, first // tile + count)
    (dk_e, dv_e, dki_e), (dq_r, dqi_r, dwi_r) = lax.scan(
        one_tile, (dk[:extent], dv[:extent], dki[:extent]),
        (*(_tiles(x, first, count, tile) for x in (q, qi, wi, do, o)),
         lse[rows], bits_r))
    dk, dv, dki = (x.at[:extent].set(x_e) for x, x_e in
                   ((dk, dk_e), (dv, dv_e), (dki, dki_e)))
    dqs.append(dq_r.reshape((count * tile,) + q.shape[1:]))
    dqis.append(dqi_r.reshape((count * tile,) + qi.shape[1:]))
    dwis.append(dwi_r.reshape((count * tile,) + wi.shape[1:]))

  grads = (jnp.concatenate(dqs), dk, dv, jnp.concatenate(dqis), dki,
           jnp.concatenate(dwis))
  return tuple(g.astype(x.dtype) for g, x in
               zip(grads, (q, k, v, qi, ki, wi))) + (None,)


def _whole_mask(chosen_runs, length: int):
  """Runs of int8 ``[tiles, tile, extent]`` -> ``[T, T]``: a run's rows
  padded with zeros over the keys past its extent."""
  return jnp.concatenate([
      jnp.pad(c.reshape(-1, c.shape[-1]), ((0, 0), (0, length - c.shape[-1])))
      for c in chosen_runs])


def unpacked_runs(packed, length: int, tile: int):
  """The forward's kept bits, a run of tiles each -> the selections as int8
  ``[tiles, tile, extent]`` a run."""
  return [
      unpack_bits(bits.reshape(count * tile, -1), extent).astype(jnp.int8)
      .reshape(count, tile, extent)
      for (_, count, extent), bits in zip(tile_runs(length, tile), packed)]


def _kernel_blocks(q, tile: int, interpret: bool):
  """What every kernel call of a sequence is told beside its operands: the
  kernels' blocks are the selection's tiles."""
  return dict(group=q.shape[2], hd=q.shape[3], block_q=tile, block_k=tile,
              interpret=interpret)


def _kernel_operands(cd, *arrays):
  """``[T, ...]`` -> ``[T, heads x head_dim]`` in the products' type: the
  kernels' layout is the model's own."""
  return tuple(x.astype(cd).reshape(x.shape[0], -1) for x in arrays)


def _forward_kernels(topk, tile, interpret, q, k, v, qi, ki, wi, seg):
  """The forward on a TPU: the selection a tile at a time in XLA as ever,
  then the whole sequence's attention in one kernel under that mask, the
  heads' mean probabilities in a second, and the KL a tile at a time in XLA
  over ``[tile, extent]`` arrays."""
  length = q.shape[0]
  cd = mxu_operand_dtype(q.dtype)
  runs = tile_runs(length, tile)
  at = _kernel_blocks(q, tile, interpret)
  packed, scores, chosen_runs, counts = [], [], [], 0

  for first, count, extent in runs:
    ki_e, seg_e = ki[:extent], seg[:extent]

    def select(xs, first=first, ki_e=ki_e, seg_e=seg_e):
      i, qi_t, wi_t, seg_t = xs
      score, chosen, bits, count_t = _select_tile(
          topk, tile, first + i * tile, qi_t, wi_t, seg_t, ki_e, seg_e)
      return score, chosen.astype(jnp.int8), bits, count_t

    score_r, chosen_r, bits_r, count_r = lax.map(select, (
        jnp.arange(count), *(_tiles(x, first, count, tile)
                             for x in (qi, wi, seg))))
    packed.append(bits_r)
    scores.append(score_r)
    chosen_runs.append(chosen_r)
    counts = counts + jnp.sum(count_r, axis=0)

  with jax.named_scope(scopes.ATTN_CORE):
    mask = _whole_mask(chosen_runs, length)
    plan = psa.block_plan(psa.block_counts(mask, tile, tile))
    q2, k2, v2 = _kernel_operands(cd, q, k, v)
    o, lse = psa.attend(q2, k2, v2, mask, plan, **at)           # [Hkv, T, G]
  with jax.named_scope(scopes.SPARSE_INDEX), \
      jax.named_scope(scopes.INDEX_LOSS):
    target = psa.head_mean(q2, k2, lse, mask, plan, **at)       # [T, T]
    kls = 0.0
    for (first, count, extent), score_r, chosen_r in zip(runs, scores,
                                                         chosen_runs):
      kl_r = lax.map(
          lambda xs: jnp.sum(_kl(xs[0], xs[1], xs[2] != 0)),
          (_tiles(target[:, :extent], first, count, tile), score_r, chosen_r))
      kls = kls + jnp.sum(kl_r)

  return _named((q, k, v, qi, ki, wi, seg), packed,
                o.reshape(q.shape).astype(q.dtype), jnp.swapaxes(lse, 1, 2),
                kls, counts)


def _backward_kernels(tile, interpret, residuals, cotangents):
  """The backward on a TPU: the mask again from its bits, ``dq`` and
  ``dk, dv`` from two kernels that rebuild a block's probabilities from the
  kept log-sum-exp, the heads' mean probabilities out of the ``dq`` kernel
  (it forms every head's anyway), and the KL's gradient through the index
  score a tile at a time in XLA."""
  q, k, v, qi, ki, wi, seg, packed, o, lse = residuals           # [Hkv, G, T]
  do, dkl, _ = cotangents
  length = q.shape[0]
  cd = mxu_operand_dtype(q.dtype)
  f32 = jnp.float32
  at = _kernel_blocks(q, tile, interpret)

  with jax.named_scope(scopes.ATTN_CORE):
    chosen_runs = unpacked_runs(packed, length, tile)
    mask = _whole_mask(chosen_runs, length)
    counts = psa.block_counts(mask, tile, tile)
    plan, plan_t = psa.block_plan(counts), psa.block_plan(counts.T)
    q2, k2, v2, do2 = _kernel_operands(cd, q, k, v, do)
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)     # [T, Hkv, G]
    # the heads' mean probabilities come out of the same pass as dq
    dq, target = psa.grad_q(q2, k2, v2, do2, jnp.swapaxes(lse, 1, 2),
                            jnp.swapaxes(delta, 0, 1), mask, plan, **at)
    dk, dv = psa.grad_kv(q2, k2, v2, do2, lse, jnp.moveaxis(delta, 0, 2),
                         mask.T, plan_t, **at)

  dki = jnp.zeros(ki.shape, f32)
  dqis, dwis = [], []
  for (first, count, extent), chosen_r in zip(tile_runs(length, tile),
                                              chosen_runs):
    ki_e = ki[:extent].astype(cd)

    def one_tile(dki_e, xs, ki_e=ki_e):
      qi_t, wi_t, target_t, chosen_t = xs
      dqi_t, dwi_t, dki_t = _index_backward_tile(
          dkl, target_t, chosen_t != 0, qi_t, wi_t, ki_e)
      return dki_e + dki_t, (dqi_t, dwi_t)

    dki_e, (dqi_r, dwi_r) = lax.scan(
        one_tile, dki[:extent],
        (*(_tiles(x, first, count, tile)
           for x in (qi, wi, target[:, :extent])), chosen_r))
    dki = dki.at[:extent].set(dki_e)
    dqis.append(dqi_r.reshape((count * tile,) + qi.shape[1:]))
    dwis.append(dwi_r.reshape((count * tile,) + wi.shape[1:]))

  grads = (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
           jnp.concatenate(dqis), dki, jnp.concatenate(dwis))
  return tuple(g.astype(x.dtype) for g, x in
               zip(grads, (q, k, v, qi, ki, wi))) + (None,)


_sparse_attention.defvjp(_forward, _backward)


def sparse_attention(q, k, v, qi, ki, wi, seg, *, topk: int, tile: int):
  """``q [B, T, Hkv, G, hd]`` (already scaled), ``k, v [B, T, Hkv, hd]``,
  the indexer's ``qi [B, T, Hi, di]``, ``ki [B, T, di]`` and ``wi [B, T, Hi]``
  (already scaled), ``seg [B, T]`` the document of each position ->
  (``o`` like ``q``; the indexer's loss, ``mean_t KL[t]`` over every
  position of the batch; counters, int32 scalars: ``selected_pairs``,
  ``visible_pairs``, ``active_queries``: those with more than ``topk``
  visible keys; ``attended_blocks``, the ``tile x tile`` blocks of queries
  and keys with a selected pair, and ``skipped_blocks``, the rest of the
  ``(T / tile) ** 2`` a sequence: what the kernels run and what they pass
  over). Module docstring."""
  batch, length = q.shape[:2]
  tile = min(tile, length)
  one = functools.partial(_sparse_attention, topk, tile)
  operands = (q, k, v, qi, ki, wi, seg)
  if attention_kernels(length, q.shape[-1], tile) is None:
    o, kl, counts = jax.vmap(one)(*operands)
  else:
    # a kernel call a sequence: its block plan goes in by scalar prefetch
    o, kl, counts = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *(one(*(x[b] for x in operands)) for b in range(batch)))
  counts = jnp.sum(counts, axis=0)
  return o, jnp.sum(kl) / (batch * length), {
      "selected_pairs": counts[0], "visible_pairs": counts[1],
      "active_queries": counts[2], "attended_blocks": counts[3],
      "skipped_blocks": batch * (length // tile) ** 2 - counts[3]}
