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

:func:`sparse_attention` is all of that from the projected operands, a tile
of ``tile`` queries at a time against the keys up to the tile's end: no
``[T, T]`` array lives whole (at 16,384 positions one float32 head of it is
1.07 GB). A tile's mask is data, which no splash ``Mask`` can be, so the
products are XLA's. Forward and backward are written out (``jax.custom_vjp``):
JAX's own transpose of a loop over tiles keeps every tile's probabilities at
once, and a ``jax.checkpoint`` a tile runs each tile's forward three times
under a rematerialised layer. Here a tile's backward rebuilds its scores from
the kept log-sum-exp, as a flash kernel's does, and the KL's target comes out
of the same probabilities the attention's own backward needs.

What outlives the forward, under names a rematerialised layer keeps
(``layers/remat.py``): the selection, bit-packed (``T x extent / 8`` bytes:
21 MB a layer at 16,384 positions), so that the top-k runs ONCE a layer a
step; and the attention's output and log-sum-exp, so that the rebuilt layer
runs no attention at all.

Selecting (:func:`select_topk`) is a k-th value and a comparison, not a sort:
the floats are mapped to integers of the same order and the k-th largest is
found bit by bit, 32 counts over the tile; equal scores at the threshold are
taken from the lowest index by a running count. The set is exactly
``lax.top_k``'s (``tests/test_sparse_index.py``).

The main attention's products are handed what the MXU multiplies at default
precision (``ops.packed_table.mxu_operand_dtype``: bfloat16 on a TPU, the
operands' own type elsewhere), float32 out; so are the three products of the
indexer's BACKWARD. Only the forward score, which decides, is at ``highest``.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sparse_attention(topk, tile, q, k, v, qi, ki, wi, seg):
  return _forward(topk, tile, q, k, v, qi, ki, wi, seg)[0]


def _forward(topk, tile, q, k, v, qi, ki, wi, seg):
  """One sequence: ``q [T, Hkv, G, hd]`` (scaled), ``k, v [T, Hkv, hd]``,
  ``qi [T, Hi, di]``, ``ki [T, di]``, ``wi [T, Hi]``, ``seg [T]`` ->
  ((``o`` like ``q``, the KLs summed over queries, int32 ``[3]``: selected
  pairs, visible pairs, queries with more than ``topk`` visible keys),
  residuals)."""
  cd = mxu_operand_dtype(q.dtype)
  outs, packed, lses, kls, counts = [], [], [], 0.0, 0

  for first, count, extent in tile_runs(q.shape[0], tile):
    k_e, v_e = k[:extent].astype(cd), v[:extent].astype(cd)
    ki_e, seg_e = ki[:extent], seg[:extent]

    def one_tile(xs, first=first, k_e=k_e, v_e=v_e, ki_e=ki_e, seg_e=seg_e):
      i, q_t, qi_t, wi_t, seg_t = xs
      seen = _visible(seg_t, seg_e, first + i * tile)
      with jax.named_scope(scopes.SPARSE_INDEX):
        with jax.named_scope(scopes.INDEX_SCORES):
          score, _ = index_scores(qi_t, wi_t, ki_e)
        with jax.named_scope(scopes.INDEX_SELECT):
          chosen = select_topk(score, seen, topk)
          bits = pack_bits(chosen)
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
      n_seen = jnp.sum(seen, axis=-1, dtype=jnp.int32)
      count_t = jnp.stack([jnp.sum(chosen, dtype=jnp.int32), jnp.sum(n_seen),
                           jnp.sum(n_seen > topk, dtype=jnp.int32)])
      return o_t.astype(q.dtype), bits, lse_t, kl_t, count_t

    o_r, bits_r, lse_r, kl_r, count_r = lax.map(one_tile, (
        jnp.arange(count), *(_tiles(x, first, count, tile)
                             for x in (q, qi, wi, seg))))
    outs.append(o_r.reshape((count * tile,) + q.shape[1:]))
    packed.append(checkpoint_name(bits_r, SPARSE_SELECTION))
    lses.append(lse_r)
    kls, counts = kls + jnp.sum(kl_r), counts + jnp.sum(count_r, axis=0)

  o = checkpoint_name(jnp.concatenate(outs), SPARSE_ATTN_RESIDUALS)
  lse = checkpoint_name(jnp.concatenate(lses), SPARSE_ATTN_RESIDUALS)
  return (o, kls, counts), (q, k, v, qi, ki, wi, seg, tuple(packed), o, lse)


def _backward(topk, tile, residuals, cotangents):
  """A tile at a time: the scores again from the kept log-sum-exp, then the
  attention's four products; the target again from those probabilities, the
  indexer's score again, and the KL's gradient through it."""
  del topk
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
      with jax.named_scope(scopes.SPARSE_INDEX):
        with jax.named_scope(scopes.INDEX_LOSS):
          target = jnp.mean(p, axis=(0, 1))
        with jax.named_scope(scopes.INDEX_SCORES):
          score, raw = index_scores(qi_t.astype(cd), wi_t, ki_e,
                                    precision=None)
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
          dki_e = dki_e + jnp.einsum("hqs,qhd->sd", draw, qi_t.astype(cd),
                                     preferred_element_type=f32)
      return (dk_e, dv_e, dki_e), (dq_t, dqi_t, dwi_t)

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


_sparse_attention.defvjp(_forward, _backward)


def sparse_attention(q, k, v, qi, ki, wi, seg, *, topk: int, tile: int):
  """``q [B, T, Hkv, G, hd]`` (already scaled), ``k, v [B, T, Hkv, hd]``,
  the indexer's ``qi [B, T, Hi, di]``, ``ki [B, T, di]`` and ``wi [B, T, Hi]``
  (already scaled), ``seg [B, T]`` the document of each position ->
  (``o`` like ``q``; the indexer's loss, ``mean_t KL[t]`` over every
  position of the batch; counters, int32 scalars: ``selected_pairs``,
  ``visible_pairs``, ``active_queries``: those with more than ``topk``
  visible keys). Module docstring."""
  tile = min(tile, q.shape[1])
  one = functools.partial(_sparse_attention, topk, tile)
  o, kl, counts = jax.vmap(one)(q, k, v, qi, ki, wi, seg)
  counts = jnp.sum(counts, axis=0)
  return o, jnp.sum(kl) / (q.shape[0] * q.shape[1]), {
      "selected_pairs": counts[0], "visible_pairs": counts[1],
      "active_queries": counts[2]}
