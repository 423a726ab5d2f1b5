"""Attention under a mask that is DATA, as Mosaic kernels.

`layers/sparse_index.py` chooses, per query, the keys a learned indexer
scored highest; the set is known before the attention runs and no static
``Mask`` describes it, so JAX's splash kernels cannot take it. These four
kernels run the attention core under such a mask flash-style: a block of
queries against a block of keys at a time, the score block never leaves
VMEM, softmax statistics online, and a block in which nothing is selected is
neither fetched nor multiplied.

What a call is handed (one sequence of ``T`` positions; ``Hkv`` key-value
heads of ``G`` query heads each, ``hd`` a head):

  ``q, do  [T, Hkv * G * hd]``  the model's own row-major layout: a block is
                                ``[block_q, G * hd]``, a head a static slice
                                of 128-lane tiles. No transpose on the way in
                                or out.
  ``k, v   [T, Hkv * hd]``
  ``mask   int8 [T, T]``        1 where query ``t`` attends key ``s``; shared
                                by every head: a grid step reads one
                                ``[block_q, block_k]`` block of it and runs
                                the ``G`` heads of a key-value head under it
                                (a float32 bias a head is what this avoids).
  ``plan``                      :func:`block_plan` of the mask: per (query
                                block, key block) the number of selected
                                pairs, and for a block with none the block
                                whose operands are already in VMEM. Both go
                                in by scalar prefetch: the count gates the
                                step's body (``pl.when``), the other steers
                                the index maps so that an empty block starts
                                no DMA.

The kernels (their names in HLO and in a device trace):

  ``de_sparse_attn_fwd``   grid ``(Hkv, T / block_q, T / block_k)``, keys
      innermost: scores, running max and sum a head in VMEM scratch, the
      output accumulated in its own float32 block and divided at the last
      key block -> ``o``, the log-sum-exp.
  ``de_sparse_attn_mean``  grid ``(T / block_q, T / block_k, Hkv)``, heads
      innermost: ``p = exp(s - lse)`` again for every head of the block,
      summed in float32 into one ``[block_q, block_k]`` block -> the heads'
      mean probabilities ``[T, T]``, 0 in a skipped block: the target of the
      indexer's KL, which the forward needs for the loss's value. One extra
      QK product, in the forward only.
  ``de_sparse_attn_dq``    that grid too: ``p`` from the kept log-sum-exp,
      ``dp = do v^T``, ``ds = p (dp - delta)``, ``dq += ds k`` with every
      head's ``dq`` of the query block resident in VMEM (float32
      ``[block_q, Hkv * G * hd]``, 8 MB, a head's lanes found by the grid's
      own index), and the same ``p`` summed over the
      heads: the backward gets the KL's target from the probabilities it
      forms anyway.
  ``de_sparse_attn_dkv``   grid ``(Hkv, T / block_k, T / block_q)``, queries
      innermost, everything transposed (keys on the sublanes, so the mask
      comes transposed and the log-sum-exp as a row): ``dv += p^T do``,
      ``dk += ds^T q``, summed over the ``G`` heads in the output blocks.

Arithmetic: the six products take their operands as handed (bfloat16 on a
TPU: the caller casts, `ops.packed_table.mxu_operand_dtype`) and accumulate
in float32; max, exp, sums, log-sum-exp, ``delta`` and the heads' mean are
float32. A masked score is ``MASK_VALUE``, not ``-inf``: a query whose first
attended block holds none of its keys carries a finite running max, and what
it summed there is multiplied by ``exp(MASK_VALUE - m) == 0`` when its first
real key arrives. EVERY QUERY MUST SELECT A KEY (a causal selection always
keeps one: the query itself is visible).

Shapes (``fits``): ``hd`` and both blocks multiples of 128, the blocks
divide ``T``. Every wrapper takes ``interpret`` (Pallas's interpreter, any
backend: `tests/test_pallas_sparse_attn.py`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

NUM_LANES = 128
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
# of a chip's 128 MiB: a forward step holds q, o (double-buffered), the
# running statistics of G heads and a few [block_q, block_k] float32 values
# (some 20 MB); a dq step every head's dq of its query block, twice (16 MB),
# beside them
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

FWD_NAME = "de_sparse_attn_fwd"
MEAN_NAME = "de_sparse_attn_mean"
DQ_NAME = "de_sparse_attn_dq"
DKV_NAME = "de_sparse_attn_dkv"

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def fits(length: int, head_dim: int, block_q: int, block_k: int) -> bool:
  """Whether the kernels take these shapes (module docstring)."""
  return (head_dim % NUM_LANES == 0 and block_q % NUM_LANES == 0
          and block_k % NUM_LANES == 0 and length % block_q == 0
          and length % block_k == 0)


class BlockPlan(NamedTuple):
  """Per (row block, column block) of a mask, row-major and flat (scalar
  prefetch wants one dimension): ``counts`` the selected pairs, ``fetch``
  the column block to have in VMEM at that step: the block itself where it
  has a pair, else the nearest one before it in the row that has (after it,
  for the empty blocks a row starts with), so that consecutive steps name
  the same block and the pipeline starts no copy."""
  counts: jax.Array
  fetch: jax.Array


def block_counts(mask: jax.Array, block_q: int, block_k: int) -> jax.Array:
  """int8 ``[T, T]`` -> int32 ``[T / block_q, T / block_k]``: selected pairs
  a block."""
  rows, cols = mask.shape
  # along a row first (the minor dimension: no relayout of the mask), then
  # down the block's rows
  along = jnp.sum(mask.reshape(rows, cols // block_k, block_k), axis=2,
                  dtype=jnp.int32)
  return jnp.sum(along.reshape(rows // block_q, block_q, cols // block_k),
                 axis=1)


def block_plan(counts: jax.Array) -> BlockPlan:
  """:class:`BlockPlan` of ``counts [rows, cols]``."""
  n = counts.shape[1]
  at = jnp.arange(n, dtype=jnp.int32)[None, :]
  live = counts > 0
  before = lax.cummax(jnp.where(live, at, -1), axis=1)
  after = lax.cummin(jnp.where(live, at, n), axis=1, reverse=True)
  fetch = jnp.where(before >= 0, before, jnp.where(after < n, after, 0))
  return BlockPlan(counts.reshape(-1), fetch.astype(jnp.int32).reshape(-1))


def _keep(mask_ref):
  return mask_ref[...].astype(jnp.int32) != 0


# the outermost grid dimension of every kernel is independent; the two inside
# it carry an accumulator and the plan's "the block the step before left"
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _fwd_kernel(counts_ref, fetch_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, m_ref, l_ref, *, group: int, hd: int):
  del fetch_ref
  i, j, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
  repeats = hd // NUM_LANES

  @pl.when(j == 0)
  def init():
    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
    l_ref[...] = jnp.zeros_like(l_ref)

  @pl.when(counts_ref[i * nk + j] > 0)
  def run():
    keep = _keep(mask_ref)
    k, v = k_ref[...], v_ref[...]
    for g in range(group):
      cols = slice(g * hd, (g + 1) * hd)
      s = lax.dot_general(q_ref[:, cols], k, _NT,
                          preferred_element_type=jnp.float32)
      s = jnp.where(keep, s, MASK_VALUE)
      m_prev, l_prev = m_ref[g], l_ref[g]         # [bq, 128], equal lanes
      m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
      p = jnp.exp(s - jnp.tile(m_next, (1, s.shape[1] // NUM_LANES)))
      alpha = jnp.exp(m_prev - m_next)
      l_ref[g] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
      m_ref[g] = m_next
      o_ref[:, cols] = jnp.tile(alpha, (1, repeats)) * o_ref[:, cols] \
          + lax.dot_general(p.astype(v.dtype), v, _NN,
                            preferred_element_type=jnp.float32)

  @pl.when(j == nk - 1)
  def end():
    for g in range(group):
      cols = slice(g * hd, (g + 1) * hd)
      total = l_ref[g]
      o_ref[:, cols] = o_ref[:, cols] / jnp.tile(total, (1, repeats))
      lse_ref[:, g:g + 1] = (m_ref[g] + jnp.log(total))[:, :1]


def attend(q, k, v, mask, plan: BlockPlan, *, group: int, hd: int,
           block_q: int, block_k: int, interpret: bool = False):
  """-> (``o`` float32 ``[T, Hkv * G * hd]``, the log-sum-exp float32
  ``[Hkv, T, G]``). Module docstring."""
  length, hkv = _shapes(q, k, group, hd, block_q, block_k)
  nk = length // block_k
  kv_spec = pl.BlockSpec((block_k, hd),
                         lambda h, i, j, c, f: (f[i * nk + j], h))
  return pl.pallas_call(
      functools.partial(_fwd_kernel, group=group, hd=hd),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(hkv, length // block_q, nk),
          in_specs=[
              pl.BlockSpec((block_q, group * hd), lambda h, i, j, c, f: (i, h)),
              kv_spec, kv_spec,
              pl.BlockSpec((block_q, block_k),
                           lambda h, i, j, c, f: (i, f[i * nk + j])),
          ],
          out_specs=[
              pl.BlockSpec((block_q, group * hd), lambda h, i, j, c, f: (i, h)),
              pl.BlockSpec((None, block_q, group),
                           lambda h, i, j, c, f: (h, i, 0)),
          ],
          scratch_shapes=[pltpu.VMEM((group, block_q, NUM_LANES), jnp.float32),
                          pltpu.VMEM((group, block_q, NUM_LANES), jnp.float32)],
      ),
      out_shape=[out_struct(q.shape, jnp.float32, q, k, v, mask),
                 out_struct((hkv, length, group), jnp.float32, q, k, v,
                            mask)],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
      name=FWD_NAME,
  )(plan.counts, plan.fetch, q, k, v, mask)


def _heads_innermost_specs(length, hkv, group, hd, block_q, block_k):
  """Block specs of a grid ``(query block, key block, key-value head)``:
  ``q`` (or ``do``), ``k`` (or ``v``), a ``[Hkv, T, G]`` row statistic, and
  the mask's block. The steps of a block with no selected pair name the key
  block the plan gives and the last head, which is what the step before them
  left in VMEM."""
  nk = length // block_k

  def head(i, j, h, c):
    return jnp.where(c[i * nk + j] > 0, h, hkv - 1)

  return (
      pl.BlockSpec((block_q, group * hd),
                   lambda i, j, h, c, f: (i, head(i, j, h, c))),
      pl.BlockSpec((block_k, hd),
                   lambda i, j, h, c, f: (f[i * nk + j], head(i, j, h, c))),
      pl.BlockSpec((None, block_q, group),
                   lambda i, j, h, c, f: (head(i, j, h, c), i, 0)),
      pl.BlockSpec((block_q, block_k),
                   lambda i, j, h, c, f: (i, f[i * nk + j])))


def _mean_kernel(counts_ref, fetch_ref, q_ref, k_ref, lse_ref, mask_ref,
                 out_ref, *, group: int, hd: int):
  del fetch_ref
  i, j, nk = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
  h, hkv = pl.program_id(2), pl.num_programs(2)

  @pl.when(h == 0)
  def init():
    out_ref[...] = jnp.zeros_like(out_ref)

  @pl.when(counts_ref[i * nk + j] > 0)
  def run():
    keep = _keep(mask_ref)
    k = k_ref[...]
    total = out_ref[...]
    for g in range(group):
      s = lax.dot_general(q_ref[:, g * hd:(g + 1) * hd], k, _NT,
                          preferred_element_type=jnp.float32)
      total += jnp.exp(jnp.where(keep, s, MASK_VALUE) - lse_ref[:, g:g + 1])
    # the last head's step leaves the mean
    out_ref[...] = jnp.where(h == hkv - 1, total * (1.0 / (group * hkv)),
                             total)


def head_mean(q, k, lse, mask, plan: BlockPlan, *, group: int, hd: int,
              block_q: int, block_k: int, interpret: bool = False):
  """``lse [Hkv, T, G]`` -> float32 ``[T, T]``: the mean over all ``Hkv * G``
  heads of ``exp(q k^T - lse)`` where ``mask``, 0 elsewhere."""
  length, hkv = _shapes(q, k, group, hd, block_q, block_k)
  q_spec, k_spec, row_spec, block_spec = _heads_innermost_specs(
      length, hkv, group, hd, block_q, block_k)
  return pl.pallas_call(
      functools.partial(_mean_kernel, group=group, hd=hd),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(length // block_q, length // block_k, hkv),
          in_specs=[q_spec, k_spec, row_spec, block_spec],
          out_specs=pl.BlockSpec((block_q, block_k),
                                 lambda i, j, h, c, f: (i, j)),
      ),
      out_shape=out_struct((length, length), jnp.float32, q, k, lse, mask),
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
      name=MEAN_NAME,
  )(plan.counts, plan.fetch, q, k, lse, mask)


def _dq_kernel(counts_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, mask_ref, dq_ref, mean_ref, *, group: int, hd: int):
  del fetch_ref
  i, j, nk = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
  h, hkv = pl.program_id(2), pl.num_programs(2)

  # this key-value head's lanes of the query block's [block_q, Hkv * G * hd]
  at = lambda g: pl.ds(pl.multiple_of((h * group + g) * hd, NUM_LANES), hd)

  @pl.when(j == 0)
  def init_dq():
    for g in range(group):
      dq_ref[:, at(g)] = jnp.zeros((dq_ref.shape[0], hd), dq_ref.dtype)

  @pl.when(h == 0)
  def init_mean():
    mean_ref[...] = jnp.zeros_like(mean_ref)

  @pl.when(counts_ref[i * nk + j] > 0)
  def run():
    keep = _keep(mask_ref)
    k, v = k_ref[...], v_ref[...]
    total = mean_ref[...]
    for g in range(group):
      cols = slice(g * hd, (g + 1) * hd)
      s = lax.dot_general(q_ref[:, cols], k, _NT,
                          preferred_element_type=jnp.float32)
      p = jnp.exp(jnp.where(keep, s, MASK_VALUE) - lse_ref[:, g:g + 1])
      total += p
      dp = lax.dot_general(do_ref[:, cols], v, _NT,
                           preferred_element_type=jnp.float32)
      ds = (p * (dp - delta_ref[:, g:g + 1])).astype(k.dtype)
      dq_ref[:, at(g)] += lax.dot_general(
          ds, k, _NN, preferred_element_type=jnp.float32)
    # the last head's step leaves the mean
    mean_ref[...] = jnp.where(h == hkv - 1, total * (1.0 / (group * hkv)),
                              total)


def grad_q(q, k, v, do, lse, delta, mask, plan: BlockPlan, *, group: int,
           hd: int, block_q: int, block_k: int, interpret: bool = False):
  """``lse, delta [Hkv, T, G]`` -> (``dq`` float32 like ``q``; the heads'
  mean probabilities float32 ``[T, T]`` as :func:`head_mean` gives them,
  from the probabilities this backward forms anyway)."""
  length, hkv = _shapes(q, k, group, hd, block_q, block_k)
  q_spec, kv_spec, row_spec, block_spec = _heads_innermost_specs(
      length, hkv, group, hd, block_q, block_k)
  return pl.pallas_call(
      functools.partial(_dq_kernel, group=group, hd=hd),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(length // block_q, length // block_k, hkv),
          in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                    block_spec],
          out_specs=[
              # every head's dq of the query block stays in VMEM while the
              # key blocks and, inside them, the key-value heads go by
              pl.BlockSpec((block_q, hkv * group * hd),
                           lambda i, j, h, c, f: (i, 0)),
              pl.BlockSpec((block_q, block_k), lambda i, j, h, c, f: (i, j)),
          ],
      ),
      out_shape=[out_struct(q.shape, jnp.float32, q, k, v, do, mask),
                 out_struct((length, length), jnp.float32, q, k, v, do, mask)],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
      name=DQ_NAME,
  )(plan.counts, plan.fetch, q, k, v, do, lse, delta, mask)


def _dkv_kernel(counts_ref, fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, mask_ref, dk_ref, dv_ref, *, group: int, hd: int):
  del fetch_ref
  j, i, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

  @pl.when(i == 0)
  def init():
    dk_ref[...] = jnp.zeros_like(dk_ref)
    dv_ref[...] = jnp.zeros_like(dv_ref)

  @pl.when(counts_ref[j * nq + i] > 0)
  def run():
    keep = _keep(mask_ref)                                # [bk, bq]
    k, v = k_ref[...], v_ref[...]
    dk, dv = dk_ref[...], dv_ref[...]
    for g in range(group):
      cols = slice(g * hd, (g + 1) * hd)
      q, do = q_ref[:, cols], do_ref[:, cols]
      s = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
      p = jnp.exp(jnp.where(keep, s, MASK_VALUE) - lse_ref[g:g + 1, :])
      dv += lax.dot_general(p.astype(do.dtype), do, _NN,
                            preferred_element_type=jnp.float32)
      dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
      ds = (p * (dp - delta_ref[g:g + 1, :])).astype(q.dtype)
      dk += lax.dot_general(ds, q, _NN,
                            preferred_element_type=jnp.float32)
    dk_ref[...], dv_ref[...] = dk, dv


def grad_kv(q, k, v, do, lse, delta, mask_t, plan_t: BlockPlan, *,
            group: int, hd: int, block_q: int, block_k: int,
            interpret: bool = False):
  """``lse, delta [Hkv, G, T]``, ``mask_t`` the mask transposed (keys on the
  rows), ``plan_t`` its plan (blocks ``[block_k, block_q]``) -> (``dk``,
  ``dv``) float32 like ``k``."""
  length, hkv = _shapes(q, k, group, hd, block_q, block_k)
  nq = length // block_q
  q_spec = pl.BlockSpec((block_q, group * hd),
                        lambda h, j, i, c, f: (f[j * nq + i], h))
  kv_spec = pl.BlockSpec((block_k, hd), lambda h, j, i, c, f: (j, h))
  row_spec = pl.BlockSpec((None, group, block_q),
                          lambda h, j, i, c, f: (h, 0, f[j * nq + i]))
  return pl.pallas_call(
      functools.partial(_dkv_kernel, group=group, hd=hd),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=2,
          grid=(hkv, length // block_k, nq),
          in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                    pl.BlockSpec((block_k, block_q),
                                 lambda h, j, i, c, f: (j, f[j * nq + i]))],
          out_specs=[kv_spec, kv_spec],
      ),
      out_shape=[out_struct(k.shape, jnp.float32, q, k, v, do, mask_t)] * 2,
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
      name=DKV_NAME,
  )(plan_t.counts, plan_t.fetch, q, k, v, do, lse, delta, mask_t)


def _shapes(q, k, group: int, hd: int, block_q: int, block_k: int):
  """(T, Hkv) of ``q [T, Hkv * G * hd]`` and ``k [T, Hkv * hd]``."""
  length, hkv = k.shape[0], k.shape[1] // hd
  if q.shape != (length, hkv * group * hd) or k.shape != (length, hkv * hd):
    raise ValueError(f"q {q.shape} and k {k.shape} are not {group} query "
                     f"heads a key-value head of {hd}")
  if not fits(length, hd, block_q, block_k):
    raise ValueError(f"{length} positions of {hd}-wide heads in blocks of "
                     f"{block_q} x {block_k}: see fits()")
  return length, hkv
