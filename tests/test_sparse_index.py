"""The learned sparse-attention indexer (`layers/sparse_index.py`): the
selection is exactly ``lax.top_k``'s set, ties to the lower index, at its
edges (a row with exactly ``k``, ``k + 1`` and one visible entry, none beyond
``k`` columns); the mask packs to bits and back; the tiled attention with its
written-out backward is the untiled one under JAX's own transpose, outputs,
the indexer's loss and every gradient, at a ``topk`` smaller than a tile and
larger than one, with a document that starts inside a tile; the edges at the
published ``topk`` of 2,048; bfloat16 in the score product chooses another
set."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_embeddings_tpu.layers import sparse_index
from distributed_embeddings_tpu.layers.sparse_index import (
    pack_bits,
    select_topk,
    sparse_attention,
    tile_runs,
    unpack_bits,
)


def _top_k_set(scores, visible, k):
  """``lax.top_k`` on the masked scores, scattered into a mask: the rule the
  selection is held to (of equal scores the lower index first)."""
  n = scores.shape[-1]
  if k >= n:
    return np.asarray(visible)
  _, best = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), k)
  chosen = np.zeros(scores.shape, bool)
  np.put_along_axis(chosen, np.asarray(best), True, axis=-1)
  return chosen & np.asarray(visible)


@pytest.mark.parametrize("k", [1, 3, 8, 31, 64, 100])
@pytest.mark.parametrize("kind", ["normal", "ties", "zeros", "signed_zero"])
def test_the_selected_set_is_top_ks(kind, k):
  rng = np.random.default_rng(k)
  scores = rng.normal(size=(40, 64)).astype(np.float32)
  if kind == "ties":          # eight distinct values: ties at every threshold
    scores = np.round(scores * 2) / 2
  if kind == "zeros":         # what sixteen shut ReLUs give: exact zeros
    scores = np.maximum(scores, 0)
  if kind == "signed_zero":   # extremes, and one sign of zero throughout
    scores[:, ::7] = 0.0
    scores[:, 1::9] = np.float32(3e38)
    scores[:, 2::11] = np.float32(-3e38)
    scores[:, 3::13] = np.float32(1e-45)   # a subnormal
  visible = rng.random((40, 64)) < 0.7
  visible[0] = True
  visible[1] = False
  visible[1, 5] = True                     # one visible entry
  visible[2] = np.arange(64) < k           # exactly k
  visible[3] = np.arange(64) < k + 1       # k + 1
  got = np.asarray(jax.jit(functools.partial(select_topk, k=k))(
      jnp.asarray(scores), jnp.asarray(visible)))
  want = _top_k_set(jnp.asarray(scores), jnp.asarray(visible), k)
  assert np.array_equal(got, want)
  assert np.array_equal(got.sum(-1), np.minimum(visible.sum(-1), k))
  assert not (got & ~visible).any()


def test_equal_scores_go_to_the_lower_index():
  scores = jnp.asarray([[1.0, 5.0, 1.0, 1.0, 5.0, 1.0, 0.0, 1.0]])
  seen = jnp.asarray([[True, True, False, True, True, True, True, True]])
  got = np.asarray(select_topk(scores, seen, 4))[0]
  # both fives, then the ones at the lowest visible positions: 0 and 3
  assert got.tolist() == [True, True, False, True, True, False, False, False]


@pytest.mark.parametrize("n", [1, 7, 8, 9, 64, 100])
def test_the_mask_packs_to_bits_and_back(n):
  rng = np.random.default_rng(n)
  mask = jnp.asarray(rng.random((5, n)) < 0.4)
  packed = pack_bits(mask)
  assert packed.dtype == jnp.uint8 and packed.shape == (5, -(-n // 8))
  assert np.array_equal(unpack_bits(packed, n), mask)
  assert int(jnp.sum(jax.lax.population_count(packed))) == int(mask.sum())


@pytest.mark.parametrize("length,tile,want", [
    (48, 8, [(0, 2, 16), (16, 2, 32), (32, 1, 40), (40, 1, 48)]),
    (16, 8, [(0, 1, 8), (8, 1, 16)]), (8, 8, [(0, 1, 8)]),
    (16384, 512, [(0, 8, 4096), (4096, 8, 8192), (8192, 8, 12288),
                  (12288, 8, 16384)])])
def test_tiles_run_in_at_most_four_key_extents(length, tile, want):
  assert tile_runs(length, tile) == want
  with pytest.raises(ValueError, match="tiles of"):
    tile_runs(length + 1, tile)


# ---- the tiled attention against the untiled one ---------------------------
def _operands(seed, batch, length, hkv=2, group=2, hd=8, hi=3, di=4):
  rng = np.random.default_rng(seed)
  f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
  return (f(batch, length, hkv, group, hd) * hd ** -0.5,
          f(batch, length, hkv, hd), f(batch, length, hkv, hd),
          f(batch, length, hi, di), f(batch, length, di),
          f(batch, length, hi) * 0.3)


def _untiled(q, k, v, qi, ki, wi, seg, topk, bf16_scores=False):
  """Full ``[T, T]`` arrays, ``lax.top_k``'s set, JAX's own transpose."""
  length = q.shape[1]
  at = jnp.arange(length)
  seen = (at[None, :] <= at[:, None])[None] \
      & (seg[:, :, None] == seg[:, None, :])
  if bf16_scores:
    qi, ki = qi.astype(jnp.bfloat16), ki.astype(jnp.bfloat16)
  raw = jnp.einsum("bqhd,bsd->bqhs", qi, ki,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
  index = jnp.einsum("bqh,bqhs->bqs", wi, jax.nn.relu(raw))
  chosen = jnp.asarray(_top_k_set(jax.lax.stop_gradient(index), seen, topk))
  s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                 precision=jax.lax.Precision.HIGHEST)
  p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, -jnp.inf), axis=-1)
  o = jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                 precision=jax.lax.Precision.HIGHEST)
  target = jax.lax.stop_gradient(jnp.mean(p, axis=(1, 2)))
  log_index = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
  kl = jnp.sum(jnp.where(chosen, target * (
      jnp.log(jnp.where(chosen, target, 1.0))
      - jnp.where(chosen, log_index, 0.0)), 0.0), axis=-1)
  return o, jnp.mean(kl), chosen


def _documents(batch, length, starts):
  seg = np.zeros((batch, length), np.int32)
  for b, at in starts:
    seg[b, at:] += 1
  return jnp.asarray(seg)


# a tile of 8 in 48 positions: six tiles in four runs; documents that start
# inside a tile (positions 13, 29, 43) and at a tile's first position (16)
@pytest.mark.parametrize("topk", [3, 8, 20, 48, 1000],
                         ids=lambda k: f"topk{k}")
def test_the_tiled_path_is_the_untiled_one(topk):
  """Outputs, the indexer's loss, the counters and every gradient of
  ``sum(sin(o)) + 3 kl``: float32 with every product at ``highest``, the same
  formulas in another order of sums, so 2e-5 of a leaf's largest value is
  twenty times the largest reading (1e-6)."""
  batch, length = 3, 48
  ops = _operands(topk, batch, length)
  seg = _documents(batch, length, [(0, 13), (0, 29), (1, 16), (2, 43)])

  def tiled(*ops):
    o, kl, counters = sparse_attention(*ops, seg, topk=topk, tile=8)
    return jnp.sum(jnp.sin(o)) + 3.0 * kl, (o, kl, counters)

  def plain(*ops):
    o, kl, chosen = _untiled(*ops, seg, topk)
    return jnp.sum(jnp.sin(o)) + 3.0 * kl, (o, kl, chosen)

  with jax.default_matmul_precision("highest"):
    (_, (o, kl, counters)), got = jax.jit(jax.value_and_grad(
        tiled, argnums=tuple(range(6)), has_aux=True))(*ops)
    (_, (o_want, kl_want, chosen)), want = jax.value_and_grad(
        plain, argnums=tuple(range(6)), has_aux=True)(*ops)
  np.testing.assert_allclose(o, o_want, atol=2e-5 * float(jnp.abs(o_want).max()))
  assert float(kl) == pytest.approx(float(kl_want), rel=2e-5)
  at = np.arange(length)
  seen = (at[None, :] <= at[:, None])[None] \
      & (np.asarray(seg)[:, :, None] == np.asarray(seg)[:, None, :])
  assert int(counters["selected_pairs"]) == int(chosen.sum())
  assert int(counters["visible_pairs"]) == int(seen.sum())
  assert int(counters["active_queries"]) == int((seen.sum(-1) > topk).sum())
  for name, g, w in zip(("q", "k", "v", "qi", "ki", "wi"), got, want):
    largest = float(jnp.abs(w).max())
    assert largest > 0, name
    assert float(jnp.abs(g - w).max()) <= 2e-5 * largest, name


def test_the_kept_mask_is_the_selected_set():
  """What the forward keeps for the backward, unpacked, is ``lax.top_k``'s
  set on the untiled scores, run by run of tiles."""
  batch, length, topk, tile = 2, 48, 5, 8
  ops = _operands(7, batch, length)
  seg = _documents(batch, length, [(0, 21), (1, 9), (1, 30)])
  with jax.default_matmul_precision("highest"):
    _, _, chosen = _untiled(*ops, seg, topk)
    for b in range(batch):
      _, residuals = sparse_index._forward(
          topk, tile, *(x[b] for x in ops), seg[b])
      packed = residuals[7]
      runs = tile_runs(length, tile)
      assert len(packed) == len(runs) == 4
      for (first, count, extent), bits in zip(runs, packed):
        assert bits.shape == (count, tile, extent // 8)
        for i in range(count):
          rows = slice(first + i * tile, first + (i + 1) * tile)
          got = np.asarray(unpack_bits(bits[i], extent))
          assert np.array_equal(got, np.asarray(chosen[b, rows, :extent]))
          assert not np.asarray(chosen[b, rows, extent:]).any()


def test_the_edges_at_the_published_topk():
  """``topk`` 2,048 in tiles of 512: query 2,047 of a document sees exactly
  2,048 keys and keeps them all, query 2,048 sees 2,049 and drops one, the
  first query of a document sees one; a document starts inside a tile."""
  length, topk, tile = 3072, 2048, 512
  q, k, v, qi, ki, wi = _operands(3, 1, length, hkv=1, group=1, hd=8, hi=2,
                                  di=4)
  seg = _documents(1, length, [(0, 2300)])
  with jax.default_matmul_precision("highest"):
    o, kl, counters = jax.jit(functools.partial(
        sparse_attention, topk=topk, tile=tile))(q, k, v, qi, ki, wi, seg)
    o_want, kl_want, chosen = _untiled(q, k, v, qi, ki, wi, seg, topk)
  kept = np.asarray(chosen[0].sum(-1))
  assert kept[2047] == 2048 and kept[2048] == 2048 and kept[2299] == 2048
  assert kept[0] == 1 and kept[2300] == 1 and kept[3071] == 772
  # query 2,048 dropped exactly one of its 2,049, and not itself by rule
  assert np.asarray(chosen[0, 2048, :2049]).sum() == 2048
  assert int(counters["selected_pairs"]) == int(kept.sum())
  assert int(counters["active_queries"]) == 2300 - 2048
  np.testing.assert_allclose(o, o_want, atol=2e-5 * float(jnp.abs(o_want).max()))
  assert float(kl) == pytest.approx(float(kl_want), rel=2e-5)


def test_bfloat16_in_the_score_product_chooses_another_set():
  """The product that decides is float32 at ``highest``: with its operands
  rounded to bfloat16 some queries keep other keys, and the output leaves
  the tolerance the tiled path is held to by orders of magnitude."""
  batch, length, topk = 4, 96, 24   # five seeds flip 6 to 24 pairs here
  ops = _operands(11, batch, length)
  seg = _documents(batch, length, [(0, 20)])
  with jax.default_matmul_precision("highest"):
    o, _, _ = jax.jit(functools.partial(
        sparse_attention, topk=topk, tile=8))(*ops, seg)
    o_want, _, chosen = _untiled(*ops, seg, topk)
    o_low, _, chosen_low = _untiled(*ops, seg, topk, bf16_scores=True)
  flipped = int((np.asarray(chosen) != np.asarray(chosen_low)).sum())
  assert flipped >= 2 and flipped % 2 == 0     # a key out, a key in
  tol = 2e-5 * float(jnp.abs(o_want).max())
  assert float(jnp.abs(o - o_want).max()) <= tol
  assert float(jnp.abs(o_low - o_want).max()) > 100 * tol
