"""The sparse-attention kernels (`ops/pallas_sparse_attn.py`) in Pallas's
interpreter on the CPU, against the XLA tile loop of
`layers/sparse_index.py`, which is their oracle and the path every backend
but a TPU takes.

Here both paths run float32 operands with every product at ``highest``, so
what separates them is the order of sums (an online softmax a block of keys
at a time against a whole row at once): 2e-5 of a value's largest is the
tolerance `tests/test_sparse_index.py` holds the tile loop to against an
untiled formula, and these readings are under 1e-6. On the chip the operands
are bfloat16 and `tools/smoke_pallas_sparse_attn.py` compares the two paths
there.

The kernels want 128-lane blocks, so the toy is 512 or 768 positions in
tiles of 128 with heads of 128. Its cases: one document; several documents
with a boundary inside a block; a ``topk`` larger than most queries' visible
keys; a row of blocks of which some are skipped; and the counters of blocks,
which add up to the grid. What the interpreter cannot show (Mosaic's
refusals) the last test shows: it compiles the four kernels at the cell's
shapes for a described v5e, with no chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_embeddings_tpu.layers import sparse_index
from distributed_embeddings_tpu.layers.sparse_index import sparse_attention
from distributed_embeddings_tpu.ops import pallas_sparse_attn as psa

TILE, HKV, G, HD = 128, 2, 2, 128
TOL = 2e-5
# name -> (positions, topk, [the first position of each later document])
CASES = {
    "one_document": (512, 100, []),
    # 200 and 450 lie inside blocks, 256 on a block's first row
    "boundaries_inside_blocks": (512, 60, [200, 256, 450]),
    # nobody past a document's first 400 positions: few queries drop a key
    "fewer_visible_than_topk": (512, 400, [300]),
    # the second document starts at a block's edge: its three rows of
    # blocks skip the three columns of the first, and the diagonal's above
    "rows_with_skipped_blocks": (768, 100, [384]),
}


def _operands(seed, length, hi=3, di=8):
  rng = np.random.default_rng(seed)
  f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
  return (f(1, length, HKV, G, HD) * HD ** -0.5, f(1, length, HKV, HD),
          f(1, length, HKV, HD), f(1, length, hi, di), f(1, length, di),
          f(1, length, hi) * 0.3)


def _segments(length, starts):
  seg = np.zeros((1, length), np.int32)
  for at in starts:
    seg[0, at:] += 1
  return jnp.asarray(seg)


def _loss(path, seg, topk, monkeypatch):
  """``sum(sin(o)) + 3 kl`` through ``sparse_attention`` on one path: the
  kernels in the interpreter (``True``) or the tile loop (``None``)."""
  monkeypatch.setattr(sparse_index, "attention_kernels", lambda *_: path)

  def loss(*ops):
    o, kl, counters = sparse_attention(*ops, seg, topk=topk, tile=TILE)
    return jnp.sum(jnp.sin(o)) + 3.0 * kl, (o, kl, counters)

  return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                    has_aux=True))


def _close(got, want, name):
  largest = float(jnp.abs(want).max())
  assert largest > 0, name
  assert float(jnp.abs(got - want).max()) <= TOL * largest, name


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_path_is_the_tile_loop(case, monkeypatch):
  """The layer whole, forward and backward: the output, the indexer's KL,
  the five counters, and the gradients of ``q``, ``k``, ``v`` (through the
  ``dq`` and ``dkv`` kernels) and of ``qi``, ``ki``, ``wi`` (through the
  heads' mean probabilities of the backward pass)."""
  length, topk, starts = CASES[case]
  ops, seg = _operands(1, length), _segments(length, starts)
  with jax.default_matmul_precision("highest"):
    (_, (o, kl, counters)), got = _loss(True, seg, topk, monkeypatch)(*ops)
    (_, (o_want, kl_want, counters_want)), want = _loss(
        None, seg, topk, monkeypatch)(*ops)
  _close(o, o_want, "o")
  assert float(kl) == pytest.approx(float(kl_want), rel=TOL)
  assert {n: int(c) for n, c in counters.items()} \
      == {n: int(c) for n, c in counters_want.items()}
  for name, g, w in zip(("q", "k", "v", "qi", "ki", "wi"), got, want):
    _close(g, w, name)


def _selection(case):
  """A case's operands in the kernels' layout, and its mask from the tile
  loop's own kept bits."""
  length, topk, starts = CASES[case]
  q, k, v, qi, ki, wi = (x[0] for x in _operands(2, length))
  with jax.default_matmul_precision("highest"):
    _, residuals = sparse_index._forward(
        topk, TILE, q, k, v, qi, ki, wi, _segments(length, starts)[0])
  mask = sparse_index._whole_mask(
      sparse_index.unpacked_runs(residuals[7], length, TILE), length)
  return q, k, v, mask


def _plain(q, k, v, mask):
  """Every head's scores whole: -> (``o [T, Hkv, G, hd]``, the log-sum-exp
  ``[Hkv, G, T]``, the probabilities ``[Hkv, G, T, T]``)."""
  s = jnp.einsum("qkgd,skd->kgqs", q, k)
  s = jnp.where(mask[None, None] != 0, s, -jnp.inf)
  lse = jax.nn.logsumexp(s, axis=-1)
  p = jnp.exp(s - lse[..., None])
  return jnp.einsum("kgqs,skd->qkgd", p, v), lse, p


@pytest.mark.parametrize("case", CASES)
def test_each_kernel_against_the_whole_scores(case):
  """The four kernels one by one against formulas that hold a head's whole
  ``[T, T]`` scores: the output and the log-sum-exp; the heads' mean
  probabilities from the forward's kernel and from the ``dq`` kernel
  (exactly 0 in a skipped block); ``dq`` and ``dk, dv`` against JAX's own
  transpose."""
  q, k, v, mask = _selection(case)
  length = q.shape[0]
  at = dict(group=G, hd=HD, block_q=TILE, block_k=TILE, interpret=True)
  flat = lambda x: x.reshape(length, -1)
  counts = psa.block_counts(mask, TILE, TILE)
  plan, plan_t = psa.block_plan(counts), psa.block_plan(counts.T)
  with jax.default_matmul_precision("highest"):
    (o_want, lse_want, p), vjp = jax.vjp(
        lambda q, k, v: _plain(q, k, v, mask), q, k, v)
    do = jnp.cos(o_want)
    dq_want, dk_want, dv_want = vjp(
        (do, jnp.zeros_like(lse_want), jnp.zeros_like(p)))
    o, lse = psa.attend(flat(q), flat(k), flat(v), mask, plan, **at)
    mean = psa.head_mean(flat(q), flat(k), lse, mask, plan, **at)
    delta = jnp.sum(do * o_want, axis=-1)                       # [T, Hkv, G]
    dq, mean_again = psa.grad_q(flat(q), flat(k), flat(v), flat(do), lse,
                                jnp.swapaxes(delta, 0, 1), mask, plan, **at)
    dk, dv = psa.grad_kv(flat(q), flat(k), flat(v), flat(do),
                         jnp.swapaxes(lse, 1, 2), jnp.moveaxis(delta, 0, 2),
                         mask.T, plan_t, **at)
  _close(o.reshape(q.shape), o_want, "o")
  _close(jnp.swapaxes(lse, 1, 2), lse_want, "log-sum-exp")
  _close(mean, jnp.mean(p, axis=(0, 1)), "the heads' mean")
  empty = np.repeat(np.repeat(np.asarray(counts) == 0, TILE, 0), TILE, 1)
  assert empty.any() and not np.asarray(mean)[empty].any()
  # the dq kernel's own sum over the heads of the probabilities it forms
  _close(mean_again, jnp.mean(p, axis=(0, 1)), "the heads' mean, backward")
  assert not np.asarray(mean_again)[empty].any()
  _close(dq.reshape(q.shape), dq_want, "dq")
  _close(dk.reshape(k.shape), dk_want, "dk")
  _close(dv.reshape(v.shape), dv_want, "dv")


@pytest.mark.parametrize("case", CASES)
def test_attended_and_skipped_blocks_are_the_grid(case, monkeypatch):
  """``attended_blocks`` is the number of blocks the kernels' own plan runs
  (a count over zero), on either path, and with ``skipped_blocks`` it is the
  whole ``(T / tile) ** 2``; under a causal mask at least the blocks above
  the diagonal are skipped."""
  length, topk, starts = CASES[case]
  *_, mask = _selection(case)
  counts = np.asarray(psa.block_counts(mask, TILE, TILE))
  n = length // TILE
  assert counts.sum() == np.asarray(mask).sum()
  assert not np.triu(counts, 1).any() and np.diag(counts).all()
  for path in (True, None):
    monkeypatch.setattr(sparse_index, "attention_kernels", lambda *_: path)
    with jax.default_matmul_precision("highest"):
      _, _, counters = jax.jit(functools.partial(
          sparse_attention, topk=topk, tile=TILE))(
              *_operands(2, length), _segments(length, starts))
    assert int(counters["attended_blocks"]) == (counts > 0).sum()
    assert int(counters["attended_blocks"]) \
        + int(counters["skipped_blocks"]) == n * n
    assert int(counters["skipped_blocks"]) >= n * (n - 1) // 2
    assert int(counters["selected_pairs"]) == counts.sum()
  if case == "rows_with_skipped_blocks":
    # the second document's rows pass over the first's columns
    assert not counts[3:, :3].any() and int(counters["skipped_blocks"]) == 24


def test_the_plan_names_a_block_that_is_already_there():
  """``block_plan``: a block with a pair fetches itself; an empty one the
  last before it in its row that has one, and the empty ones a row starts
  with the first that has; a row with none fetches block 0."""
  counts = jnp.asarray([[0, 0, 3, 0, 1, 0],
                        [2, 0, 0, 0, 0, 0],
                        [0, 0, 0, 0, 0, 0],
                        [0, 0, 0, 0, 0, 7]], jnp.int32)
  plan = psa.block_plan(counts)
  assert plan.counts.tolist() == counts.reshape(-1).tolist()
  assert plan.fetch.reshape(4, 6).tolist() == [
      [2, 2, 2, 2, 4, 4], [0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0],
      [5, 5, 5, 5, 5, 5]]


def test_a_skipped_block_is_never_read():
  """Keys no query selects, a whole column of blocks of them, hold NaN in
  ``k`` and ``v``: every output of the four kernels is finite, and the
  gradient of those keys exactly 0."""
  length, dead = 512, slice(128, 256)
  rng = np.random.default_rng(3)
  f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
  q, do = f(length, HKV * G * HD) * HD ** -0.5, f(length, HKV * G * HD)
  k, v = f(length, HKV * HD), f(length, HKV * HD)
  mask = np.tril(rng.random((length, length)) < 0.3) | np.eye(length,
                                                               dtype=bool)
  mask[:, dead] = False
  mask = jnp.asarray(mask, jnp.int8)
  k, v = k.at[dead].set(jnp.nan), v.at[dead].set(jnp.nan)
  at = dict(group=G, hd=HD, block_q=TILE, block_k=TILE, interpret=True)
  counts = psa.block_counts(mask, TILE, TILE)
  plan, plan_t = psa.block_plan(counts), psa.block_plan(counts.T)
  o, lse = psa.attend(q, k, v, mask, plan, **at)
  mean = psa.head_mean(q, k, lse, mask, plan, **at)
  delta = jnp.sum((do * o).reshape(length, HKV, G, HD), axis=-1)
  dq, mean_again = psa.grad_q(q, k, v, do, lse, jnp.swapaxes(delta, 0, 1),
                              mask, plan, **at)
  dk, dv = psa.grad_kv(q, k, v, do, jnp.swapaxes(lse, 1, 2),
                       jnp.moveaxis(delta, 0, 2), mask.T, plan_t, **at)
  for name, x in (("o", o), ("lse", lse), ("mean", mean), ("dq", dq),
                  ("mean of the backward", mean_again), ("dk", dk),
                  ("dv", dv)):
    assert bool(jnp.isfinite(x).all()), name
  assert not np.asarray(dk[dead]).any() and not np.asarray(dv[dead]).any()
  assert not np.asarray(mean[:, dead]).any()
  assert not np.asarray(mean_again[:, dead]).any()


def test_which_shapes_the_kernels_take():
  """128-lane heads and blocks that divide the sequence; no backend but a
  TPU takes the kernels' path whatever the shapes."""
  assert psa.fits(8192, 128, 512, 512) and psa.fits(16384, 256, 512, 512)
  assert not psa.fits(8192, 64, 512, 512)      # a head is not whole lanes
  assert not psa.fits(8192, 128, 64, 64)       # nor a block
  assert not psa.fits(8192 + 256, 128, 512, 512)
  assert jax.default_backend() != "tpu"
  assert sparse_index.attention_kernels(8192, 128, 512) is None
  for name in (psa.FWD_NAME, psa.MEAN_NAME, psa.DQ_NAME, psa.DKV_NAME):
    # `benchmark/layer_metrics/attn_layout_ms` takes `splash_*` kernels out
    # of `de_attn_core`; these stay in
    assert name.startswith("de_sparse_attn")


@pytest.fixture(scope="module")
def one_chip():
  """A described, not attached, v5e chip to compile for; the persistent
  compile cache is off meanwhile (an entry written for a described chip
  cannot be read back and warns)."""
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  from jax.sharding import SingleDeviceSharding
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no TPU compiler in this installation
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", was)
  compilation_cache.reset_cache()


# positions a sequence in keye_dsa_train_1chip, and in ISSUE 40's first mix
@pytest.mark.parametrize("length", [8192, 16384])
def test_the_chips_compiler_takes_the_kernels_at_the_cells_shapes(
    one_chip, length):
  """Compiled for the TPU (nothing runs): 32 query heads over 4 key-value
  heads of 128, bfloat16, blocks of 512, the int8 mask and the scalar-
  prefetched plan. Mosaic's refusals, more VMEM than a kernel may take among
  them, are raised here, on the CPU."""
  hkv, group, hd, block = 4, 8, 128, 512
  shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
  bf16, f32 = jnp.bfloat16, jnp.float32
  q = shape((length, hkv * group * hd), bf16)
  k = shape((length, hkv * hd), bf16)
  mask = shape((length, length), jnp.int8)
  blocks = (length // block) ** 2
  plan = psa.BlockPlan(shape((blocks,), jnp.int32),
                       shape((blocks,), jnp.int32))
  by_query, by_key = (shape((hkv, length, group), f32),
                      shape((hkv, group, length), f32))
  at = dict(group=group, hd=hd, block_q=block, block_k=block)
  for name, call, operands in (
      (psa.FWD_NAME, psa.attend, (q, k, k, mask, plan)),
      (psa.MEAN_NAME, psa.head_mean, (q, k, by_query, mask, plan)),
      (psa.DQ_NAME, psa.grad_q, (q, k, k, q, by_query, by_query, mask, plan)),
      (psa.DKV_NAME, psa.grad_kv, (q, k, k, q, by_key, by_key, mask, plan))):
    compiled = jax.jit(functools.partial(call, **at)).lower(
        *operands).compile()
    assert name in compiled.as_text()
