"""Parity and lowering tests for the fused Pallas interaction kernels.

The kernels only run on real TPU hardware (`use_pallas_interact` gates on
backend); here they execute in Pallas interpret mode — valid for these
kernels because they have no input/output aliasing or RMW (unlike
`pallas_apply`, whose simulator exists for that reason) — and are checked
against the explicit XLA einsum form `pallas_interact.xla_reference` and
its `jax.vjp` (the matmul-form `_tril_products` is covered by
`test_models.py` against the reference semantics,
`/root/reference/examples/dlrm/utils.py:92-113`).

Every parity case runs more than one grid step. The kernels' scratches are
uint32 words, which the interpreter hands over as zeros, so no parity case
can tell whether the body zeroes the words of padded parts:
`test_assembly_zeroes_the_padded_words_on_every_grid_step` poisons the
scratch with NaN words before every step's assembly instead, so a body
that left them alone, or zeroed them on the first grid step only, fails it.

What interpret mode cannot show — Mosaic's refusals of a shape cast, of a
strided access, of more VMEM than a kernel may take — the last tests
show: they compile both kernels at the cells' shapes for a described
v5e, with no chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_embeddings_tpu.models.dlrm import _tril_select_np
from distributed_embeddings_tpu.ops.pallas_interact import (
    BWD_BLOCK,
    FWD_BLOCK,
    PARTS_BWD_NAME,
    PARTS_FWD_NAME,
    _assemble,
    _bf16_bits,
    bwd_select_np,
    fwd_select_np,
    interact_parts_bwd,
    interact_parts_fwd,
    rows_per_sample,
    samples_per_tile,
    use_pallas_interact,
    xla_reference,
)

D = 128
# 9 parts at a batch of 512 are what this file tested before PR 34
PART_COUNTS = [2, 8, 9, 16, 27, 32]
BATCHES = [256, 512]


def _blocks(b):
  """(fwd, bwd) samples a grid step: the step's own where they leave more
  than one grid step of `b`, else half of `b`."""
  return min(FWD_BLOCK, b // 2), min(BWD_BLOCK, b // 2)


def _mk_parts(seed, f, b):
  rng = np.random.default_rng(seed)
  return [jnp.asarray(rng.standard_normal((b, D)) * 0.3, jnp.bfloat16)
          for _ in range(f)]


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("k", [-1, 0])
@pytest.mark.parametrize("f", PART_COUNTS)
def test_parts_fwd_matches_xla_form(f, k, b):
  parts = _mk_parts(f, f, b)
  m_np, _ = _tril_select_np(f, k)
  block, _ = _blocks(b)
  assert b // block > 1
  got = interact_parts_fwd(parts, m_np, block=block, interpret=True)
  want = xla_reference(jnp.concatenate(parts, axis=1), m_np, f)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("k", [-1, 0])
@pytest.mark.parametrize("f", PART_COUNTS)
def test_parts_bwd_matches_xla_vjp(f, k, b):
  parts = _mk_parts(100 + f, f, b)
  m_np, _ = _tril_select_np(f, k)
  _, block = _blocks(b)
  assert b // block > 1

  flat = jnp.concatenate(parts, axis=1)
  acts, vjp = jax.vjp(lambda x: xla_reference(x, m_np, f), flat)
  rng = np.random.default_rng(2)
  d_acts = jnp.asarray(rng.standard_normal(acts.shape), jnp.float32)
  (want_flat,) = vjp(d_acts)

  got = interact_parts_bwd(d_acts, parts, m_np, block=block, interpret=True)
  assert len(got) == f
  for p in range(f):
    w = np.asarray(want_flat[:, p * D:(p + 1) * D], np.float32)
    g = np.asarray(got[p], np.float32)
    scale = max(np.abs(w).max(), 1e-3)
    np.testing.assert_allclose(g, w, rtol=0, atol=4e-2 * scale,
                               err_msg=f"part {p}")


@pytest.mark.parametrize("f", [2, 9, 27, 32])
def test_assembly_zeroes_the_padded_words_on_every_grid_step(f):
  """The assembled block of each of three grid steps holds part p of sample
  s at row `s * R + p` and zeros at the rows of padded parts, though the
  scratch held NaN words when each step began."""
  b, block = 48, 16
  r = rows_per_sample(f)

  def kernel(*refs):
    part_refs, out_ref, xs_ref = refs[:f], refs[f], refs[f + 1]
    xs_ref[...] = jnp.full(xs_ref.shape, 0xFFFFFFFF, jnp.uint32)
    x4 = _assemble(part_refs, xs_ref, block, r)
    out_ref[...] = x4.reshape(block * r, D).astype(jnp.float32)

  parts = _mk_parts(7, f, b)
  got = pl.pallas_call(
      kernel, grid=(b // block,),
      in_specs=[pl.BlockSpec((block, D), lambda i: (i, 0)) for _ in range(f)],
      out_specs=pl.BlockSpec((block * r, D), lambda i: (i, 0)),
      out_shape=jax.ShapeDtypeStruct((b * r, D), jnp.float32),
      scratch_shapes=[pltpu.VMEM((block * r // 2, D), jnp.uint32)],
      interpret=True)(*parts)
  got = np.asarray(got).reshape(b, r, D)
  want = np.zeros((b, r, D), np.float32)
  want[:, :f] = np.stack([np.asarray(p, np.float32) for p in parts], axis=1)
  np.testing.assert_array_equal(got, want)


def test_bf16_bits_rounds_as_astype_does():
  """Ties to even, both signs, values that round up into the next exponent,
  zeros and infinities: the high half is `astype(bfloat16)`'s bits."""
  rng = np.random.default_rng(3)
  bits = np.concatenate([
      rng.integers(0, 2**32, 4096, dtype=np.uint64).astype(np.uint32),
      # exact ties (low half 0x8000) on even and odd high halves, and near
      np.array([0x3F808000, 0x3F818000, 0xBF808000, 0xBF818000, 0x3F807FFF,
                0x3F808001, 0x3F7FFFFF, 0x7F7FFFFF, 0x00000000, 0x80000000,
                0x7F800000, 0xFF800000], np.uint32)])
  x = bits.view(np.float32)
  x = x[~np.isnan(x)].reshape(-1, 1)
  x = np.resize(x, (x.size // 128 * 128,)).reshape(-1, 128)
  got = np.asarray(jax.jit(_bf16_bits)(jnp.asarray(x)))
  want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
  np.testing.assert_array_equal(got, want.view(np.uint32))
  nan = np.asarray(jax.jit(_bf16_bits)(jnp.full((8, 128), np.nan, jnp.float32)))
  assert np.isnan(nan.view(np.float32)).all()


@pytest.mark.parametrize("f,rows,samples", [
    (2, 8, 16), (8, 8, 16), (9, 16, 8), (16, 16, 8), (17, 32, 4),
    (27, 32, 4), (32, 32, 4)])
def test_samples_per_tile_is_read_off_the_part_count(f, rows, samples):
  assert rows_per_sample(f) == rows
  assert samples_per_tile(f) == samples
  assert rows * samples == 128


@pytest.mark.parametrize("k", [-1, 0])
@pytest.mark.parametrize("f", [9, 27])
def test_the_selection_constants_skip_only_zero_tiles(f, k):
  """Put back where `where` says, the tiles are the whole tiled constant:
  what was left out was zero."""
  m_np, npair = _tril_select_np(f, k)
  r, t = rows_per_sample(f), samples_per_tile(f)
  lower = np.tril(np.ones((f, f), np.float32))[:, :, None] * (m_np > 0)
  tiles, where = fwd_select_np(m_np)
  back = np.zeros((f, 128, -(-npair // 128) * 128), np.float32)
  for p in range(f):
    for col, i in where[p]:
      back[p, :, col * 128:(col + 1) * 128] = tiles[i]
  for j in range(t):
    np.testing.assert_array_equal(back[:, j * r:j * r + f, :npair], lower)
  assert not back[:, :, npair:].any()
  # the lower triangle at weight 1 selects what the half-weight form does
  sym = np.random.default_rng(0).standard_normal((f, f)).astype(np.float32)
  sym = sym + sym.T
  np.testing.assert_allclose(np.einsum("pq,pqn->n", sym, lower),
                             np.einsum("pq,pqn->n", sym, m_np), rtol=1e-6)
  # row 27's pairs reach one or two of the output's three lane tiles
  assert max(len(w) for w in where) <= 2
  tiles_t, where_t = bwd_select_np(m_np)
  assert tiles_t.shape[1:] == (128, 128)
  for p in range(f):
    for col, i in where_t[p]:
      hi = min((col + 1) * 128, npair)
      np.testing.assert_array_equal(
          tiles_t[i][:hi - col * 128, :f], m_np[p, :, col * 128:hi].T)


def test_gate_logic():
  bf, f32 = jnp.bfloat16, jnp.float32
  if jax.default_backend() != "tpu":
    # non-TPU backends: always off, even for kernel-legal shapes
    assert not use_pallas_interact(FWD_BLOCK * 4, 27, 128, bf)
  # dtype/shape guards are backend-independent
  assert not use_pallas_interact(FWD_BLOCK * 4, 27, 128, f32)
  assert not use_pallas_interact(FWD_BLOCK * 4, 64, 128, bf)  # f too wide
  assert not use_pallas_interact(FWD_BLOCK * 4, 27, 64, bf)  # d not lane-mult
  assert not use_pallas_interact(FWD_BLOCK + 1, 27, 128, bf)  # ragged batch
  assert all(b % FWD_BLOCK == 0 and b % BWD_BLOCK == 0 for b in BATCHES)


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


# samples a chip in dlrm_train_1chip and in dlrm_train_4chip
@pytest.mark.parametrize("b", [65536, 16384])
def test_the_chips_compiler_takes_both_kernels_at_the_cells_shapes(
    one_chip, b):
  """Compiled for the TPU (nothing runs): Mosaic's refusals — a shape cast
  it cannot do, a strided access off the tiling, more VMEM than a kernel
  may take — are raised here, on the CPU."""
  f = 27
  m_np, npair = _tril_select_np(f, -1)
  parts = tuple(jax.ShapeDtypeStruct((b, D), jnp.bfloat16, sharding=one_chip)
                for _ in range(f))
  d_acts = jax.ShapeDtypeStruct((b, npair), jnp.float32, sharding=one_chip)
  fwd = jax.jit(lambda ps: interact_parts_fwd(ps, m_np)).lower(
      parts).compile()
  assert PARTS_FWD_NAME in fwd.as_text()
  bwd = jax.jit(lambda da, ps: interact_parts_bwd(da, ps, m_np)).lower(
      d_acts, parts).compile()
  assert PARTS_BWD_NAME in bwd.as_text()
