"""The attention layer (`layers/attention.py`): every mask description's two
faces against each other and against a dense mask written by hand; the XLA
tile loop against attention by whole scores, mask by layout by tile; the
splash kernel in Pallas's interpreter against the tile loop on the same
operands rounded to bfloat16, and its lowering for the TPU at every published
head shape; the documents folded into the kernel's block maps against a count
of same-document pairs a block, and the kernel under those maps against the
kernel under the static ones, bit for bit; the rotary pass by hand; and that
no decoder and no layer imports a model."""

import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_sdar_moe
from distributed_embeddings_tpu.layers import attention
from distributed_embeddings_tpu.layers.attention import (
    BlockDiffusion,
    Causal,
    Window,
    attention_splash,
    attention_xla,
    rope,
    rope_frequencies,
)
from distributed_embeddings_tpu.layers.decoder import document_segments

PACKAGE = pathlib.Path(__file__).parent.parent / "distributed_embeddings_tpu"


def _case(length, hkv, group, hd, starts_at=(), seed=1, batch=1, scale=0.1):
  """``q [batch, length, hkv, group, hd]`` (``group`` None: ``[batch,
  length, hkv, hd]``, heads with no group), ``k``, ``v`` and the segment ids
  of documents that start at ``starts_at`` in sample 0."""
  rng = np.random.default_rng(seed)
  heads = (hkv,) if group is None else (hkv, group)
  q = jnp.asarray(rng.normal(size=(batch, length, *heads, hd)) * scale,
                  jnp.float32)
  k, v = (jnp.asarray(rng.normal(size=(batch, length, hkv, hd)), jnp.float32)
          for _ in range(2))
  starts = np.zeros((batch, length), bool)
  starts[:, 0] = True
  starts[0, list(starts_at)] = True
  return q, k, v, jnp.asarray(np.cumsum(starts, axis=1) - 1, jnp.int32)


def _by_hand(mask, length):
  """``[length, length]`` bool, query x key, a position pair at a time."""
  if isinstance(mask, BlockDiffusion):
    return reference_sdar_moe.mask_by_hand(length // 2, mask.block_length)
  dense = np.zeros((length, length), bool)
  for i in range(length):
    for j in range(i + 1):
      dense[i, j] = isinstance(mask, Causal) or i - j < mask.window
  return dense


def _by_whole_scores(q, k, v, allowed):
  """Attention with every score computed: ``allowed [B or 1, S, S]``."""
  grouped = q.ndim == 5
  if not grouped:
    q = q[:, :, :, None]
  scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k)
  prob = jax.nn.softmax(jnp.where(allowed[:, None, None], scores, -jnp.inf),
                        axis=-1)
  out = jnp.einsum("bkgqs,bskd->bqkgd", prob, v)
  return out if grouped else out[:, :, :, 0]


# ---- a description's two faces ---------------------------------------------
@pytest.mark.parametrize("mask,length,tiles", [
    (BlockDiffusion(4), 32, (4, 8, 16, 32, 5, 24)),
    (BlockDiffusion(2), 16, (2, 8, 3)),
    (BlockDiffusion(3), 24, (3, 6, 12, 24, 7)),
    (Causal(), 24, (4, 7, 24)),
    (Window(5), 24, (4, 7, 24)),
    (Window(1), 12, (5,)),
    (Window(40), 24, (8,)),
], ids=str)
def test_the_tiles_reach_is_the_dense_mask_is_the_kernels_mask(
    mask, length, tiles):
  """What the tile loop is told, assembled over a sequence, is the mask a
  position pair at a time, and so is what the kernel is told: nothing a tile
  may see lies outside its ranges, and inside them `allowed` says the
  same."""
  want = _by_hand(mask, length)
  assert np.array_equal(np.asarray(mask.splash(length)[:, :]), want)
  assert want.any(axis=1).all()                    # no query sees nothing
  if isinstance(mask, BlockDiffusion):             # benchmark/roofline_lm.py
    half = length // 2
    assert want.sum() == half * (half + mask.block_length)
  for tile in tiles:
    got = np.zeros_like(want)
    for a in range(0, length, tile):
      e = min(a + tile, length)
      ranges, allowed = mask.reach(a, e, length)
      keys = np.concatenate([np.arange(*r) for r in ranges])
      assert allowed.shape == (e - a, len(keys)) and allowed.dtype == bool
      assert len(set(keys)) == len(keys) and (np.diff(keys) > 0).all()
      got[a:e, keys] = allowed
    assert np.array_equal(got, want), tile


def test_a_description_is_its_fields():
  """Frozen and hashable: equal descriptions build one kernel."""
  assert Causal() == Causal() and hash(Window(5)) == hash(Window(5))
  assert len({Causal(), Causal(), Window(5), Window(5), Window(6),
              BlockDiffusion(4), BlockDiffusion(4)}) == 4


# ---- the tile loop ---------------------------------------------------------
@pytest.mark.parametrize("tile", [4, 8, 16])
def test_tiled_block_diffusion_is_masked_attention(tile):
  length, block, batch = 16, 4, 3
  q, k, v, _ = _case(2 * length, 2, 2, 8, batch=batch, scale=1.0)
  with jax.default_matmul_precision("highest"):
    got = attention_xla(q, k, v, BlockDiffusion(block), tile=tile)
    want = _by_whole_scores(
        q, k, v, reference_sdar_moe.mask_by_hand(length, block)[None])
  np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("mask,hkv,group,hd,length,starts_at,tiles", [
    (Window(5), 2, 3, 16, 24, (7, 15), (4, 7, 24)),
    (Causal(), 2, 3, 16, 24, (7, 15), (4, 7, 24)),
    # half a lane tile a head, four query heads a key-value head (LFM2)
    (Causal(), 2, 4, 64, 48, (7, 30), (5, 16, 48)),
    # heads with no group (Olmo): the grouped loop at a group of one
    (Causal(), 3, None, 16, 24, (7, 15), (4, 7, 24)),
    (Window(9), 3, None, 16, 24, (7, 15), (8,)),
], ids=str)
def test_the_tiled_path_is_attention_by_full_scores(
    mask, hkv, group, hd, length, starts_at, tiles):
  q, k, v, seg = _case(length, hkv, group, hd, starts_at=starts_at, batch=2)
  s = np.asarray(seg)
  allowed = _by_hand(mask, length)[None] & (s[:, :, None] == s[:, None, :])
  with jax.default_matmul_precision("highest"):
    want = _by_whole_scores(q, k, v, allowed)
    for tile in tiles:
      got = attention_xla(q, k, v, mask, seg, tile)
      assert got.shape == q.shape
      np.testing.assert_allclose(got, want, atol=2e-6)


def _sees(attend, q, k, v, seg, query):
  """The keys whose value moves the output at ``query``: ``[L]`` bool."""
  g = jax.grad(lambda v: jnp.sum(attend(q, k, v, seg)[0, query]))(v)
  return np.asarray(jnp.any(g[0] != 0, axis=(1, 2)))


def test_the_windows_edge_at_the_published_512():
  """A query sees itself and the 511 tokens before it: ``i - j`` 511 is
  seen, 512 is not; a document that starts inside the window cuts it
  short; a full layer sees the whole document."""
  length, window = 1100, 512
  q, k, v, seg = _case(length, 1, 2, 8, starts_at=(700,))
  sliding = lambda q, k, v, s: attention_xla(q, k, v, Window(window), s, 256)
  whole = lambda q, k, v, s: attention_xla(q, k, v, Causal(), s, 256)
  seen = _sees(sliding, q, k, v, seg, 650)
  assert seen[650 - 511] and not seen[650 - 512]
  assert seen[139:651].all() and not seen[:139].any() \
      and not seen[651:].any()
  seen = _sees(sliding, q, k, v, seg, 1000)      # its document starts at 700
  assert seen[700:1001].all() and not seen[:700].any()
  seen = _sees(sliding, q, k, v, seg, 1099)      # 1099 - 511 = 588 < 700
  assert seen[700:1100].all() and not seen[:700].any()
  seen = _sees(sliding, q, k, v, seg, 300)       # shorter than the window
  assert seen[:301].all() and not seen[301:].any()
  seen = _sees(whole, q, k, v, seg, 650)
  assert seen[:651].all() and not seen[651:].any()
  seen = _sees(whole, q, k, v, seg, 1099)
  assert seen[700:].all() and not seen[:700].any()


# ---- the kernel ------------------------------------------------------------
@pytest.mark.parametrize("mask,length,hkv,group,hd,starts_at,batch,rel", [
    # SDAR: [xt ; x0] of L = 128, no documents
    pytest.param(BlockDiffusion(4), 256, 1, 2, 128, None, 2, 1e-3, id="sdar"),
    # Olmo: 2 heads with no group, the multi-head kernel
    pytest.param(Causal(), 256, 2, None, 128, (37, 130), 2, 3e-3, id="olmo"),
    # Laguna: a window of 128 over blocks of 128 (a query block reads its own
    # block and the one before) and none; three query heads a key-value head
    pytest.param(Window(128), 384, 2, 3, 128, (37, 290), 1, 3e-3,
                 id="laguna_window"),
    pytest.param(Causal(), 384, 2, 3, 128, (37, 290), 1, 3e-3,
                 id="laguna_full"),
    # LFM2: half a lane tile a head, a group of 4
    pytest.param(Causal(), 384, 2, 4, 64, (37, 290), 1, 3e-3, id="lfm2"),
])
def test_the_splash_path_is_the_tiled_path_on_bfloat16_operands(
    mask, length, hkv, group, hd, starts_at, batch, rel):
  """The kernel the TPU runs, in Pallas's interpreter, under each
  description, in both layouts, with the documents as segment ids and with
  none: values and gradients are those of the XLA path given the same
  operands rounded to bfloat16 (which is what the MXU's default precision
  makes of float32)."""
  q, k, v, seg = _case(length, hkv, group, hd, starts_at=starts_at or (),
                       batch=batch)
  if starts_at is None:
    seg = None
  rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
  splash = lambda q, k, v: jnp.sum(jnp.sin(attention_splash(
      q, k, v, mask, seg, 128, interpret=True)))
  tiled = lambda q, k, v: jnp.sum(jnp.sin(attention_xla(
      rounded(q), rounded(k), rounded(v), mask, seg, 64)))
  # compiled ahead of time, as the benchmark compiles its step: the kernel's
  # block maps are constants of the program, not hidden arguments
  got = jax.jit(jax.value_and_grad(splash, argnums=(0, 1, 2))).lower(
      q, k, v).compile()(q, k, v)
  with jax.default_matmul_precision("highest"):
    want = jax.value_and_grad(tiled, argnums=(0, 1, 2))(q, k, v)
  # the kernel also rounds the softmax's probabilities to bfloat16 before
  # the product with V (2^-9 a value), which the tiled path does not
  assert float(got[0]) == pytest.approx(float(want[0]), rel=rel)
  for g, w in zip(got[1], want[1]):
    assert g.shape == w.shape
    assert float(jnp.max(jnp.abs(g - w))) < 0.02 * float(jnp.max(jnp.abs(w)))


@pytest.mark.parametrize("mask,length,hkv,group,hd,documents,kernel", [
    # SDAR: 8 query heads a key head of 128 over [xt ; x0], no segment ids
    (BlockDiffusion(4), 2048, 1, 8, 128, False, "mqa"),
    # Olmo: 15 heads of 128 with no group
    (Causal(), 1024, 15, None, 128, True, "mha"),
    # Laguna: 64 and 48 query heads over 8 key-value heads of 128; the local
    # mask keeps 3 of 10 blocks of a 2,048-token sequence
    (Window(512), 2048, 8, 8, 128, True, "mqa"),
    (Causal(), 2048, 8, 6, 128, True, "mqa"),
    # LFM2: 32 query heads over 8 key-value heads of 64
    (Causal(), 2048, 8, 4, 64, True, "mqa"),
], ids=str)
def test_the_splash_path_lowers_for_the_tpu_at_published_head_shapes(
    mask, length, hkv, group, hd, documents, kernel):
  """Pallas -> Mosaic lowering of forward and backward in blocks of 512
  with no chip (it does not run Mosaic's own compile: `/root/scratch`-style
  rehearsals and the chip do)."""
  q, k, v, seg = _case(length, hkv, group, hd, starts_at=(700,))
  f = jax.grad(lambda q, k, v: jnp.sum(attention_splash(
      q, k, v, mask, seg if documents else None, 512)), argnums=(0, 1, 2))
  text = jax.jit(f).trace(q, k, v).lower(
      lowering_platforms=("tpu",)).as_text()
  for part in ("fwd", "dq", "dkv"):
    assert f"splash_{kernel}_{part}" in text
  assert text.count("tpu_custom_call") >= 3


# ---- the documents in the kernel's block maps -------------------------------
PASSES = ("fwd", "dq", "dkv")


def _segments(length, starts_by_sample):
  """``[B, length]`` int32: the documents' numbers where sample ``b``'s start
  at 0 and at ``starts_by_sample[b]``."""
  starts = np.zeros((len(starts_by_sample), length), bool)
  starts[:, 0] = True
  for b, at in enumerate(starts_by_sample):
    starts[b, list(at)] = True
  return jnp.asarray(np.cumsum(starts, axis=1) - 1, jnp.int32)


def _steps_by_row(info, which):
  """A pass's ``block_mask`` and ``data_next`` as ``[rows, steps]``: a row is
  the block a kernel holds (queries; keys in dkv) and walks its steps."""
  turned = (lambda x: np.asarray(x)[0].T) if which == "dkv" \
      else (lambda x: np.asarray(x)[0])
  return turned(info.block_mask), turned(info.data_next)


def test_the_documents_numbers_do_not_decrease_along_a_sequence():
  """What `documents_in_block_maps` rests on: a block holds every document
  from its first position's to its last's."""
  u = np.random.default_rng(3).uniform(size=(4, 4096)).astype(np.float32)
  seg = np.asarray(document_segments(jnp.asarray(u), 256))
  steps = np.diff(seg, axis=1)
  assert (steps >= 0).all() and (steps <= 1).all()
  assert (seg[:, 0] == 0).all() and seg.max() > 8


@pytest.mark.parametrize("which", PASSES)
@pytest.mark.parametrize("mask,length,block", [
    (Causal(), 4096, 512), (Window(512), 4096, 512),
    (BlockDiffusion(4), 2048, 256)], ids=str)
def test_the_planned_maps_against_a_count_of_pairs(mask, length, block, which):
  """On seeded document starts (one on a block's first position each time):
  a step the plan kills holds no same-document pair the mask allows, a step
  it keeps joins two blocks that share a document (and under `Causal` and
  `Window` then holds such a pair); a row that loses a step names, at every
  dead step, the block of the nearest live step before it (after it for
  those the row starts with), so consecutive dead steps name one block; a
  row that loses none is as it was; dtypes and the other leaves are kept."""
  grouped = not isinstance(mask, Causal)        # both factories
  kernel = attention._splash_kernel(mask, length, 2, grouped, block, True)
  dense = np.asarray(mask.splash(length)[:, :])
  n, killed_in_all = length // block, 0
  for seed in range(6):
    rng = np.random.default_rng(seed)
    starts = np.flatnonzero(rng.uniform(size=length) < 4.0 / length)
    starts = np.union1d(starts, [block * int(rng.integers(1, n))])
    seg = _segments(length, [starts])[0]
    planned = attention.documents_in_block_maps(kernel, seg, block)
    s = np.asarray(seg)
    pairs = (dense & (s[:, None] == s[None, :])).reshape(
        n, block, n, block).any(axis=(1, 3))
    first, last = s[::block], s[block - 1::block]
    share = (last[:, None] >= first[None, :]) \
        & (last[None, :] >= first[:, None])
    if which == "dkv":
      pairs = pairs.T
    info = getattr(kernel, f"{which}_mask_info")
    got = getattr(planned, f"{which}_mask_info")
    for name in info._fields:
      before, after = getattr(info, name), getattr(got, name)
      assert (before is None) == (after is None), name
      if before is not None and name not in ("block_mask", "data_next"):
        assert np.array_equal(before, after), name
      if before is not None:
        assert before.dtype == after.dtype and before.shape == after.shape
    static, fetch = _steps_by_row(info, which)
    live, named = _steps_by_row(got, which)
    own = np.broadcast_to(np.arange(len(static))[:, None], fetch.shape)
    assert np.array_equal(live[live > 0], static[live > 0])
    assert not (live[static == 0] > 0).any()
    dead = (static > 0) & (live == 0)
    assert not pairs[own[dead], fetch[dead]].any()
    kept = live > 0
    assert share[own[kept], fetch[kept]].all()
    if not isinstance(mask, BlockDiffusion):
      assert pairs[own[kept], fetch[kept]].all()
    assert kept.any(axis=1).all()                 # no row is left empty
    killed_in_all += int(dead.sum())
    for r in range(len(static)):
      if not dead[r].any():
        assert np.array_equal(named[r], fetch[r])
        continue
      at = np.flatnonzero(kept[r])
      for step in range(static.shape[1]):
        if kept[r, step]:
          assert named[r, step] == fetch[r, step]
        else:
          near = at[at < step][-1] if (at < step).any() else at[0]
          assert named[r, step] == fetch[r, near], (r, step)
  assert killed_in_all > 0


@pytest.mark.parametrize("mask,length,block", [
    (Causal(), 2048, 512), (Window(512), 2048, 512),
    (BlockDiffusion(4), 1024, 128)], ids=str)
def test_one_document_leaves_the_static_maps_as_they_are(mask, length, block):
  kernel = attention._splash_kernel(mask, length, 2, True, block, True)
  planned = attention.documents_in_block_maps(
      kernel, jnp.zeros((length,), jnp.int32), block)
  before, after = (jax.tree_util.tree_flatten(x) for x in (kernel, planned))
  assert before[1] == after[1] and len(before[0]) == len(after[0])
  for a, b in zip(before[0], after[0]):
    assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("mask,length,hkv,group,hd,starts", [
    # LFM2's layout: a group of 4 over heads of 64; the second sequence's
    # documents are others, one from a block's first position
    pytest.param(Causal(), 768, 2, 4, 64, [(37, 290, 600), (256, 300)],
                 id="lfm2"),
    # GLM's: heads of 256 with no group
    pytest.param(Causal(), 768, 2, None, 256, [(128, 300, 640), (5,)],
                 id="glm"),
    # Laguna's window layers: a document from a block's first position is
    # what lets the plan skip one of a row's two blocks
    pytest.param(Window(128), 768, 2, 3, 128, [(37, 256, 512), (384,)],
                 id="laguna_window"),
    # one document a sequence: nothing to skip
    pytest.param(Causal(), 512, 1, 2, 128, [(), ()], id="one_document"),
])
def test_the_planned_kernel_is_the_unplanned_kernel_bit_for_bit(
    monkeypatch, mask, length, hkv, group, hd, starts):
  """A skipped block's products were discarded anyway (its scores are all
  the mask's value): forward and the three gradients under the planned maps
  EQUAL those under the static maps, in Pallas's interpreter; and both are
  the XLA path's on the rounded operands, at the tolerance of
  `test_the_splash_path_is_the_tiled_path_on_bfloat16_operands`."""
  q, k, v, _ = _case(length, hkv, group, hd, batch=len(starts))
  seg = _segments(length, starts)
  splash = jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(attention_splash(
      q, k, v, mask, seg, 128, interpret=True))), argnums=(0, 1, 2))
  got = jax.jit(splash)(q, k, v)
  with monkeypatch.context() as patch:
    patch.setattr(attention, "documents_in_block_maps",
                  lambda kernel, seg, block: kernel)
    want = jax.jit(splash)(q, k, v)
  for g, w in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    assert np.array_equal(np.asarray(g), np.asarray(w))
  rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
  with jax.default_matmul_precision("highest"):
    tiled = jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(attention_xla(
        rounded(q), rounded(k), rounded(v), mask, seg, 64))),
                               argnums=(0, 1, 2))(q, k, v)
  assert float(got[0]) == pytest.approx(float(tiled[0]), rel=3e-3)
  for g, w in zip(got[1], tiled[1]):
    assert float(jnp.max(jnp.abs(g - w))) < 0.02 * float(jnp.max(jnp.abs(w)))


@pytest.mark.parametrize("mask,length,hkv,group,hd", [
    (BlockDiffusion(4), 256, 1, 2, 128), (Causal(), 256, 2, None, 128)],
                         ids=str)
def test_without_segment_ids_the_call_traces_to_what_it_did(
    mask, length, hkv, group, hd):
  """`seg=None` (SDAR) goes round the plan: the jaxpr is that of the
  statements `attention_splash` was before the documents entered the maps."""
  q, k, v, _ = _case(length, hkv, group, hd, batch=2)

  def before(q, k, v):
    grouped = q.ndim == 5
    kernel = attention._splash_kernel(
        mask, q.shape[1], q.shape[3 if grouped else 2], grouped, 128, True)
    call = lambda q, k, v, s: kernel(q, k, v, segment_ids=None)
    sample = jax.vmap(call, in_axes=(0, 0, 0, None)) if grouped else call
    heads_first = lambda x: jnp.moveaxis(x, 1, -2).astype(jnp.bfloat16)
    out = jax.vmap(sample)(heads_first(q), heads_first(k), heads_first(v),
                           None)
    return jnp.moveaxis(out, -2, 1).astype(q.dtype)

  text = lambda f: re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(f)(q, k, v)))
  assert text(before) == text(lambda q, k, v: attention_splash(
      q, k, v, mask, None, 128, interpret=True))
  assert "pallas_call" in text(before)


# ---- the rotary pass -------------------------------------------------------
def test_the_plain_table_of_a_whole_head_is_what_it_was():
  """All of a head rotated at one theta, no factor."""
  x = jnp.asarray(np.random.default_rng(1).normal(size=(5, 2, 8)),
                  jnp.float32)
  inv = rope_frequencies(1e6, 8)
  assert np.array_equal(inv, 1.0 / (1e6 ** (np.arange(4, dtype=np.float32)
                                            / 4)))
  pos = jnp.arange(5)
  ang = np.arange(5, dtype=np.float32)[:, None] * inv[None, :]
  cos, sin = (np.concatenate([f(ang)] * 2, -1)[:, None] for f in
              (np.cos, np.sin))
  xs = np.asarray(x)
  want = xs * cos + np.concatenate([-xs[..., 4:], xs[..., :4]], -1) * sin
  np.testing.assert_allclose(rope(x, pos, inv), want, atol=1e-6)


# ---- who imports whom ------------------------------------------------------
DECODERS = ("sdar_moe", "olmo_hybrid", "laguna", "keye_sparse", "lfm2_moe")


def _imports(path):
  """(dots, module, the names taken from it) of every import of the file."""
  for node in ast.walk(ast.parse(path.read_text())):
    if isinstance(node, ast.ImportFrom):
      yield node.level, node.module or "", [a.name for a in node.names]
    elif isinstance(node, ast.Import):
      for alias in node.names:
        yield 0, alias.name, []


@pytest.mark.parametrize("path", [
    *(PACKAGE / "models" / f"{name}.py" for name in DECODERS),
    *sorted((PACKAGE / "layers").glob("*.py"))],
    ids=lambda p: f"{p.parent.name}/{p.name}")
def test_models_import_layers_and_nothing_imports_a_model(path):
  """A decoder takes what it shares with another from `layers/`, never from
  the other's file (one dot from `models/` is a sibling); a layer knows no
  model."""
  for level, module, names in _imports(path):
    said = f"{path.name}: from {'.' * level}{module} import {names}"
    assert not (path.parent.name == "models" and level == 1), said
    assert "models" not in module.split("."), said
    assert not (module == "" and "models" in names), said
