"""The attention layer (`layers/attention.py`): every mask description's two
faces against each other and against a dense mask written by hand; the XLA
tile loop against attention by whole scores, mask by layout by tile; the
splash kernel in Pallas's interpreter against the tile loop on the same
operands rounded to bfloat16, and its lowering for the TPU at every published
head shape; the rotary pass by hand; and that no decoder and no layer imports
a model."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_sdar_moe
from distributed_embeddings_tpu.layers.attention import (
    BlockDiffusion,
    Causal,
    Window,
    attention_splash,
    attention_xla,
    rope,
    rope_frequencies,
)

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
