"""Keye-VL-2.0's language model (`models/keye_sparse.py`) against the plain
reference (`tests/reference_keye_sparse.py`) at toy widths that keep what the
published model has: grouped queries with q/k norms, an indexer of several
heads over one shared key with a LayerNorm and a partial rotary pass, a
``topk`` smaller and larger than a tile of queries, documents that start
inside a tile, a softmax router over experts of which a share is held. On
seeded weights: logits, both loss terms, every gradient leaf and the
gradient of the table's rows; WHO OWNS WHICH GRADIENT (the indexer's leaves
get none from the language-model loss, every other leaf and the layer's
input none from the KL); the selection is computed once a layer under the
rematerialisation plan; the eight shares of a layer's experts add up to the
uncut layer with attention and indexer counted once; the counters are the
documents' own counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_keye_sparse as ref
from distributed_embeddings_tpu.layers import remat
from distributed_embeddings_tpu.layers.decoder import (
    document_segments,
    next_token_loss,
)
from distributed_embeddings_tpu.layers.moe import moe_share
from distributed_embeddings_tpu.models import keye_sparse
from distributed_embeddings_tpu.models.keye_sparse import (
    INDEXER_LEAVES,
    KeyeSparse,
    KeyeSparseConfig,
    decoder_layer,
    layer_shapes,
    sparse_training_loss,
)
from test_remat_plan import _count, _primitive

# 48 positions in tiles of 8 (six tiles in four runs of key extents),
# documents of mean 16 (a start inside most tiles), five keys kept a query:
# the selection binds for every query past its document's fifth token
TOY = KeyeSparseConfig(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=12, num_experts=16, num_experts_per_tok=3,
    num_hidden_layers=2, vocab_size=50, experts_held=(0, 16),
    indexer_num_heads=3, indexer_head_dim=8, topk=5, q_chunk_size=8,
    indexer_rotary_dim=4, seq_len=48, mean_document_length=16)
B = 3
# Model against reference in float32 with every product at `highest`: the
# same formulas but for the tiles, the written-out backward, the experts
# (sort + grouped matmuls against a loop) and the order of sums, so what is
# left is float32 rounding. A leaf's largest value times 2e-5 is twenty times
# the largest reading over the cases (logits 4.5e-7, gradients 1.0e-6 of the
# leaf's largest), and a ten-thousandth of what one flipped key moves.
TOL = 2e-5


def _params(cfg, seed=0):
  rng = np.random.default_rng(seed)
  ranges = {"gain": (0.8, 1.2), "bias": (-0.2, 0.2), "matrix": (-0.3, 0.3)}
  leaf = lambda shape, kind: jnp.asarray(
      rng.uniform(*ranges[kind], shape), jnp.float32)
  params = {f"layer_{i}_{n}": leaf(shape, kind)
            for i in range(cfg.num_hidden_layers)
            for n, (shape, kind) in layer_shapes(cfg).items()}
  params["final_norm"] = leaf((cfg.hidden_size,), "gain")
  params["head"] = leaf((cfg.hidden_size, cfg.vocab_size), "matrix")
  return params


def _batch(cfg, seed=0, batch=B):
  rng = np.random.default_rng(seed)
  rows = jnp.asarray(rng.normal(size=(batch, cfg.seq_len, cfg.hidden_size))
                     * 0.5, jnp.float32)
  numerical = jnp.asarray(rng.random((batch, cfg.seq_len)), jnp.float32)
  targets = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                     (batch, cfg.seq_len)), jnp.int32)
  return rows, numerical, targets


def _apply(cfg, params, rows, numerical, **kw):
  return KeyeSparse(cfg, **kw).apply({"params": params}, numerical, None,
                                     emb_acts=[rows])


def _is_indexer(name):
  return name.endswith(INDEXER_LEAVES)


@pytest.mark.parametrize("cfg", [
    TOY, dataclasses.replace(TOY, topk=20, experts_held=(4, 8)),
    dataclasses.replace(TOY, topk=48, q_chunk_size=16, num_hidden_layers=1)],
    ids=["topk5", "topk20-share", "topk-all"])
def test_the_model_is_the_plain_reference(cfg):
  params = _params(cfg, 1)
  rows, numerical, targets = _batch(cfg, 1)
  rcfg = dataclasses.asdict(cfg)
  with jax.default_matmul_precision("highest"):
    out = jax.jit(lambda p, r: _apply(cfg, p, r, numerical,
                                      with_counters=True))(params, rows)
    (logits, weight, index_kl, chosen), (lm, kl) = jax.jit(lambda p, r: (
        ref.forward(rcfg, p, r, numerical),
        ref.loss_terms(rcfg, p, r, numerical, targets)))(params, rows)
    got = jax.jit(jax.grad(lambda p, r: sparse_training_loss(
        _apply(cfg, p, r, numerical), {"targets": targets}),
                           argnums=(0, 1)))(params, rows)
    want = jax.jit(jax.grad(
        lambda p, r: ref.loss(rcfg, p, r, numerical, targets),
        argnums=(0, 1)))(params, rows)
  assert float(jnp.abs(out["logits"] - logits).max()) \
      <= TOL * float(jnp.abs(logits).max())
  assert np.array_equal(out["weight"], weight)
  labels = {"targets": targets}
  assert float(next_token_loss(out, labels)) == pytest.approx(float(lm),
                                                              rel=TOL)
  assert float(out["index_kl"]) == pytest.approx(float(kl), rel=TOL)
  assert float(kl) > 0.01 * cfg.num_hidden_layers * (cfg.topk < cfg.seq_len)
  assert float(sparse_training_loss(out, labels)) == pytest.approx(
      float(lm + kl), rel=TOL)
  # the selected SET's size, layer by layer, is the reference's
  assert out["index"]["selected_pairs"].tolist() \
      == [int(c.sum()) for c in chosen]
  for name in params:
    largest = float(jnp.abs(want[0][name]).max())
    assert largest > 0, name
    assert float(jnp.abs(got[0][name] - want[0][name]).max()) \
        <= TOL * largest, name
  assert float(jnp.abs(got[1] - want[1]).max()) \
      <= TOL * float(jnp.abs(want[1]).max())


def test_who_owns_which_gradient():
  """One ``value_and_grad`` of the sum; ``stop_gradient`` decides which
  leaves see which term. Exactly zero, not small: the indexer's five leaves
  a layer under the language-model loss alone; every other leaf, and the
  table's rows (so ``h``, through the indexer), under the KL alone. The
  reference with the indexer's input left attached shows what the test
  would see."""
  cfg = TOY
  params = _params(cfg, 2)
  rows, numerical, targets = _batch(cfg, 2)
  labels = {"targets": targets}
  terms = {
      "lm": lambda out: next_token_loss(out, labels),
      "kl": lambda out: out["index_kl"]}
  grads = jax.jit(lambda p, r: {
      name: jax.grad(lambda p, r, term=term: term(
          _apply(cfg, p, r, numerical)), argnums=(0, 1))(p, r)
      for name, term in terms.items()})(params, rows)
  for name in params:
    lm = float(jnp.abs(grads["lm"][0][name]).max())
    kl = float(jnp.abs(grads["kl"][0][name]).max())
    if _is_indexer(name):
      assert lm == 0.0 and kl > 0.0, name
    else:
      assert kl == 0.0 and lm > 0.0, name
  assert float(jnp.abs(grads["kl"][1]).max()) == 0.0
  assert float(jnp.abs(grads["lm"][1]).max()) > 0.0
  assert sum(map(_is_indexer, params)) == 5 * cfg.num_hidden_layers
  # attached, the KL reaches the rows and the first layer's projections
  rcfg = dataclasses.asdict(cfg)
  attached = jax.jit(jax.grad(lambda p, r: ref.loss_terms(
      rcfg, p, r, numerical, targets, detach_input=False)[1],
                              argnums=(0, 1)))(params, rows)
  assert float(jnp.abs(attached[1]).max()) > 0.0
  assert float(jnp.abs(attached[0]["layer_0_wq"]).max()) > 0.0


def test_dense_attention_in_the_selections_place_is_another_model():
  """``select=False`` in the reference (every visible key attended) moves the
  logits by four orders of magnitude more than the tolerance."""
  cfg = TOY
  params = _params(cfg, 3)
  rows, numerical, _ = _batch(cfg, 3)
  rcfg = dataclasses.asdict(cfg)
  with jax.default_matmul_precision("highest"):
    logits = jax.jit(lambda p, r: _apply(cfg, p, r, numerical))(
        params, rows)["logits"]
    dense, _, _, _ = ref.forward(rcfg, params, rows, numerical, select=False)
  assert float(jnp.abs(logits - dense).max()) \
      > 1e4 * TOL * float(jnp.abs(dense).max())


# ---- the rematerialisation plan ---------------------------------------------
def _selections(jaxpr):
  """Calls of `select_topk` in a jaxpr: each maps the scores to integers by
  two bit casts, which nothing else of the model does; a loop over tiles is
  one body, so one call a run of tiles."""
  return _count(jaxpr, _primitive("bitcast_convert_type")) // 2


def test_the_selection_is_made_once_a_layer(monkeypatch):
  """Under `checkpoint_layer` a layer's forward is traced again for its
  backward; the selection is named (`remat.SPARSE_SELECTION`) and kept, so
  the step's jaxpr holds one `select_topk` a run of tiles a layer, as many
  as the forward alone. With the name struck from the plan it holds twice
  that. The attention's scores likewise: kept output and log-sum-exp
  (`remat.SPARSE_ATTN_RESIDUALS`) leave the rebuilt layer no `exp` of a
  tile's scores."""
  cfg = TOY
  params = _params(cfg)
  rows, numerical, targets = _batch(cfg)
  loss = lambda p, r: sparse_training_loss(
      _apply(cfg, p, r, numerical), {"targets": targets})
  runs = 4 * cfg.num_hidden_layers
  forward = jax.make_jaxpr(loss)(params, rows).jaxpr
  assert _selections(forward) == runs
  grad = lambda: jax.grad(lambda p, r: loss(p, r), argnums=(0, 1))
  whole = jax.make_jaxpr(grad())(params, rows).jaxpr
  assert _selections(whole) == runs
  assert {remat.SPARSE_SELECTION, remat.SPARSE_ATTN_RESIDUALS} \
      <= set(remat.KEPT)
  monkeypatch.setattr(remat, "KEPT", tuple(
      n for n in remat.KEPT if n != remat.SPARSE_SELECTION))
  again = jax.make_jaxpr(grad())(params, rows).jaxpr
  assert _selections(again) == 2 * runs


def test_gradients_under_the_plan_are_those_with_no_checkpoint(monkeypatch):
  """Leaf by leaf: rematerialisation repeats the forward's own operations on
  the forward's own operands, and the written-out backward reads the kept
  mask, output and log-sum-exp either way. Each side is one compiled
  program, whose fusions may order a sum otherwise: 1e-6 of a leaf's
  largest value, float32's own step."""
  cfg = TOY
  params = _params(cfg, 4)
  rows, numerical, targets = _batch(cfg, 4)
  loss = lambda p, r: sparse_training_loss(
      _apply(cfg, p, r, numerical), {"targets": targets})
  grad = lambda: jax.jit(jax.grad(lambda p, r: loss(p, r), argnums=(0, 1)))
  got = grad()(params, rows)
  monkeypatch.setattr(keye_sparse, "checkpoint_layer", lambda layer: layer)
  want = grad()(params, rows)
  for name in params:
    largest = float(jnp.abs(want[0][name]).max())
    assert largest > 0, name
    assert float(jnp.abs(got[0][name] - want[0][name]).max()) \
        <= 1e-6 * largest, name
  assert float(jnp.abs(got[1] - want[1]).max()) \
      <= 1e-6 * float(jnp.abs(want[1]).max())


# ---- the share --------------------------------------------------------------
def test_the_shares_add_up_with_attention_and_indexer_counted_once():
  """Eight chips of two experts each: every share's layer output is
  ``x + attention + its experts' part``; attention (with its indexer, whose
  loss and counters every share reports alike) is computed by every chip for
  its own tokens, so the shares' expert parts, added to ONE copy of
  ``x + attention``, are the uncut layer."""
  cfg = dataclasses.replace(TOY, num_hidden_layers=1)
  params = _params(cfg, 5)
  p = ref.layer_of(params, 0)
  rows, numerical, _ = _batch(cfg, 5)
  seg = document_segments(numerical, cfg.mean_document_length)
  rcfg = dataclasses.asdict(cfg)
  with jax.default_matmul_precision("highest"):
    layer = jax.jit(decoder_layer, static_argnums=0)
    whole, kl, _, counters = layer(cfg, p, rows, seg)
    h = ref.rms(rows, p["attn_norm"], cfg.rms_norm_eps)
    o, kl_want, chosen = ref.attention(
        rcfg, p, h, ref.document_starts(numerical, cfg.mean_document_length))
    after_attention = rows + o
    h2 = ref.rms(after_attention, p["moe_norm"], cfg.rms_norm_eps)
    total = jnp.zeros_like(rows)
    for first in range(0, 16, 2):
      held = dataclasses.replace(cfg, experts_held=(first, 2))
      mine = {n: (w[first:first + 2] if n in ("w_gate", "w_up", "w_down")
                  else w) for n, w in p.items()}
      y, moe = moe_share(h2.reshape(-1, cfg.hidden_size), mine["router"],
                         mine["w_gate"], mine["w_up"], mine["w_down"],
                         held.share)
      total = total + y.reshape(rows.shape)
      assert int(moe["assignments"]) == int(moe["computed"])
      if first in (0, 14):   # a share's whole layer: x + attention + its part
        part, kl_s, _, counters_s = layer(held, mine, rows, seg)
        assert float(jnp.abs(part - after_attention - y.reshape(rows.shape)
                             ).max()) <= TOL * float(jnp.abs(part).max())
        assert float(kl_s) == float(kl)
        assert jax.tree_util.tree_map(int, counters_s) \
            == jax.tree_util.tree_map(int, counters)
  scale = float(jnp.abs(whole).max())
  assert float(jnp.abs(after_attention + total - whole).max()) <= TOL * scale
  assert float(kl) == pytest.approx(float(kl_want), rel=TOL)
  assert int(counters["selected_pairs"]) == int(chosen.sum())
  # and the uncut layer is the reference's
  uncut = after_attention + ref.moe(rcfg, p, ref.rms(
      after_attention, p["moe_norm"], cfg.rms_norm_eps))
  assert float(jnp.abs(uncut - whole).max()) <= TOL * scale


def test_the_counters_are_the_documents_own_counts():
  """Per layer the same three numbers, whatever the weights: a query keeps
  ``min(visible, topk)`` keys; and the blocks attended and skipped add up to
  the grid, the blocks above the diagonal among the skipped."""
  cfg = TOY
  rows, numerical, _ = _batch(cfg, 6)
  out = jax.jit(lambda p, r: _apply(cfg, p, r, numerical,
                                    with_counters=True))(_params(cfg, 6), rows)
  starts = np.asarray(numerical) < 1.0 / cfg.mean_document_length
  starts[:, 0] = True
  at = np.arange(cfg.seq_len)
  first = np.maximum.accumulate(np.where(starts, at[None], 0), axis=1)
  seen = at[None] - first + 1
  want = {"visible_pairs": int(seen.sum()),
          "selected_pairs": int(np.minimum(seen, cfg.topk).sum()),
          "active_queries": int((seen > cfg.topk).sum())}
  assert set(out["index"]) == set(want) | {"attended_blocks",
                                           "skipped_blocks"}
  for name, n in want.items():
    assert out["index"][name].tolist() == [n] * cfg.num_hidden_layers, name
  # tile x tile blocks of queries and keys: with a selected pair or without
  blocks = B * (cfg.seq_len // cfg.q_chunk_size) ** 2
  assert (out["index"]["attended_blocks"]
          + out["index"]["skipped_blocks"]).tolist() \
      == [blocks] * cfg.num_hidden_layers
  assert (out["index"]["skipped_blocks"] >= blocks // 2 - 3 * B).all()
  assert 0 < want["active_queries"] < B * cfg.seq_len
  assert set(out["moe"]) == {"assignments", "loads", "computed"}


def test_the_configuration_refuses_what_the_layer_cannot_run():
  with pytest.raises(ValueError, match="one shared head"):
    dataclasses.replace(TOY, indexer_num_kv_heads=2)
  with pytest.raises(ValueError, match="indexer_rotary_dim"):
    dataclasses.replace(TOY, indexer_rotary_dim=12)
  with pytest.raises(ValueError, match="query heads over"):
    dataclasses.replace(TOY, num_attention_heads=5)
  with pytest.raises(ValueError, match="one sequence input"):
    KeyeSparse(TOY).apply({"params": _params(TOY)}, jnp.zeros((1, 48)), None)
