"""Olmo-Hybrid (`models/olmo_hybrid.py`) against the plain reference
(`tests/reference_olmo_hybrid.py`) at toy widths that keep ``d_k != d_v``,
on seeded weights, in float64: logits, loss, every gradient leaf and the
gradient of the table's rows, over packed documents;
the two head shares of both mixers, which add up to the whole layer; one
step through `make_sparse_train_step` (loss, dense gradients, the token rows'
summed-Adam update) against the reference's; bfloat16 inside the recurrence,
which the model's tolerance refuses. The splash path at the published head
shapes: `tests/test_attention.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_olmo_hybrid as ref
from distributed_embeddings_tpu.layers.decoder import (
    document_segments,
    next_token_loss,
)
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models.olmo_hybrid import (
    FULL,
    LINEAR,
    OlmoHybrid,
    OlmoHybridConfig,
    full_attention_mixer,
    layer_shapes,
    linear_attention_mixer,
)
from distributed_embeddings_tpu.ops.packed_table import adam_rule
from distributed_embeddings_tpu.parallel.lookup_engine import DistributedLookup
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

# one period of the published pattern; 24 tokens in chunks of 8 with a
# document every 6 tokens on average: starts fall mid-chunk
TOY = OlmoHybridConfig(
    hidden_size=32, intermediate_size=48, num_attention_heads=4, head_dim=8,
    linear_key_head_dim=6, linear_value_head_dim=10, layer_types=(
        LINEAR, LINEAR, LINEAR, FULL), vocab_size=50, heads_held=(0, 4),
    seq_len=24, mean_document_length=6, chunk=8, attention="xla")
B = 3
# Model against reference in float64 (`jax.enable_x64`): the same formulas
# but for the rule (chunks against one token at a time), the convolution and
# the attention's tiles, so what is left is float64 rounding. 1e-9 of a leaf's
# largest value is five hundred times the largest reading (1.8e-12).
TOL64 = 1e-9
# In float32 this toy is ill-conditioned: three stacked recurrent layers at
# d_k 6 (more tokens a chunk than key dimensions) amplify rounding, and the
# REFERENCE's own float32 gradients stand 1e-4 to 2e-3 of a leaf's largest
# value from its float64 self. The float32 train step is therefore held to
# 5e-3: over that noise, and a sixth of the least that bfloat16 inside the
# recurrence moves any leaf (3%; the logits by 20-60%: the test below)
TOL32 = 5e-3


def _leaf(rng, shape, leaf):
  """Seeded toy weights by kind of leaf (``layer_shapes``), large enough
  that every path matters; gains off 1."""
  lo, hi = {"matrix": (-0.3, 0.3), "gain": (0.8, 1.2), "conv": (-0.5, 0.5),
            "a_log": (-1.0, 1.0), "dt_bias": (-2.0, 0.5)}[leaf]
  return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)


def _params(cfg, seed=0):
  rng = np.random.default_rng(seed)
  params = {f"layer_{i}_{n}": _leaf(rng, shape, leaf)
            for i, kind in enumerate(cfg.layer_types)
            for n, (shape, leaf) in layer_shapes(cfg, kind).items()}
  params["final_norm"] = _leaf(rng, (cfg.hidden_size,), "gain")
  params["head"] = _leaf(rng, (cfg.hidden_size, cfg.vocab_size), "matrix")
  return params


def _batch(cfg, seed=0, batch=B):
  rng = np.random.default_rng(seed)
  rows = jnp.asarray(rng.normal(size=(batch, cfg.seq_len, cfg.hidden_size))
                     * 0.5, jnp.float32)
  numerical = jnp.asarray(rng.random((batch, cfg.seq_len)), jnp.float32)
  targets = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                     (batch, cfg.seq_len)), jnp.int32)
  return rows, numerical, targets


def _rcfg(cfg):
  return dataclasses.asdict(cfg)


def _f64(tree):
  return jax.tree_util.tree_map(
      lambda x: x.astype(jnp.float64) if x.dtype == jnp.float32 else x, tree)


def test_the_models_leaves_are_the_layers_shapes():
  rows, numerical, _ = _batch(TOY)
  params = OlmoHybrid(TOY).init(jax.random.PRNGKey(0), numerical, None,
                                emb_acts=[rows])["params"]
  want = _params(TOY)
  assert {k: v.shape for k, v in params.items()} \
      == {k: v.shape for k, v in want.items()}
  assert len(params) == 3 * 18 + 11 + 2
  # fla's ranges: a seeded head forgets between a half and a thousandth a token
  for i in range(3):
    decay = np.exp(-np.exp(params[f"layer_{i}_a_log"]) * np.log1p(np.exp(
        params[f"layer_{i}_dt_bias"])))
    assert 0.45 < decay.min() and decay.max() < 0.9995


@pytest.mark.parametrize("cfg", [
    TOY, dataclasses.replace(TOY, heads_held=(1, 2)),
    dataclasses.replace(TOY, seq_len=21, chunk=16, mean_document_length=4)],
    ids=["all_heads_held", "a_share_of_two_heads", "ragged_length"])
def test_the_model_is_the_plain_reference(cfg):
  with jax.enable_x64(True):
    rows, numerical, targets = _f64(_batch(cfg))
    params = _f64(_params(cfg))
    model, rcfg = OlmoHybrid(cfg), _rcfg(cfg)
    seg = np.asarray(document_segments(numerical, cfg.mean_document_length))
    assert seg.max() >= 2 and (np.diff(seg, axis=1) >= 0).all()

    def ours(p, r):
      out = model.apply({"params": p}, numerical, None, emb_acts=[r])
      return next_token_loss(out, {"targets": targets}), out

    (loss, out), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(params, rows)
    want_logits, want_weight = jax.jit(
        lambda p, r: ref.forward(rcfg, p, r, numerical))(params, rows)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p, r: ref.loss(rcfg, p, r, numerical, targets),
        argnums=(0, 1)))(params, rows)
    assert out["logits"].dtype == jnp.float64
    assert np.array_equal(out["weight"], want_weight)
    # a document's last token and the sequence's last count for nothing
    assert not np.asarray(out["weight"])[:, -1].any()
    assert 0.6 < float(jnp.mean(out["weight"])) < 0.95
    scale = float(jnp.max(jnp.abs(want_logits)))
    np.testing.assert_allclose(out["logits"], want_logits, atol=TOL64 * scale)
    assert float(loss) == pytest.approx(float(want_loss), rel=TOL64)
    assert set(grads[0]) == set(want_grads[0])
    for name, want in want_grads[0].items():
      scale = float(jnp.max(jnp.abs(want)))
      assert scale > 0, name
      np.testing.assert_allclose(grads[0][name], want, atol=TOL64 * scale,
                                 err_msg=name)
    # the table rows' gradient: what apply_sparse gets, one row an occurrence
    np.testing.assert_allclose(
        grads[1], want_grads[1], atol=TOL64 * float(jnp.max(jnp.abs(
            want_grads[1]))))


def _layer_of(params, i):
  return {n[len(f"layer_{i}_"):]: w for n, w in params.items()
          if n.startswith(f"layer_{i}_")}


def _share(cfg, kind, p, first, count):
  """Heads ``first .. first+count`` of a layer's weights: columns of the
  projections, rows of ``Wo``, channels of the convolutions and of the q/k
  gains, entries of the per-head leaves."""
  width = {"wq": cfg.head_dim, "wk": cfg.head_dim, "wv": cfg.head_dim,
           "q_norm": cfg.head_dim, "k_norm": cfg.head_dim} if kind == FULL \
      else {"wq": cfg.linear_key_head_dim, "wk": cfg.linear_key_head_dim,
            "conv_q": cfg.linear_key_head_dim,
            "conv_k": cfg.linear_key_head_dim,
            "wv": cfg.linear_value_head_dim, "wg": cfg.linear_value_head_dim,
            "conv_v": cfg.linear_value_head_dim, "wb": 1, "wa": 1,
            "a_log": 1, "dt_bias": 1}
  out = dict(p)
  for name, w in width.items():
    out[name] = p[name][..., first * w:(first + count) * w]
  rows = cfg.head_dim if kind == FULL else cfg.linear_value_head_dim
  out["wo"] = p["wo"][first * rows:(first + count) * rows]
  return out


@pytest.mark.parametrize("kind", [LINEAR, FULL])
def test_the_two_head_shares_add_up_to_the_whole_layer(kind):
  """Each chip of a tensor-parallel pair computes ``o Wo`` over its own two
  heads of four; their sum is the uncut reference's mixer (the sublayer's
  norm, the residual and the MLP are counted once, after the sum). The q/k
  norm of the deployment spans a chip's channels: two groups."""
  cfg = TOY
  half = dataclasses.replace(cfg, heads_held=(0, 2))
  mixer = linear_attention_mixer if kind == LINEAR else full_attention_mixer
  with jax.enable_x64(True):
    rows, numerical, _ = _f64(_batch(cfg, 3))
    whole = _f64(_layer_of(_params(cfg, 3), 0 if kind == LINEAR else 3))
    seg = document_segments(numerical, cfg.mean_document_length)
    starts = ref.document_starts(numerical, cfg.mean_document_length)
    parts = [mixer(dataclasses.replace(half, heads_held=(first, 2)),
                   _share(cfg, kind, whole, first, 2), rows, seg)
             for first in (0, 2)]
    if kind == LINEAR:
      want = ref.linear_mixer(_rcfg(cfg), whole, rows, starts)[0]
    else:
      want = ref.full_mixer(_rcfg(cfg), whole, rows, starts, norm_groups=2)
      published = ref.full_mixer(_rcfg(cfg), whole, rows, starts)
    scale = float(jnp.max(jnp.abs(want)))
    for part in parts:   # neither share is nothing
      assert float(jnp.max(jnp.abs(part))) > 0.1 * scale
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=TOL64 * scale)
    if kind == FULL:
      # the stated departure: one norm across all four heads' channels is
      # another layer (the gains differ by group here, so visibly)
      assert float(jnp.max(jnp.abs(published - want))) > 0.01 * scale


def test_one_step_on_the_sparse_train_step_is_the_references():
  """Token table as a sequence input under summed Adam, the dense leaves
  under SGD (so that a leaf's change IS its gradient): the step's loss, every
  dense gradient and the new token rows against the plain reference's."""
  cfg, batch, lr = TOY, 4, 0.05
  rng = np.random.default_rng(4)
  cats = jnp.asarray(rng.integers(0, 12, (batch, cfg.seq_len)), jnp.int32)
  _, numerical, _ = _batch(cfg, 4, batch)
  labels = {"targets": jnp.pad(cats[:, 1:], ((0, 0), (0, 1)))}
  plan = DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)
  model, dense = OlmoHybrid(cfg), _params(cfg, 4)
  rule, opt = adam_rule(lr, summed=True), optax.sgd(1.0)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1))
  (name, buf), = state["fused"].items()
  layout = DistributedLookup(plan).fused_layouts(rule)[name]
  table0 = layout.unpack(buf)[0][:cfg.vocab_size]
  with jax.default_matmul_precision("highest"):
    step = make_sparse_train_step(model, plan, next_token_loss, opt, rule,
                                  None, state, (numerical, [cats], labels),
                                  donate=False)
    after, loss = step(state, numerical, [cats], labels)
    want_loss, (g_dense, g_table) = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss(_rcfg(cfg), p, jnp.take(t, cats, axis=0),
                              numerical, labels["targets"]),
        argnums=(0, 1)))(dense, table0)
    # and the program's own model, differentiated outside the step: the
    # same float32 function compiled another way, so held to ten times the
    # rounding it reads on this toy (1.1e-5)
    own_loss, own = jax.jit(jax.value_and_grad(
        lambda p: next_token_loss(model.apply(
            {"params": p}, numerical, None,
            emb_acts=[jnp.take(table0, cats, axis=0)]), labels)))(dense)
  assert float(loss) == pytest.approx(float(own_loss), rel=1e-6)
  assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
  for leaf, g in g_dense.items():
    scale = float(jnp.max(jnp.abs(g)))
    change = dense[leaf] - after["dense"][leaf]
    np.testing.assert_allclose(change, own[leaf], atol=1e-4 * scale,
                               err_msg=leaf)
    np.testing.assert_allclose(change, g, atol=TOL32 * scale, err_msg=leaf)
  # summed Adam's first step on the rows read is optax.adam's on the table
  tx = optax.adam(lr)
  upd, _ = tx.update(g_table, tx.init(table0), table0)
  touched = np.unique(np.asarray(cats))
  table1 = np.asarray(layout.unpack(after["fused"][name])[0])
  # Adam's first step is the rate times g / (|g| + 1e-8): where |g| is
  # float32 noise around 0 its sign is too, so compare where it is not
  g_rows = np.abs(np.asarray(g_table)[touched])
  sure = g_rows > TOL32 * g_rows.max()
  assert sure.mean() > 0.9
  np.testing.assert_allclose(
      (table1[touched] - np.asarray(table0)[touched])[sure],
      np.asarray(upd)[touched][sure], atol=1e-3 * lr)
  idle = np.setdiff1d(np.arange(cfg.vocab_size), touched)
  assert len(idle) and np.array_equal(table1[idle], np.asarray(table0)[idle])


def test_the_whole_thing_trains_on_the_sparse_step():
  """30 steps on one batch through Adam on both sides: the loss falls."""
  cfg, batch = TOY, 4
  rng = np.random.default_rng(5)
  cats = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)),
                     jnp.int32)
  _, numerical, _ = _batch(cfg, 5, batch)
  labels = {"targets": jnp.pad(cats[:, 1:], ((0, 0), (0, 1)))}
  plan = DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)
  model = OlmoHybrid(cfg)
  dense = model.init(jax.random.PRNGKey(0), numerical, None, emb_acts=[
      jnp.zeros((batch, cfg.seq_len, cfg.hidden_size))])["params"]
  rule, opt = adam_rule(3e-3, summed=True), optax.adam(3e-3)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1))
  step = make_sparse_train_step(model, plan, next_token_loss, opt, rule, None,
                                state, (numerical, [cats], labels))
  losses = []
  for _ in range(30):
    state, loss = step(state, numerical, [cats], labels)
    losses.append(float(loss))
  assert np.all(np.isfinite(losses))
  assert 0.7 * np.log(cfg.vocab_size) < losses[0] < 1.5 * np.log(
      cfg.vocab_size)
  assert losses[-1] < 0.7 * losses[0]


def test_bfloat16_inside_the_recurrence_fails_the_models_tolerance():
  """The reference with its recurrence alone in bfloat16 (state, decays and
  the two products a token; everything around it float32): the logits move
  by tens of times `TOL32`, and so does every gradient leaf."""
  cfg = TOY
  rows, numerical, targets = _batch(cfg, 6)
  params = _params(cfg, 6)
  plain = ref.delta_rule

  def low(q, k, v, alpha, beta, starts):
    o, s = plain(*(x.astype(jnp.bfloat16) for x in (q, k, v, alpha, beta)),
                 starts)
    return o.astype(jnp.float32), s.astype(jnp.float32)
  with jax.default_matmul_precision("highest"):
    both = lambda: jax.jit(lambda p: (
        ref.forward(_rcfg(cfg), p, rows, numerical)[0],
        jax.grad(lambda p: ref.loss(_rcfg(cfg), p, rows, numerical,
                                    targets))(p)))(params)
    want, g_want = both()
    ref.delta_rule = low
    try:
      got, g_got = both()
    finally:
      ref.delta_rule = plain
  gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
  assert 20 * TOL32 < gap, gap
  for leaf, w in g_want.items():
    gap = float(jnp.max(jnp.abs(g_got[leaf] - w)) / jnp.max(jnp.abs(w)))
    assert gap > 4 * TOL32, (leaf, gap)


def test_without_a_tpu_the_splash_path_raises():
  assert OlmoHybridConfig().attention == "splash"
  rows, numerical, _ = _batch(TOY, 1)
  params = _params(TOY, 1)
  with pytest.raises(ValueError, match="is a TPU kernel"):
    OlmoHybrid(dataclasses.replace(TOY, attention="splash")).apply(
        {"params": params}, numerical, None, emb_acts=[rows])
  with pytest.raises(ValueError, match="splash or xla"):
    OlmoHybrid(dataclasses.replace(TOY, attention="auto")).apply(
        {"params": params}, numerical, None, emb_acts=[rows])
  with pytest.raises(ValueError, match="one sequence input"):
    OlmoHybrid(TOY).apply({"params": params}, numerical, None)
  with pytest.raises(ValueError, match="heads_held"):
    dataclasses.replace(TOY, heads_held=(3, 2))
  with pytest.raises(ValueError, match="layer_types names"):
    dataclasses.replace(TOY, layer_types=("sliding_attention",))
