"""Laguna (`models/laguna.py`) against the plain reference
(`tests/reference_laguna.py`) at toy widths that keep what the published
model has: layers of two head counts, a window shorter than a document, two
rotary tables (one of them YaRN over half a head), a leading dense layer, a
sigmoid router scaled by 2.5 beside a shared expert. On seeded weights:
logits, loss, every gradient leaf and the gradient of the table's rows; the
rotary tables against numbers worked out by hand from the published keys; the eight shares of the routed experts and the shared expert
counted once, which add up to the uncut layer; one step through
`make_sparse_train_step`; bfloat16 in the router's product or in the scores,
which the model's tolerance refuses. The window's edge, the tile loop and the
splash path at both published head counts: `tests/test_attention.py`."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_laguna as ref
from distributed_embeddings_tpu.layers.attention import rope
from distributed_embeddings_tpu.layers.decoder import (
    document_segments,
    next_token_loss,
)
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.moe import moe_share, shared_expert
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models.laguna import (
    DENSE,
    FULL,
    SLIDING,
    SPARSE,
    Laguna,
    LagunaConfig,
    freeze_rope_parameters,
    layer_shapes,
    rotary_table,
)
from distributed_embeddings_tpu.ops.packed_table import adam_rule
from distributed_embeddings_tpu.parallel.lookup_engine import DistributedLookup
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

# the published rope_parameters, cut to a head of 16: YaRN over 8 of them
ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 16, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1}}
# the published first five layers: dense + full, then sliding x 3, full; a
# window of 5 under documents of mean 8 in 24 tokens, so a window ends
# inside a document and a document starts inside a window
TOY = LagunaConfig(
    hidden_size=32, intermediate_size=48, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=12, shared_expert_intermediate_size=12,
    num_experts=16, num_experts_per_tok=3, sliding_window=5,
    num_hidden_layers=5, layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    mlp_layer_types=(DENSE,) + (SPARSE,) * 4,
    num_attention_heads_per_layer=(4, 6, 6, 6, 4),
    rope_parameters=freeze_rope_parameters(ROPE), vocab_size=50,
    experts_held=(0, 16), seq_len=24, mean_document_length=8,
    attention="xla")
B = 3
# Model against reference in float32 with every product at `highest`: the
# same formulas but for the experts (sort + grouped matmuls against a loop),
# the attention's tiles and the order of sums, so what is left is float32
# rounding. A leaf's largest value times 2e-5 is ten times the largest
# reading over the three cases (logits 1.1e-6, gradients 2.0e-6 of the leaf's
# largest), and a seven-thousandth of what bfloat16 in the scores (0.14) or
# in the router's product (0.23) moves the logits by (the test below).
TOL = 2e-5


def _params(cfg, seed=0):
  rng = np.random.default_rng(seed)
  leaf = lambda shape, kind: jnp.asarray(
      rng.uniform(*((0.8, 1.2) if kind == "gain" else (-0.3, 0.3)), shape),
      jnp.float32)
  params = {f"layer_{i}_{n}": leaf(shape, kind)
            for i in range(cfg.num_hidden_layers)
            for n, (shape, kind) in layer_shapes(cfg, i).items()}
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


def _rcfg(cfg):
  return dict(dataclasses.asdict(cfg), rope_parameters={
      kind: dict(keys) for kind, keys in cfg.rope_parameters})


def _layer_of(params, i):
  return {n[len(f"layer_{i}_"):]: w for n, w in params.items()
          if n.startswith(f"layer_{i}_")}


def test_layers_of_two_head_counts_live_in_one_model():
  rows, numerical, _ = _batch(TOY)
  params = Laguna(TOY).init(jax.random.PRNGKey(0), numerical, None,
                            emb_acts=[rows])["params"]
  want = _params(TOY)
  assert {k: v.shape for k, v in params.items()} \
      == {k: v.shape for k, v in want.items()}
  assert len(params) == 10 + 4 * 14 + 2
  assert params["layer_0_wq"].shape == (32, 4 * 16)        # full: 4 heads
  assert params["layer_1_wq"].shape == params["layer_1_wg"].shape \
      == (32, 6 * 16)                                      # sliding: 6
  assert params["layer_1_wo"].shape == (6 * 16, 32)
  assert params["layer_1_wk"].shape == params["layer_0_wk"].shape == (32, 32)
  assert params["layer_0_w_gate"].shape == (32, 48)        # the dense layer
  assert params["layer_1_w_gate"].shape == (16, 32, 12)    # 16 experts
  assert params["layer_1_shared_down"].shape == (12, 32)
  assert "layer_0_router" not in params
  # the published model, whole: 40 layers, the counts ISSUE 35 works from
  full = LagunaConfig()
  count = lambda i: sum(int(np.prod(s)) for s, _ in
                        layer_shapes(full, i).values())
  attn = lambda h: 2048 * 128 * (3 * h + 16)
  assert count(0) == attn(48) + 3 * 2048 * 8192 + 2 * 2048
  assert count(1) == attn(64) + 257 * 3 * 2048 * 512 + 2048 * 256 + 2 * 2048
  assert full.layer_types[:5] == TOY.layer_types


@pytest.mark.parametrize("cfg", [
    TOY, dataclasses.replace(TOY, experts_held=(4, 8)),
    dataclasses.replace(TOY, seq_len=21, sliding_window=3,
                        mean_document_length=5)],
    ids=["the_whole_layer", "a_share_of_eight_experts", "ragged_length"])
def test_the_model_is_the_plain_reference(cfg):
  rows, numerical, targets = _batch(cfg)
  first, held = cfg.experts_held
  params = {n: w[first:first + held] if w.ndim == 3 else w   # the share's
            for n, w in _params(dataclasses.replace(
                cfg, experts_held=(0, 16))).items()}
  model, rcfg = Laguna(cfg), _rcfg(cfg)
  seg = np.asarray(document_segments(numerical, cfg.mean_document_length))
  assert seg.max() >= 2 and (np.diff(seg, axis=1) >= 0).all()

  def ours(p, r):
    out = model.apply({"params": p}, numerical, None, emb_acts=[r])
    return next_token_loss(out, {"targets": targets}), out

  with jax.default_matmul_precision("highest"):
    (loss, out), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(params, rows)
    want_logits, want_weight = jax.jit(
        lambda p, r: ref.forward(rcfg, p, r, numerical))(params, rows)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p, r: ref.loss(rcfg, p, r, numerical, targets),
        argnums=(0, 1)))(params, rows)
  assert np.array_equal(out["weight"], want_weight)
  assert not np.asarray(out["weight"])[:, -1].any()
  assert 0.6 < float(jnp.mean(out["weight"])) < 0.95
  scale = float(jnp.max(jnp.abs(want_logits)))
  np.testing.assert_allclose(out["logits"], want_logits, atol=TOL * scale)
  assert float(loss) == pytest.approx(float(want_loss), rel=TOL)
  assert set(grads[0]) == set(want_grads[0])
  for name, want in want_grads[0].items():
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, name
    np.testing.assert_allclose(grads[0][name], want, atol=TOL * scale,
                               err_msg=name)
  # the table rows' gradient: what apply_sparse gets, one row an occurrence
  np.testing.assert_allclose(
      grads[1], want_grads[1],
      atol=TOL * float(jnp.max(jnp.abs(want_grads[1]))))


def test_the_counters_of_every_expert_layer_come_out_with_the_model():
  cfg = dataclasses.replace(TOY, experts_held=(4, 8))
  rows, numerical, _ = _batch(cfg, 2)
  params = {n: w[4:12] if w.ndim == 3 else w
            for n, w in _params(TOY, 2).items()}
  out = Laguna(cfg, with_counters=True).apply(
      {"params": params}, numerical, None, emb_acts=[rows])
  moe = out["moe"]
  assert moe["loads"].shape == (4, 8) and moe["assignments"].shape == (4,)
  assert np.array_equal(moe["assignments"], moe["computed"])
  assert np.array_equal(moe["assignments"], np.sum(moe["loads"], axis=1))
  assert 0 < int(moe["assignments"].min()) \
      and int(moe["assignments"].max()) < B * cfg.seq_len * 3


# ---- the rotary tables -----------------------------------------------------
def test_both_rotary_tables_are_the_published_keys_worked_by_hand():
  """Laguna-XS.2's own ``rope_parameters`` at a head of 128. Sliding:
  plain RoPE over all 128 dimensions at theta 1e4. Full: YaRN over the first
  64 at theta 5e5, factor 64, 4,096 original positions: dimension pair ``i``
  turns ``4096 theta^(-i / 32) / 2 pi`` times in the original context; 64
  turns (``beta_fast``) fall at i = 5.66, one (``beta_slow``) at 15.80, so
  pairs 0..5 keep their frequency, pairs 16..31 have it divided by 64 and
  pair ``i`` between them by the blend ``(i - 5) / 11``."""
  cfg = LagunaConfig()
  inv, factor = rotary_table(cfg, SLIDING)
  assert inv.shape == (64,) and inv.dtype == np.float32 and factor == 1.0
  np.testing.assert_allclose(inv[[0, 1, 32, 63]], [
      1.0, 10000 ** (-1 / 64), 0.01, 10000 ** (-63 / 64)], rtol=2e-6)
  inv, factor = rotary_table(cfg, FULL)
  assert inv.shape == (32,) and inv.dtype == np.float32
  assert factor == 1.4158883083359672
  assert factor == pytest.approx(0.1 * math.log(64) + 1)
  turns = lambda i: 4096 * 500000 ** (-i / 32) / (2 * math.pi)
  assert turns(5) > 64 > turns(6) and turns(15) > 1 > turns(16)
  plain = [500000 ** (-i / 32) for i in range(32)]
  np.testing.assert_allclose(inv[:6], plain[:6], rtol=2e-6)
  np.testing.assert_allclose(inv[16:], np.array(plain[16:]) / 64, rtol=2e-6)
  # worked by hand: ln 5e5 = 13.1224, so 5e5^(-10/32) = e^-4.10074 =
  # 0.0165604; the blend 5/11: 0.0090330 + 0.0001176
  assert plain[10] == pytest.approx(0.0165604, rel=1e-5)
  assert inv[10] == pytest.approx(
      0.0165604 * (6 / 11) + 0.0165604 / 64 * (5 / 11), rel=1e-5)
  assert inv[10] == pytest.approx(0.0091506, rel=1e-4)
  assert (np.diff(inv) < 0).all()
  # and the rotation: the first 64 dimensions of a head turn, the rest pass
  x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 2, 128)),
                  jnp.float32)
  y = np.asarray(rope(x, jnp.asarray([0, 1, 4097]), inv, factor))
  assert np.array_equal(y[..., 64:], np.asarray(x)[..., 64:])
  np.testing.assert_allclose(y[0, :, :64], factor * np.asarray(x)[0, :, :64],
                             rtol=1e-6)
  for pos, at in ((1, 1), (4097, 2)):
    for i in (0, 10, 31):
      a, b = np.asarray(x)[at, 0, i], np.asarray(x)[at, 0, 32 + i]
      ang = pos * float(inv[i])
      np.testing.assert_allclose(
          y[at, 0, [i, 32 + i]],
          [factor * (a * math.cos(ang) - b * math.sin(ang)),
           factor * (b * math.cos(ang) + a * math.sin(ang))],
          rtol=2e-4, atol=2e-6)
  # the reference's table, written out on its own from the same keys
  rcfg = _rcfg(cfg)
  for kind in (SLIDING, FULL):
    inv, factor = rotary_table(cfg, kind)
    cos, sin = ref.rotary(rcfg, kind, 5000)
    ang = np.arange(5000)[:, None] * inv.astype(np.float64)[None, :]
    np.testing.assert_allclose(cos[:, :len(inv)], factor * np.cos(ang),
                               atol=2e-3)   # float32 frequencies at 5,000
    np.testing.assert_allclose(cos[:9, :len(inv)],
                               factor * np.cos(ang[:9]), atol=2e-6)


# ---- the expert layer ------------------------------------------------------
def test_the_shares_and_the_shared_expert_counted_once_add_up():
  """Eight chips hold two routed experts each and every one computes the
  shared expert for its own tokens: the routed parts of all shares plus the
  shared expert ONCE are the uncut layer (and the scaled sigmoid router is
  the reference's, expert by expert)."""
  cfg = TOY
  rows, _, _ = _batch(cfg, 3)
  h = rows.reshape(-1, cfg.hidden_size)
  p = _layer_of(_params(cfg, 3), 1)
  rcfg = _rcfg(cfg)
  with jax.default_matmul_precision("highest"):
    whole = ref.sparse_mlp(rcfg, p, h)
    routed = ref.sparse_mlp(rcfg, p, h, shared=False)
    parts, assigned = [], 0
    for first in range(0, 16, 2):
      share = dataclasses.replace(cfg, experts_held=(first, 2)).share
      sl = slice(first, first + 2)
      out, c = moe_share(h, p["router"], p["w_gate"][sl], p["w_up"][sl],
                         p["w_down"][sl], share)
      np.testing.assert_allclose(out, ref.sparse_mlp(
          dict(rcfg, experts_held=(first, 2)),
          {**p, "w_gate": p["w_gate"][sl], "w_up": p["w_up"][sl],
           "w_down": p["w_down"][sl]}, h, shared=False), atol=1e-5)
      assigned += int(c["assignments"])
      parts.append(out)
    shared = shared_expert(h, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
  assert assigned == h.shape[0] * cfg.num_experts_per_tok
  scale = float(jnp.max(jnp.abs(whole)))
  np.testing.assert_allclose(sum(parts), routed, atol=1e-5 * scale)
  np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5 * scale)
  # counted eight times it is another layer; left out, another again
  assert float(jnp.max(jnp.abs(shared))) > 0.05 * scale
  # the chosen weights sum to the routed scaling factor
  w = ref.router_weights(rcfg, h, p["router"])
  np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.5, rtol=1e-6)
  assert np.all(np.sum(np.asarray(w) > 0, axis=-1) == 3)


@pytest.mark.parametrize("where", ["router", "scores"])
def test_bfloat16_in_the_router_or_in_the_scores_fails_the_tolerance(where):
  """The reference with the router's product alone, or the attention scores
  alone, in bfloat16 (one MXU pass; everything else float32): the logits
  move by a tenth and more of their largest, thousands of times `TOL`."""
  cfg = TOY
  rows, numerical, _ = _batch(cfg, 6)
  params, rcfg = _params(cfg, 6), _rcfg(cfg)
  low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
  with jax.default_matmul_precision("highest"):
    want = ref.forward(rcfg, params, rows, numerical)[0]
    if where == "router":
      plain, name = ref.router_weights, "router_weights"
      stand_in = lambda c, h, w: plain(c, low(h), low(w))
    else:
      plain, name = ref.rotate, "rotate"
      stand_in = lambda x, cos, sin: low(plain(x, cos, sin))
    setattr(ref, name, stand_in)
    try:
      got = ref.forward(rcfg, params, rows, numerical)[0]
    finally:
      setattr(ref, name, plain)
  gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
  assert gap > 0.1 > 1000 * TOL, gap


# ---- the sparse train step -------------------------------------------------
def _plan(cfg, batch):
  return DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)


def test_one_step_on_the_sparse_train_step_is_the_references():
  """Token table as a sequence input under summed Adam, the dense leaves
  under SGD (so that a leaf's change IS its gradient): the step's loss, every
  dense gradient and the new token rows against the plain reference's."""
  cfg, batch, lr = TOY, 4, 0.05
  rng = np.random.default_rng(4)
  cats = jnp.asarray(rng.integers(0, 12, (batch, cfg.seq_len)), jnp.int32)
  _, numerical, _ = _batch(cfg, 4, batch)
  labels = {"targets": jnp.pad(cats[:, 1:], ((0, 0), (0, 1)))}
  plan = _plan(cfg, batch)
  model, dense = Laguna(cfg), _params(cfg, 4)
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
  assert float(loss) == pytest.approx(float(want_loss), rel=TOL)
  for leaf, g in g_dense.items():
    scale = float(jnp.max(jnp.abs(g)))
    np.testing.assert_allclose(dense[leaf] - after["dense"][leaf], g,
                               atol=TOL * scale, err_msg=leaf)
  # summed Adam's first step on the rows read is optax.adam's on the table
  tx = optax.adam(lr)
  upd, _ = tx.update(g_table, tx.init(table0), table0)
  touched = np.unique(np.asarray(cats))
  table1 = np.asarray(layout.unpack(after["fused"][name])[0])
  # Adam's first step is the rate times g / (|g| + 1e-8): where |g| is
  # float32 noise around 0 its sign is too, so compare where it is not
  g_rows = np.abs(np.asarray(g_table)[touched])
  sure = g_rows > 10 * TOL * g_rows.max()
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
  plan = _plan(cfg, batch)
  model = Laguna(cfg)
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


# ---- what the configuration refuses ----------------------------------------
def test_without_a_tpu_the_splash_path_raises():
  assert LagunaConfig().attention == "splash"
  rows, numerical, _ = _batch(TOY, 1)
  params = _params(TOY, 1)
  with pytest.raises(ValueError, match="is a TPU kernel"):
    Laguna(dataclasses.replace(TOY, attention="splash")).apply(
        {"params": params}, numerical, None, emb_acts=[rows])
  with pytest.raises(ValueError, match="one sequence input"):
    Laguna(TOY).apply({"params": params}, numerical, None)
  with pytest.raises(ValueError, match="layer_types names"):
    dataclasses.replace(TOY, layer_types=("linear_attention",) * 5)
  with pytest.raises(ValueError, match="mlp_layer_types names"):
    dataclasses.replace(TOY, mlp_layer_types=("moe",) * 5)
  with pytest.raises(ValueError, match="names 5 layers of 6"):
    dataclasses.replace(TOY, num_hidden_layers=6)
  with pytest.raises(ValueError, match="5 query heads over 2"):
    dataclasses.replace(TOY, num_attention_heads_per_layer=(4, 5, 6, 6, 4))
  with pytest.raises(ValueError, match="rope_type='linear'"):
    rotary_table(dataclasses.replace(TOY, rope_parameters=(
        (FULL, (("rope_type", "linear"), ("rope_theta", 1e4))),)), FULL)
