"""LFM2-MoE (`models/lfm2_moe.py`) against the plain reference
(`tests/reference_lfm2_moe.py`) at toy widths that keep what the published
model has: the mixer's kind and the feed-forward's kind varying
independently by layer (a convolution layer with a dense MLP, an attention
layer and three convolution layers with experts: published layers 0, 2, 3,
4, 5), grouped queries with a q/k norm and RoPE over the whole head, a
sigmoid router whose choice is made under a selection bias. On seeded
weights: logits, loss, every gradient leaf and the gradient of the table's
rows; the eight shares of the experts, the dense parts counted once, which
add up to the uncut layer; packed documents against the documents alone; one
step through `make_sparse_train_step`, which leaves the bias where it was.
Heads of 64 through both attention paths: `tests/test_attention.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_lfm2_moe as ref
from distributed_embeddings_tpu.layers.decoder import (
    document_segments,
    next_token_loss,
)
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.moe import moe_share
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models.lfm2_moe import (
    CONV,
    DENSE,
    EXPERTS,
    FULL,
    Lfm2Moe,
    Lfm2MoeConfig,
    decoder_layer,
    layer_shapes,
)
from distributed_embeddings_tpu.ops.packed_table import adam_rule
from distributed_embeddings_tpu.parallel.lookup_engine import DistributedLookup
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

# the published layers 0, 2, 3, 4, 5 at toy widths: 16 experts top 4, 4 query
# heads over 2 key-value heads, documents of mean 8 in 24 tokens
TOY = Lfm2MoeConfig(
    hidden_size=32, intermediate_size=48, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, moe_intermediate_size=12,
    num_experts=16, num_experts_per_tok=4, layers_here=(0, 2, 3, 4, 5),
    vocab_size=50, experts_held=(0, 16), seq_len=24, mean_document_length=8,
    attention="xla")
B = 3
# Model against reference in float32 with every product at `highest`: the
# same formulas but for the experts (sort + grouped matmuls against a loop),
# the attention's tiles, the convolution's shifts and the order of sums, so
# what is left is float32 rounding; ten times the largest reading
TOL = 2e-5


def _params(cfg, seed=0):
  rng = np.random.default_rng(seed)
  ranges = {"gain": (0.8, 1.2), "matrix": (-0.3, 0.3), "conv": (-0.5, 0.5),
            "bias": (-0.1, 0.1)}
  leaf = lambda shape, kind: jnp.asarray(
      rng.uniform(*ranges[kind], shape), jnp.float32)
  params = {f"layer_{i}_{n}": leaf(shape, kind)
            for i, kinds in enumerate(cfg.kinds)
            for n, (shape, kind) in layer_shapes(cfg, *kinds).items()}
  params["embedding_norm"] = leaf((cfg.hidden_size,), "gain")
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
  return dataclasses.asdict(cfg)


def _share_of(params, first, held):
  """The leaves of a chip that holds experts ``first .. first + held``."""
  return {n: w[first:first + held] if w.ndim == 3 else w
          for n, w in params.items()}


def test_the_two_kinds_vary_independently_and_the_counts_are_the_issues():
  assert TOY.kinds == ((CONV, DENSE), (FULL, EXPERTS), (CONV, EXPERTS),
                       (CONV, EXPERTS), (CONV, EXPERTS))
  rows, numerical, _ = _batch(TOY)
  params = Lfm2Moe(TOY).init(jax.random.PRNGKey(0), numerical, None,
                             emb_acts=[rows])["params"]
  assert {k: v.shape for k, v in params.items()} \
      == {k: v.shape for k, v in _params(TOY).items()}
  assert len(params) == 8 + 13 + 3 * 10 + 2
  assert params["layer_0_w_in"].shape == (32, 96)
  assert params["layer_0_conv"].shape == (3, 32)
  assert params["layer_0_w_gate"].shape == (32, 48)        # the dense layer
  assert params["layer_1_wq"].shape == (32, 32)
  assert params["layer_1_wk"].shape == (32, 16)
  assert params["layer_1_q_norm"].shape == (8,)
  assert params["layer_1_expert_bias"].shape == (16,)
  assert params["layer_2_w_down"].shape == (16, 12, 32)
  assert "layer_0_router" not in params and "layer_1_conv" not in params
  assert not np.asarray(params["layer_1_expert_bias"]).any()   # starts at 0
  assert float(jnp.max(jnp.abs(params["layer_0_conv"]))) <= 3 ** -0.5
  # the published model, whole: 40 layers, the counts ISSUE 42 works from
  full = Lfm2MoeConfig()
  assert len(full.layer_types) == 40 and len(full.kinds) == 40
  assert sum(m == FULL for m, _ in full.kinds) == 10
  assert sum(f == DENSE for _, f in full.kinds) == 2
  assert full.kinds[:6] == ((CONV, DENSE), (CONV, DENSE), (FULL, EXPERTS),
                            (CONV, EXPERTS), (CONV, EXPERTS), (CONV, EXPERTS))
  assert full.head_dim * full.num_attention_heads == full.hidden_size
  count = lambda shapes, names: sum(
      int(np.prod(shapes[n][0])) for n in names)
  conv = layer_shapes(full, CONV, DENSE)
  assert count(conv, ("w_in", "conv", "w_out")) == 16783360
  assert count(conv, ("w_gate", "w_up", "w_down")) == 72351744
  attn = layer_shapes(dataclasses.replace(full, experts_held=(0, 8)), FULL,
                      EXPERTS)
  assert count(attn, ("wq", "wk", "wv", "wo", "q_norm", "k_norm")) == 10485888
  assert count(attn, ("w_gate", "w_up", "w_down")) == 75497472
  assert count(attn, ("router", "expert_bias")) == 131072 + 64


@pytest.mark.parametrize("cfg", [
    TOY, dataclasses.replace(TOY, experts_held=(4, 8)),
    dataclasses.replace(TOY, seq_len=21, mean_document_length=5),
    dataclasses.replace(TOY, use_expert_bias=False, norm_topk_prob=False,
                        routed_scaling_factor=1.5)],
    ids=["the_whole_layer", "a_share_of_eight_experts", "ragged_length",
         "no_bias_no_renormalisation"])
def test_the_model_is_the_plain_reference(cfg):
  rows, numerical, targets = _batch(cfg)
  params = _share_of(_params(dataclasses.replace(cfg, experts_held=(0, 16))),
                     *cfg.experts_held)
  model, rcfg = Lfm2Moe(cfg), _rcfg(cfg)
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
  scale = float(jnp.max(jnp.abs(want_logits)))
  np.testing.assert_allclose(out["logits"], want_logits, atol=TOL * scale)
  assert float(loss) == pytest.approx(float(want_loss), rel=TOL)
  assert set(grads[0]) == set(want_grads[0])
  for name, want in want_grads[0].items():
    if name.endswith("expert_bias"):
      # it enters the choice alone: exactly zero on both sides
      assert not np.asarray(grads[0][name]).any(), name
      assert not np.asarray(want).any(), name
      continue
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
  params = _share_of(_params(TOY, 2), 4, 8)
  out = Lfm2Moe(cfg, with_counters=True).apply(
      {"params": params}, numerical, None, emb_acts=[rows])
  moe = out["moe"]
  assert moe["loads"].shape == (4, 8) and moe["assignments"].shape == (4,)
  assert np.array_equal(moe["assignments"], moe["computed"])
  assert np.array_equal(moe["assignments"], np.sum(moe["loads"], axis=1))
  # the bias moved some choices and not all
  slots = B * cfg.seq_len * cfg.num_experts_per_tok
  assert moe["moved"].shape == (4,)
  assert 0 < int(moe["moved"].min()) and int(moe["moved"].max()) < slots // 2
  # and with the bias at 0 none
  still = {n: jnp.zeros_like(w) if n.endswith("expert_bias") else w
           for n, w in params.items()}
  out = Lfm2Moe(cfg, with_counters=True).apply(
      {"params": still}, numerical, None, emb_acts=[rows])
  assert not np.asarray(out["moe"]["moved"]).any()


def test_the_eight_shares_and_the_dense_parts_counted_once_add_up():
  """Eight chips hold two experts each; every one computes the mixer (and,
  on a dense layer, the MLP) for its own tokens. An expert layer's output is
  ``x + mixer`` ONCE plus the eight shares' expert parts, and that is the
  uncut reference's layer; each share alone is the reference's share."""
  cfg = TOY
  rows, numerical, _ = _batch(cfg, 3)
  params, rcfg = _params(cfg, 3), _rcfg(cfg)
  seg = document_segments(numerical, cfg.mean_document_length)
  starts = ref.document_starts(rcfg, numerical)
  for i, (mixer, ffn) in enumerate(cfg.kinds):
    if ffn != EXPERTS:
      continue
    p = ref.layer_of(params, i)
    with jax.default_matmul_precision("highest"):
      h = ref.rms(rows, p["operator_norm"], cfg.norm_eps)
      mixed = rows + (ref.short_conv(p, h, starts) if mixer == CONV
                      else ref.attention(rcfg, p, h, starts))
      hf = ref.rms(mixed, p["ffn_norm"], cfg.norm_eps).reshape(
          -1, cfg.hidden_size)
      whole = mixed + ref.experts(rcfg, p, hf).reshape(mixed.shape)
      parts, assigned = [], 0
      for first in range(0, 16, 2):
        share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
        ps = _share_of(p, first, 2)
        out, c = decoder_layer(share_cfg, mixer, ffn, ps, rows, seg)
        np.testing.assert_allclose(
            out - mixed, ref.experts(dict(rcfg, experts_held=(first, 2)), ps,
                                     hf).reshape(mixed.shape), atol=2e-5)
        y, c2 = moe_share(hf, ps["router"], ps["w_gate"], ps["w_up"],
                          ps["w_down"], share_cfg.share, ps["expert_bias"])
        assert int(c["assignments"]) == int(c2["assignments"])
        assigned += int(c["assignments"])
        parts.append(out - mixed)
    assert assigned == hf.shape[0] * cfg.num_experts_per_tok
    scale = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(mixed + sum(parts), whole, atol=1e-5 * scale)
    # the mixer counted eight times is another layer
    assert float(jnp.max(jnp.abs(mixed - rows))) > 0.02 * scale
  # the chosen weights sum to the routed scaling factor, four an expert layer
  w = ref.router_weights(rcfg, hf, p["router"], p["expert_bias"])
  np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.0, rtol=1e-6)
  assert np.all(np.sum(np.asarray(w) > 0, axis=-1) == 4)
  # the bias moved some token's choice, and the weights are the unbiased s's
  plain = ref.router_weights(rcfg, hf, p["router"])
  assert np.any((np.asarray(w) > 0) != (np.asarray(plain) > 0))
  s = np.asarray(jax.nn.sigmoid(hf @ p["router"]))
  chosen = np.asarray(w) > 0
  np.testing.assert_allclose(
      np.asarray(w)[chosen],
      (s / np.sum(s * chosen, axis=-1, keepdims=True))[chosen], rtol=2e-5)


def test_packed_documents_give_what_the_documents_give_alone():
  """The whole model on a packed sequence against each document run alone
  (rotary positions are relative, so a document's place in the sequence
  moves nothing but rounding)."""
  cfg = TOY
  rows, numerical, _ = _batch(cfg, 7, batch=1)
  params = _params(cfg, 7)
  starts = np.asarray(ref.document_starts(_rcfg(cfg), numerical))[0]
  edges = list(np.flatnonzero(starts)) + [cfg.seq_len]
  assert len(edges) >= 3
  with jax.default_matmul_precision("highest"):
    packed = Lfm2Moe(cfg).apply({"params": params}, numerical, None,
                                emb_acts=[rows])["logits"]
    for a, e in zip(edges[:-1], edges[1:]):
      alone = Lfm2Moe(dataclasses.replace(cfg, seq_len=int(e - a))).apply(
          {"params": params}, jnp.ones((1, e - a)), None,
          emb_acts=[rows[:, a:e]])["logits"]
      np.testing.assert_allclose(
          packed[0, a:e], alone[0],
          atol=2e-4 * float(jnp.max(jnp.abs(packed))))


# ---- the sparse train step -------------------------------------------------
def _plan(cfg, batch):
  return DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)


def test_one_step_on_the_sparse_train_step_is_the_references():
  """Token table as a sequence input under summed Adam, the dense leaves
  under SGD (so that a leaf's change IS its gradient): the step's loss, every
  dense gradient and the new token rows against the plain reference's; the
  selection bias is left bit for bit."""
  cfg, batch, lr = TOY, 4, 0.05
  rng = np.random.default_rng(4)
  cats = jnp.asarray(rng.integers(0, 12, (batch, cfg.seq_len)), jnp.int32)
  _, numerical, _ = _batch(cfg, 4, batch)
  labels = {"targets": jnp.pad(cats[:, 1:], ((0, 0), (0, 1)))}
  plan = _plan(cfg, batch)
  model, dense = Lfm2Moe(cfg), _params(cfg, 4)
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
    if leaf.endswith("expert_bias"):
      assert np.array_equal(after["dense"][leaf], dense[leaf]), leaf
      continue
    scale = float(jnp.max(jnp.abs(g)))
    np.testing.assert_allclose(dense[leaf] - after["dense"][leaf], g,
                               atol=TOL * scale, err_msg=leaf)
  tx = optax.adam(lr)
  upd, _ = tx.update(g_table, tx.init(table0), table0)
  touched = np.unique(np.asarray(cats))
  table1 = np.asarray(layout.unpack(after["fused"][name])[0])
  g_rows = np.abs(np.asarray(g_table)[touched])
  sure = g_rows > 10 * TOL * g_rows.max()
  assert sure.mean() > 0.9
  np.testing.assert_allclose(
      (table1[touched] - np.asarray(table0)[touched])[sure],
      np.asarray(upd)[touched][sure], atol=1e-3 * lr)
  idle = np.setdiff1d(np.arange(cfg.vocab_size), touched)
  assert len(idle) and np.array_equal(table1[idle], np.asarray(table0)[idle])


def test_the_whole_thing_trains_on_the_sparse_step_and_adam_leaves_the_bias():
  """30 steps on one batch through Adam on both sides: the loss falls, and
  the bias (zero gradient from zero moments: a zero step) is where it was."""
  cfg, batch = TOY, 4
  rng = np.random.default_rng(5)
  cats = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)),
                     jnp.int32)
  _, numerical, _ = _batch(cfg, 5, batch)
  labels = {"targets": jnp.pad(cats[:, 1:], ((0, 0), (0, 1)))}
  plan = _plan(cfg, batch)
  model = Lfm2Moe(cfg)
  dense = dict(model.init(jax.random.PRNGKey(0), numerical, None, emb_acts=[
      jnp.zeros((batch, cfg.seq_len, cfg.hidden_size))])["params"])
  bias = np.asarray(_params(cfg, 5)["layer_2_expert_bias"])
  dense["layer_2_expert_bias"] = jnp.asarray(bias)   # the step donates it
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
  assert np.array_equal(state["dense"]["layer_2_expert_bias"], bias)
  assert not np.asarray(state["dense"]["layer_1_expert_bias"]).any()


# ---- what the configuration refuses ----------------------------------------
def test_without_a_tpu_the_splash_path_raises():
  assert Lfm2MoeConfig().attention == "splash"
  rows, numerical, _ = _batch(TOY, 1)
  params = _params(TOY, 1)
  with pytest.raises(ValueError, match="is a TPU kernel"):
    Lfm2Moe(dataclasses.replace(TOY, attention="splash")).apply(
        {"params": params}, numerical, None, emb_acts=[rows])
  with pytest.raises(ValueError, match="one sequence input"):
    Lfm2Moe(TOY).apply({"params": params}, numerical, None)
  with pytest.raises(ValueError, match="layer_types names"):
    dataclasses.replace(TOY, layer_types=("linear_attention",) * 40)
  with pytest.raises(ValueError, match="layers_here names layer 40 of 40"):
    dataclasses.replace(TOY, layers_here=(0, 40))
  with pytest.raises(ValueError, match="4 query heads over 3"):
    dataclasses.replace(TOY, num_key_value_heads=3)
