"""Solar-Open2 (`models/solar_open2.py`) against the plain reference
(`tests/reference_solar_open2.py`) at toy widths that keep what the published
model has: one period of the pattern (attention, then three layers of the
delta rule whose decay is per key channel), grouped-query attention without
positions under a gate, low-rank decay and output-gate chains, a sigmoid
router that chooses under a bias beside a shared expert in every layer. On
seeded weights: logits, weight, loss, every gradient leaf and the gradient of
the table's rows, whole and as one chip's share of heads and experts; the
shares of the heads and of the experts, with what every chip computes alike
counted once, which add up to the uncut layer; four wrong models that the
comparison refuses; packed documents against the documents alone; one step
through `make_sparse_train_step`. The rule itself: `tests/test_gated_delta.py`.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_solar_open2 as ref
from distributed_embeddings_tpu.layers.decoder import (
    document_segments,
    rms_norm,
)
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models.solar_open2 import (
    GQA,
    KDA,
    SolarOpen2,
    SolarOpen2Config,
    decoder_layer,
    gqa_mixer,
    kda_mixer,
    layer_shapes,
    next_token_loss,
)
from distributed_embeddings_tpu.ops.packed_table import adam_rule
from distributed_embeddings_tpu.parallel.lookup_engine import DistributedLookup
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

# the published layers 0..3 at toy widths: 4 heads of 8 (2 key-value heads),
# 16 experts top 4, documents of mean 10 in 40 tokens, the rule in chunks of
# 32 (two sub-blocks of 16 a chunk, the second chunk padded)
TOY = SolarOpen2Config(
    hidden_size=32, moe_intermediate_size=12, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, linear_num_heads=4, linear_head_dim=8,
    gqa_layers=(0, 4), n_routed_experts=16, num_experts_per_tok=4,
    num_hidden_layers=8, layers_here=(0, 1, 2, 3), vocab_size=50,
    heads_held=(0, 4), experts_held=(0, 16), seq_len=40,
    mean_document_length=10, chunk=32, attention="xla")
B = 3
# Model against reference in float32 with every product at `highest`: the
# same formulas but for the rule (chunks against tokens), the experts (sort
# + grouped matmuls against a loop), the attention's tiles and the order of
# sums, so what is left is float32 rounding: the logits and the loss read
# 2.9e-6 to 6.0e-6 of their scale on three seeds. A gradient leaf passes three
# recurrent layers, each behind an l2 norm over a head of EIGHT channels (a
# near-zero head is amplified by 1 / |q|, which 128 channels do not see) and
# under beta up to 2: its worst leaf reads 3.2e-5 to 1.5e-4, and 1.8e-6 in
# float64, where only the router stays float32. The wrong models below read
# 0.2 to 1.3 by the logits and 0.5 to 6 by a gradient leaf
TOL = 2e-5
GRAD_TOL = 1e-3
# a head's 8 columns of these leaves (wb: its one column; wo: its 8 rows;
# a_log: its one value; dt_bias, b_g: its 8 values)
_HEAD_COLUMNS = ("wq", "wk", "wv", "wg", "conv_q", "conv_k", "conv_v",
                 "w_fb", "w_gb")


def _params(cfg, seed=0):
  rng = np.random.default_rng(seed)
  ranges = {"gain": (0.8, 1.2), "matrix": (-0.3, 0.3), "bias": (-0.1, 0.1),
            "conv": (-0.5, 0.5), "a_log": (0.0, 2.0), "dt_bias": (-4.0, 1.0)}
  leaf = lambda shape, kind: jnp.asarray(
      rng.uniform(*ranges[kind], shape), jnp.float32)
  params = {f"layer_{i}_{n}": leaf(shape, kind)
            for i, kind in enumerate(cfg.kinds)
            for n, (shape, kind) in layer_shapes(cfg, kind).items()}
  params["norm"] = leaf((cfg.hidden_size,), "gain")
  params["head"] = leaf((cfg.hidden_size, cfg.vocab_size), "matrix")
  return params


def _batch(cfg, seed=0, batch=B):
  rng = np.random.default_rng(seed)
  rows = jnp.asarray(rng.normal(size=(batch, cfg.seq_len, cfg.hidden_size))
                     * 0.5, jnp.float32)
  numerical = jnp.asarray(rng.random((batch, cfg.seq_len)), jnp.float32)
  targets = jnp.asarray(rng.integers(
      0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32)
  return rows, numerical, {"targets": targets}


def _rcfg(cfg):
  return dataclasses.asdict(cfg)


def _share_of(cfg, params, heads, experts):
  """The leaves of a chip that holds heads ``heads = (first, count)`` of
  both mixers and experts ``experts = (first, count)``, cut from the whole
  model's ``params`` (of ``cfg``, which holds every head and expert)."""
  (h0, hn), (e0, en) = heads, experts
  hd, group = cfg.head_dim, cfg.group

  def cut(name, w):
    leaf = name.split("_", 2)[2] if name.startswith("layer_") else name
    kind = cfg.kinds[int(name.split("_")[1])] if name.startswith("layer_") \
        else None
    if w.ndim == 3:                                    # an expert's matrix
      return w[e0:e0 + en]
    if kind == GQA and leaf in ("wk", "wv"):           # key-value heads
      return w[:, h0 // group * hd:(h0 + hn) // group * hd]
    if leaf in _HEAD_COLUMNS:
      return w[:, h0 * hd:(h0 + hn) * hd]
    if leaf == "wb":
      return w[:, h0:h0 + hn]
    if leaf == "wo":
      return w[h0 * hd:(h0 + hn) * hd]
    if leaf == "a_log":
      return w[h0:h0 + hn]
    if leaf in ("dt_bias", "b_g"):
      return w[h0 * hd:(h0 + hn) * hd]
    return w      # norms, w_fa, w_ga, o_norm, router, bias, shared, head
  return {n: cut(n, w) for n, w in params.items()}


def test_the_layers_kinds_and_the_counts_are_the_issues():
  assert TOY.kinds == (GQA, KDA, KDA, KDA)
  rows, numerical, _ = _batch(TOY)
  params = jax.jit(lambda: SolarOpen2(TOY).init(
      jax.random.PRNGKey(0), numerical, None, emb_acts=[rows])["params"])()
  assert {k: v.shape for k, v in params.items()} \
      == {k: v.shape for k, v in _params(TOY).items()}
  assert len(params) == (6 + 9) + 3 * (17 + 9) + 2
  assert params["layer_0_wk"].shape == (32, 2 * 8)          # 2 kv heads
  assert params["layer_0_wg"].shape == (32, 4 * 8)
  assert params["layer_1_w_fa"].shape == (32, 8)
  assert params["layer_1_w_fb"].shape == (8, 4 * 8)
  assert params["layer_1_dt_bias"].shape == (4 * 8,)
  assert params["layer_1_a_log"].shape == (4,)
  assert params["layer_2_w_down"].shape == (16, 12, 32)
  assert "layer_0_a_log" not in params and "layer_1_wg" not in params
  assert not np.asarray(params["layer_1_expert_bias"]).any()   # starts at 0
  assert not np.asarray(params["layer_1_b_g"]).any()
  # the published model, whole, and the chip's share: ISSUE 51's counts
  full = SolarOpen2Config()
  assert len(full.kinds) == 48 and full.kinds[:5] == (GQA, KDA, KDA, KDA, GQA)
  assert sum(k == GQA for k in full.kinds) == 12 and full.group == 8
  router = full.share.router
  assert (router.score, router.renormalise, router.scale,
          router.selection_bias) == ("sigmoid", True, 1.0, True)
  held = dataclasses.replace(full, heads_held=(0, 8), experts_held=(0, 8),
                             layers_here=(0, 1, 2, 3), vocab_size=24576)
  count = lambda shapes, names: sum(
      int(np.prod(shapes[n][0])) for n in names)
  gqa, kda = layer_shapes(held, GQA), layer_shapes(held, KDA)
  experts = ("w_gate", "w_up", "w_down")
  rest = ("shared_gate", "shared_up", "shared_down", "router",
          "input_norm", "post_attention_norm")
  assert count(kda, experts) == 125829120
  assert count(kda, rest) == 17047552
  assert count(kda, set(kda) - set(experts) - set(rest) - {"expert_bias"}) \
      == 18135176
  assert count(gqa, ("wq", "wk", "wv", "wg", "wo")) == 13631488
  one_period = count(gqa, gqa) + 3 * count(kda, kda)
  assert one_period == 4 * (125829120 + 17047552 + 320) + 13631488 \
      + 3 * 18135176
  # with the final norm, the head and the table (the issue's 840.8 M left
  # the four selection biases out and rounded down)
  assert one_period + 4096 + 2 * 4096 * 24576 == 840875672
  assert held.share.head_rows(8192 * 8) == 6560     # four loads of 1,638.4


SHARES = {
    "the_whole_layers": ((0, 4), (0, 16)),
    "a_share_of_heads": ((2, 2), (0, 16)),
    "a_share_of_experts": ((0, 4), (4, 8)),
    "a_share_of_both": ((0, 2), (12, 4)),
}


@pytest.mark.parametrize("heads,experts", SHARES.values(), ids=SHARES.keys())
def test_the_model_is_the_plain_reference(heads, experts):
  cfg = dataclasses.replace(TOY, heads_held=heads, experts_held=experts)
  rows, numerical, labels = _batch(cfg)
  params = _share_of(TOY, _params(TOY), heads, experts)
  assert {k: v.shape for k, v in params.items()} \
      == {k: v.shape for k, v in _params(cfg).items()}
  model, rcfg = SolarOpen2(cfg), _rcfg(cfg)
  seg = np.asarray(document_segments(numerical, cfg.mean_document_length))
  assert seg.max() >= 2 and (np.diff(seg, axis=1) >= 0).all()

  def ours(p, r):
    out = model.apply({"params": p}, numerical, None, emb_acts=[r])
    return next_token_loss(out, labels), out

  with jax.default_matmul_precision("highest"):
    (loss, out), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(params, rows)
    want_logits, want_weight = jax.jit(
        lambda p, r: ref.forward(rcfg, p, r, numerical))(params, rows)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p, r: ref.loss(rcfg, p, r, numerical, labels["targets"]),
        argnums=(0, 1)))(params, rows)
  assert np.array_equal(out["weight"], want_weight)
  assert not np.asarray(out["weight"])[:, -1].any()
  scale = float(jnp.max(jnp.abs(want_logits)))
  np.testing.assert_allclose(out["logits"], want_logits, atol=TOL * scale)
  assert float(loss) == pytest.approx(float(want_loss), rel=TOL)
  assert set(grads[0]) == set(want_grads[0])
  for name, w in want_grads[0].items():
    if name.endswith("expert_bias"):
      # it enters the choice alone: exactly zero on both sides
      assert not np.asarray(grads[0][name]).any(), name
      assert not np.asarray(w).any(), name
      continue
    scale = float(jnp.max(jnp.abs(w)))
    assert scale > 0, name
    np.testing.assert_allclose(grads[0][name], w, atol=GRAD_TOL * scale,
                               err_msg=name)
  scale = float(jnp.max(jnp.abs(want_grads[1])))
  np.testing.assert_allclose(grads[1], want_grads[1], atol=GRAD_TOL * scale)


WRONG = {
    "the_rule_in_bfloat16": dict(rule_dtype=jnp.bfloat16),
    "the_attention_layers_gate_dropped": dict(gate=False),
    "one_decay_a_head": dict(scalar_decay=True),
    "a_rotary_pass_on_the_attention_layer": dict(rope=True),
}


@pytest.mark.parametrize("wrong", WRONG.values(), ids=WRONG.keys())
def test_a_wrong_model_fails_the_comparison(wrong):
  """The reference with one thing wrong stands outside the tolerance the
  model is held to, by the logits AND by a gradient leaf: the comparison
  sees a lower precision inside the rule, a missing gate, a decay that is
  not per channel and positions that the model does not have."""
  cfg, rcfg = TOY, _rcfg(TOY)
  rows, numerical, labels = _batch(cfg)
  params = _params(cfg)
  with jax.default_matmul_precision("highest"):
    out = jax.jit(lambda p, r: SolarOpen2(cfg).apply(
        {"params": p}, numerical, None, emb_acts=[r]))(params, rows)
    grads = jax.jit(jax.grad(lambda p: next_token_loss(SolarOpen2(cfg).apply(
        {"params": p}, numerical, None, emb_acts=[rows]), labels)))(params)
    logits, _ = jax.jit(lambda p, r: ref.forward(
        rcfg, p, r, numerical, **wrong))(params, rows)
    wrong_grads = jax.jit(jax.grad(lambda p: ref.loss(
        rcfg, p, rows, numerical, labels["targets"], **wrong)))(params)
  scale = float(jnp.max(jnp.abs(out["logits"])))
  gap = float(jnp.max(jnp.abs(out["logits"] - logits))) / scale
  assert gap > 5000 * TOL, gap
  worst = max(
      float(jnp.max(jnp.abs(grads[n] - w)) / jnp.max(jnp.abs(grads[n])))
      for n, w in wrong_grads.items() if not n.endswith("expert_bias"))
  assert worst > 100 * GRAD_TOL, worst


def test_the_counters_of_every_expert_layer_come_out_with_the_model():
  cfg = dataclasses.replace(TOY, experts_held=(4, 8))
  rows, numerical, _ = _batch(cfg, 2)
  params = _share_of(TOY, _params(TOY, 2), (0, 4), (4, 8))
  out = SolarOpen2(cfg, with_counters=True).apply(
      {"params": params}, numerical, None, emb_acts=[rows])
  moe = out["moe"]                       # every layer is an expert layer
  assert moe["loads"].shape == (4, 8) and moe["assignments"].shape == (4,)
  assert np.array_equal(moe["assignments"], moe["computed"])
  assert np.array_equal(moe["assignments"], np.sum(moe["loads"], axis=1))
  slots = B * cfg.seq_len * cfg.num_experts_per_tok
  assert 0 < int(moe["moved"].min()) and int(moe["moved"].max()) < slots // 2


@pytest.mark.parametrize("kind", [GQA, KDA])
def test_every_share_and_what_every_chip_computes_once_add_up(kind):
  """Two chips hold two heads each of a mixer (tensor parallel), eight hold
  two experts each (expert parallel). The mixer's output is the SUM of the
  head shares' partial products with ``W_o``, each computed with the whole
  ``W_fa``, ``W_ga`` and ``o_norm`` (replicated: counted in every share,
  added nowhere); the expert layer's is the shared expert ONCE plus the
  eight shares' routed parts under the whole router; together they are the
  uncut reference's layer, and each share alone is the reference's share."""
  cfg, layer_i = TOY, (0 if kind == GQA else 1)
  rows, numerical, _ = _batch(cfg, 3)
  params, rcfg = _params(cfg, 3), _rcfg(cfg)
  seg = document_segments(numerical, cfg.mean_document_length)
  starts = ref.document_starts(rcfg, numerical)
  eps = cfg.rms_norm_eps
  p = ref.leaves_of(params, f"layer_{layer_i}_")
  mixer, ref_mixer = (gqa_mixer, ref.gqa_mixer) if kind == GQA \
      else (kda_mixer, ref.kda_mixer)
  with jax.default_matmul_precision("highest"):
    want = ref.layer(rcfg, p, rows, starts, kind)
    u = rms_norm(rows, p["input_norm"], eps)
    partial = []
    for first in (0, 2):
      share_cfg = dataclasses.replace(cfg, heads_held=(first, 2))
      ps = ref.leaves_of(_share_of(cfg, params, (first, 2), (0, 16)),
                         f"layer_{layer_i}_")
      part = mixer(share_cfg, ps, u, seg)
      np.testing.assert_allclose(
          part, ref_mixer(rcfg, ps, u, starts), atol=2e-5)
      if kind == KDA:   # the low-rank halves and the norm are every chip's
        for whole in ("w_fa", "w_ga", "o_norm"):
          assert np.array_equal(ps[whole], p[whole])
      partial.append(part)
    mixed = rows + sum(partial)
    np.testing.assert_allclose(
        sum(partial), ref_mixer(rcfg, p, u, starts), atol=2e-5)
    hf = ref.rms(mixed, p["post_attention_norm"], eps).reshape(
        -1, cfg.hidden_size)
    shared = ref.swiglu(hf, p["shared_gate"], p["shared_up"],
                        p["shared_down"]).reshape(mixed.shape)
    routed, assigned = [], 0
    for first in range(0, 16, 2):
      share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
      ps = ref.leaves_of(_share_of(cfg, params, (0, 4), (first, 2)),
                         f"layer_{layer_i}_")
      out, c = decoder_layer(share_cfg, kind, ps, rows, seg)
      part = out - mixed - shared
      np.testing.assert_allclose(
          part, ref.routed_experts(dict(rcfg, experts_held=(first, 2)), ps,
                                   hf).reshape(mixed.shape), atol=2e-5)
      assigned += int(c["assignments"])
      routed.append(part)
  assert assigned == hf.shape[0] * cfg.num_experts_per_tok
  scale = float(jnp.max(jnp.abs(want)))
  np.testing.assert_allclose(mixed + shared + sum(routed), want,
                             atol=1e-5 * scale)
  # a head share or the shared expert counted twice is another layer
  assert float(jnp.max(jnp.abs(partial[0]))) > 0.02 * scale
  assert float(jnp.max(jnp.abs(shared))) > 0.02 * scale
  # the chosen weights sum to the routed scaling factor, four a token
  w = ref.router_weights(rcfg, hf, p["router"], p["expert_bias"])
  np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.0, rtol=1e-6)
  assert np.all(np.sum(np.asarray(w) > 0, axis=-1) == 4)


def test_packed_documents_give_what_the_documents_give_alone():
  """No layer has positions: a document's logits are the same wherever it
  stands in the sequence, and its weight is 0 at its last token."""
  cfg = TOY
  rows, numerical, _ = _batch(cfg, 7, batch=1)
  params = _params(cfg, 7)
  starts = np.asarray(ref.document_starts(_rcfg(cfg), numerical))[0]
  edges = list(np.flatnonzero(starts)) + [cfg.seq_len]
  assert len(edges) >= 3
  run = lambda cfg, numerical, rows: jax.jit(lambda p, n, r: SolarOpen2(
      cfg).apply({"params": p}, n, None, emb_acts=[r]))(params, numerical,
                                                       rows)
  with jax.default_matmul_precision("highest"):
    packed = run(cfg, numerical, rows)
    scale = float(jnp.max(jnp.abs(packed["logits"])))
    for a, e in zip(edges[:-1], edges[1:]):
      alone = run(dataclasses.replace(cfg, seq_len=int(e - a)),
                  jnp.ones((1, e - a)), rows[:, a:e])
      np.testing.assert_allclose(packed["logits"][0, a:e],
                                 alone["logits"][0], atol=2e-4 * scale)
      assert np.array_equal(packed["weight"][0, a:e], alone["weight"][0])
      assert np.array_equal(alone["weight"][0], [1.0] * (e - a - 1) + [0.0])


# ---- the sparse train step -------------------------------------------------
def _plan(cfg, batch):
  return DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)


def _ids_and_labels(cfg, seed, batch, vocab):
  rng = np.random.default_rng(seed)
  cats = jnp.asarray(rng.integers(0, vocab, (batch, cfg.seq_len)), jnp.int32)
  return cats, {"targets": jnp.pad(cats[:, 1:], ((0, 0), (0, 1)))}


def test_one_step_on_the_sparse_train_step_is_the_references():
  """Token table as a sequence input under summed Adam, the dense leaves
  under SGD (so that a leaf's change IS its gradient): the step's loss,
  every dense gradient and the new token rows against the plain
  reference's; the selection bias is left bit for bit."""
  cfg = dataclasses.replace(TOY, heads_held=(0, 2), experts_held=(4, 8))
  batch, lr = 4, 0.05
  cats, labels = _ids_and_labels(cfg, 4, batch, 12)
  _, numerical, _ = _batch(cfg, 4, batch)
  plan = _plan(cfg, batch)
  model, dense = SolarOpen2(cfg), _params(cfg, 4)
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
                               atol=GRAD_TOL * scale, err_msg=leaf)
  tx = optax.adam(lr)
  upd, _ = tx.update(g_table, tx.init(table0), table0)
  touched = np.unique(np.asarray(cats))
  table1 = np.asarray(layout.unpack(after["fused"][name])[0])
  g_rows = np.abs(np.asarray(g_table)[touched])
  sure = g_rows > 10 * GRAD_TOL * g_rows.max()
  assert sure.mean() > 0.9
  np.testing.assert_allclose(
      (table1[touched] - np.asarray(table0)[touched])[sure],
      np.asarray(upd)[touched][sure], atol=1e-3 * lr)
  idle = np.setdiff1d(np.arange(cfg.vocab_size), touched)
  assert len(idle) and np.array_equal(table1[idle], np.asarray(table0)[idle])


def test_the_whole_thing_trains():
  """30 guarded steps on one batch through Adam on both sides: the loss
  falls, no step is bad, and the bias (zero gradient from zero moments) is
  where it was."""
  cfg, batch = TOY, 4
  cats, labels = _ids_and_labels(cfg, 5, batch, cfg.vocab_size)
  _, numerical, _ = _batch(cfg, 5, batch)
  plan = _plan(cfg, batch)
  model = SolarOpen2(cfg)
  dense = dict(model.init(jax.random.PRNGKey(0), numerical, None, emb_acts=[
      jnp.zeros((batch, cfg.seq_len, cfg.hidden_size))])["params"])
  bias = np.asarray(_params(cfg, 5)["layer_2_expert_bias"])
  dense["layer_2_expert_bias"] = jnp.asarray(bias)   # the step donates it
  rule, opt = adam_rule(3e-3), optax.adam(3e-3)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1))
  step = make_sparse_train_step(model, plan, next_token_loss, opt, rule,
                                None, state, (numerical, [cats], labels),
                                guard=True)
  losses = []
  for _ in range(30):
    state, loss, metrics = step(state, numerical, [cats], labels)
    assert int(metrics["bad_step"]) == 0
    losses.append(float(loss))
  log_v = np.log(cfg.vocab_size)
  assert 0.7 * log_v < losses[0] < 1.5 * log_v
  assert losses[-1] < 0.7 * losses[0]
  assert np.array_equal(state["dense"]["layer_2_expert_bias"], bias)


# ---- what the configuration refuses ----------------------------------------
def test_what_the_configuration_refuses():
  assert SolarOpen2Config().attention == "splash"
  rows, numerical, _ = _batch(TOY, 1)
  params = _params(TOY, 1)
  with pytest.raises(ValueError, match="is a TPU kernel"):
    SolarOpen2(dataclasses.replace(TOY, attention="splash")).apply(
        {"params": params}, numerical, None, emb_acts=[rows])
  with pytest.raises(ValueError, match="one sequence input"):
    SolarOpen2(TOY).apply({"params": params}, numerical, None)
  with pytest.raises(ValueError, match="layers_here names layer 8 of 8"):
    dataclasses.replace(TOY, layers_here=(0, 8))
  with pytest.raises(ValueError, match="whole groups"):
    dataclasses.replace(TOY, heads_held=(1, 2))
  with pytest.raises(ValueError, match="heads_held"):
    dataclasses.replace(TOY, heads_held=(2, 4))
  for key, value in (("use_rope", True), ("kda_use_full_proj", True),
                     ("use_gqa_gate", False), ("first_k_dense_replace", 1)):
    with pytest.raises(ValueError, match=f"{key}="):
      dataclasses.replace(TOY, **{key: value})
