"""GLM-4.7-Flash (`models/glm_moe_lite.py`) against the plain reference
(`tests/reference_glm_moe_lite.py`) at toy widths that keep what the published
model has: a leading dense layer and expert layers after it, latent attention
whose keys end in one shared rotary part, a sigmoid router that chooses under
a bias and scales by 1.8 beside a shared expert, and a prediction module that
reads the token rows shifted by one and predicts through the trunk's own head.
On seeded weights: both logit arrays, both weights, both losses, every
gradient leaf and the gradient of the table's rows (the head's and the rows'
being sums of two uses); the eight shares of the experts, attention, the
shared expert and the dense parts counted once, which add up to the uncut
layer, for a trunk layer and for the module's; packed documents against the
documents alone, the module's weights at a document's last two positions
included; one step through `make_sparse_train_step`, which leaves the bias
where it was and reports both terms. Latent attention itself:
`tests/test_latent_attention.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_glm_moe_lite as ref
from distributed_embeddings_tpu.layers.decoder import document_segments
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.moe import moe_share
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models.glm_moe_lite import (
    DENSE,
    EXPERTS,
    MTP_LOSS_WEIGHT,
    GlmMoeLite,
    GlmMoeLiteConfig,
    decoder_layer,
    layer_shapes,
    mtp_module,
    mtp_shapes,
    mtp_training_loss,
)
from distributed_embeddings_tpu.ops.packed_table import adam_rule
from distributed_embeddings_tpu.parallel.lookup_engine import DistributedLookup
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

# the published layers 0, 1, 2 and the module at toy widths: 16 experts top
# 4, 4 heads of 6 + 4 and 12, documents of mean 8 in 24 tokens
TOY = GlmMoeLiteConfig(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=12,
    num_attention_heads=4, q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=6,
    qk_rope_head_dim=4, v_head_dim=12, n_routed_experts=16,
    num_experts_per_tok=4, layers_here=(0, 1, 2), vocab_size=50,
    experts_held=(0, 16), seq_len=24, mean_document_length=8,
    attention="xla")
B = 3
# Model against reference in float32 with every product at `highest`: the
# same formulas but for the experts (sort + grouped matmuls against a loop),
# the attention's tiles and the order of sums, so what is left is float32
# rounding; ten times the largest reading
TOL = 2e-5


def _params(cfg, seed=0):
  rng = np.random.default_rng(seed)
  ranges = {"gain": (0.8, 1.2), "matrix": (-0.3, 0.3), "bias": (-0.1, 0.1)}
  leaf = lambda shape, kind: jnp.asarray(
      rng.uniform(*ranges[kind], shape), jnp.float32)
  params = {f"layer_{i}_{n}": leaf(shape, kind)
            for i, ffn in enumerate(cfg.kinds)
            for n, (shape, kind) in layer_shapes(cfg, ffn).items()}
  params.update({f"mtp_{n}": leaf(shape, kind)
                 for n, (shape, kind) in mtp_shapes(cfg).items()})
  params["norm"] = leaf((cfg.hidden_size,), "gain")
  params["head"] = leaf((cfg.hidden_size, cfg.vocab_size), "matrix")
  return params


def _batch(cfg, seed=0, batch=B):
  rng = np.random.default_rng(seed)
  rows = jnp.asarray(rng.normal(size=(batch, cfg.seq_len, cfg.hidden_size))
                     * 0.5, jnp.float32)
  numerical = jnp.asarray(rng.random((batch, cfg.seq_len)), jnp.float32)
  targets, targets_2 = (jnp.asarray(rng.integers(
      0, cfg.vocab_size, (batch, cfg.seq_len)), jnp.int32) for _ in range(2))
  return rows, numerical, {"targets": targets, "targets_2": targets_2}


def _rcfg(cfg):
  return dataclasses.asdict(cfg)


def _share_of(params, first, held):
  """The leaves of a chip that holds experts ``first .. first + held``."""
  return {n: w[first:first + held] if w.ndim == 3 else w
          for n, w in params.items()}


def test_the_layers_kinds_and_the_counts_are_the_issues():
  assert TOY.kinds == (DENSE, EXPERTS, EXPERTS)
  rows, numerical, _ = _batch(TOY)
  params = GlmMoeLite(TOY).init(jax.random.PRNGKey(0), numerical, None,
                                emb_acts=[rows])["params"]
  assert {k: v.shape for k, v in params.items()} \
      == {k: v.shape for k, v in _params(TOY).items()}
  assert len(params) == 12 + 2 * 17 + (4 + 17) + 2
  assert params["layer_0_w_gate"].shape == (32, 48)        # the dense layer
  assert params["layer_1_w_dkv"].shape == (32, 8 + 4)
  assert params["layer_1_w_ukv"].shape == (8, 4 * (6 + 12))
  assert params["layer_2_w_down"].shape == (16, 12, 32)
  assert params["mtp_w_eh"].shape == (64, 32)
  assert params["mtp_layer_shared_up"].shape == (32, 12)
  assert "layer_0_router" not in params and "mtp_head" not in params
  assert not np.asarray(params["layer_1_expert_bias"]).any()   # starts at 0
  # the published model, whole: the counts ISSUE 47 works from
  full = GlmMoeLiteConfig()
  assert len(full.kinds) == 47 and full.kinds[:2] == (DENSE, EXPERTS)
  assert sum(f == DENSE for f in full.kinds) == 1
  router = full.share.router
  assert (router.score, router.renormalise, router.scale,
          router.selection_bias) == ("sigmoid", True, 1.8, True)
  count = lambda shapes, names: sum(
      int(np.prod(shapes[n][0])) for n in names)
  held8 = dataclasses.replace(full, experts_held=(0, 8))
  dense, experts = layer_shapes(held8, DENSE), layer_shapes(held8, EXPERTS)
  latent = ("w_dq", "q_a_norm", "w_uq", "w_dkv", "kv_a_norm", "w_ukv", "w_o")
  assert count(dense, latent) == 21759232
  assert count(dense, ("w_gate", "w_up", "w_down")) == 62914560
  assert count(dense, dense) == 84677888
  assert count(experts, ("w_gate", "w_up", "w_down")) == 75497472
  assert count(experts, ("shared_gate", "shared_up", "shared_down")) == 9437184
  assert count(experts, ("router", "expert_bias")) == 131136
  assert count(experts, experts) == 106829120
  module = mtp_shapes(held8)
  assert count(module, module) == 115223872
  assert 84677888 + 4 * 106829120 + 115223872 + 2048 + 2048 * 19360 \
      == 666869568


@pytest.mark.parametrize("cfg", [
    TOY, dataclasses.replace(TOY, experts_held=(4, 8)),
    dataclasses.replace(TOY, seq_len=21, mean_document_length=5),
    dataclasses.replace(TOY, norm_topk_prob=False, routed_scaling_factor=1.0,
                        layers_here=(1, 2))],
    ids=["the_whole_layer", "a_share_of_eight_experts", "ragged_length",
         "no_renormalisation_no_dense_layer"])
def test_the_model_is_the_plain_reference(cfg):
  rows, numerical, labels = _batch(cfg)
  params = _share_of(_params(dataclasses.replace(cfg, experts_held=(0, 16))),
                     *cfg.experts_held)
  model, rcfg = GlmMoeLite(cfg), _rcfg(cfg)
  seg = np.asarray(document_segments(numerical, cfg.mean_document_length))
  assert seg.max() >= 2 and (np.diff(seg, axis=1) >= 0).all()

  def ours(p, r):
    out = model.apply({"params": p}, numerical, None, emb_acts=[r])
    loss, terms = mtp_training_loss(out, labels)
    return loss, (out, terms)

  args = (rcfg, params, rows, numerical, labels["targets"],
          labels["targets_2"])
  with jax.default_matmul_precision("highest"):
    (loss, (out, terms)), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(params, rows)
    want = jax.jit(lambda p, r: ref.forward(rcfg, p, r, numerical))(
        params, rows)
    want_terms = jax.jit(lambda p, r: ref.losses(
        rcfg, p, r, numerical, *args[4:]))(params, rows)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p, r: ref.loss(rcfg, p, r, numerical, *args[4:]),
        argnums=(0, 1)))(params, rows)
    # a use at a time: the trunk's term alone, the module's alone
    term = lambda i: jax.jit(jax.grad(lambda p, r: ref.losses(
        rcfg, p, r, numerical, *args[4:])[i], argnums=(0, 1)))(params, rows)
    first, second = term(0), term(1)
  for name in ("weight", "mtp_weight"):
    assert np.array_equal(out[name], want[name]), name
  assert not np.asarray(out["weight"])[:, -1].any()
  assert not np.asarray(out["mtp_weight"])[:, -2:].any()
  for name in ("logits", "mtp_logits"):
    scale = float(jnp.max(jnp.abs(want[name])))
    np.testing.assert_allclose(out[name], want[name], atol=TOL * scale,
                               err_msg=name)
  assert float(terms["next_token_loss"]) == pytest.approx(
      float(want_terms[0]), rel=TOL)
  assert float(terms["mtp_loss"]) == pytest.approx(float(want_terms[1]),
                                                   rel=TOL)
  assert float(loss) == pytest.approx(float(want_loss), rel=TOL)
  assert float(loss) == pytest.approx(
      float(want_terms[0]) + MTP_LOSS_WEIGHT * float(want_terms[1]), rel=TOL)
  assert set(grads[0]) == set(want_grads[0])
  for name, w in want_grads[0].items():
    if name.endswith("expert_bias"):
      # it enters the choice alone: exactly zero on both sides
      assert not np.asarray(grads[0][name]).any(), name
      assert not np.asarray(w).any(), name
      continue
    scale = float(jnp.max(jnp.abs(w)))
    assert scale > 0, name
    np.testing.assert_allclose(grads[0][name], w, atol=TOL * scale,
                               err_msg=name)
    # the module's leaves see the module's term alone, the trunk's final
    # norm (the module reads the trunk before it) the trunk's alone; every
    # other leaf, the head among them, sees both
    assert bool(np.asarray(first[0][name]).any()) \
        == (not name.startswith("mtp_")), name
    assert bool(np.asarray(second[0][name]).any()) == (name != "norm"), name
  # the table rows' gradient: what apply_sparse gets, one row an occurrence,
  # the sum of the trunk's use and of the module's (shifted by one)
  scale = float(jnp.max(jnp.abs(want_grads[1])))
  np.testing.assert_allclose(grads[1], want_grads[1], atol=TOL * scale)
  np.testing.assert_allclose(first[1] + MTP_LOSS_WEIGHT * second[1],
                             want_grads[1], atol=TOL * scale)
  assert float(jnp.max(jnp.abs(second[1]))) > 0.01 * scale
  np.testing.assert_allclose(
      grads[0]["head"],
      first[0]["head"] + MTP_LOSS_WEIGHT * second[0]["head"],
      atol=TOL * float(jnp.max(jnp.abs(want_grads[0]["head"]))))


def test_the_counters_of_every_expert_layer_come_out_with_the_model():
  cfg = dataclasses.replace(TOY, experts_held=(4, 8))
  rows, numerical, _ = _batch(cfg, 2)
  params = _share_of(_params(TOY, 2), 4, 8)
  out = GlmMoeLite(cfg, with_counters=True).apply(
      {"params": params}, numerical, None, emb_acts=[rows])
  moe = out["moe"]          # the trunk's two expert layers, then the module's
  assert moe["loads"].shape == (3, 8) and moe["assignments"].shape == (3,)
  assert np.array_equal(moe["assignments"], moe["computed"])
  assert np.array_equal(moe["assignments"], np.sum(moe["loads"], axis=1))
  slots = B * cfg.seq_len * cfg.num_experts_per_tok
  assert moe["moved"].shape == (3,)
  assert 0 < int(moe["moved"].min()) and int(moe["moved"].max()) < slots // 2
  still = {n: jnp.zeros_like(w) if n.endswith("expert_bias") else w
           for n, w in params.items()}
  out = GlmMoeLite(cfg, with_counters=True).apply(
      {"params": still}, numerical, None, emb_acts=[rows])
  assert not np.asarray(out["moe"]["moved"]).any()


@pytest.mark.parametrize("which", ["a_trunk_layer", "the_modules_layer"])
def test_the_eight_shares_and_what_every_chip_computes_once_add_up(which):
  """Eight chips hold two experts each; every one computes attention and the
  shared expert for its own tokens. An expert layer's output is ``x + attn +
  shared`` ONCE plus the eight shares' routed parts, and that is the uncut
  reference's layer; each share alone is the reference's share. The module's
  layer the same, on ``z`` (its two norms and ``W_eh`` computed once too)."""
  cfg = TOY
  rows, numerical, _ = _batch(cfg, 3)
  params, rcfg = _params(cfg, 3), _rcfg(cfg)
  seg = document_segments(numerical, cfg.mean_document_length)
  starts = ref.document_starts(rcfg, numerical)
  eps = cfg.rms_norm_eps
  x = jnp.asarray(np.random.default_rng(9).normal(size=rows.shape) * 0.5,
                  jnp.float32)             # a trunk's output, for the module
  with jax.default_matmul_precision("highest"):
    if which == "a_trunk_layer":
      p, inp = ref.leaves_of(params, "layer_1_"), rows
    else:
      mtp = ref.leaves_of(params, "mtp_")
      p = ref.leaves_of(mtp, "layer_")
      following = jnp.pad(rows[:, 1:], ((0, 0), (0, 1), (0, 0)))
      inp = jnp.concatenate([ref.rms(following, mtp["enorm"], eps),
                             ref.rms(x, mtp["hnorm"], eps)], -1) @ mtp["w_eh"]
    mixed = inp + ref.attention(rcfg, p, ref.rms(inp, p["input_norm"], eps),
                                starts)
    hf = ref.rms(mixed, p["post_attention_norm"], eps).reshape(
        -1, cfg.hidden_size)
    shared = ref.swiglu(hf, p["shared_gate"], p["shared_up"],
                        p["shared_down"]).reshape(mixed.shape)
    whole = mixed + ref.experts(rcfg, p, hf).reshape(mixed.shape)
    np.testing.assert_allclose(
        whole, ref.layer(rcfg, p, inp, starts, False), atol=1e-6)
    parts, assigned = [], 0
    for first in range(0, 16, 2):
      share_cfg = dataclasses.replace(cfg, experts_held=(first, 2))
      ps = _share_of(p, first, 2)
      if which == "a_trunk_layer":
        out, c = decoder_layer(share_cfg, EXPERTS, ps, rows, seg)
      else:
        module = {**{f"layer_{n}": w for n, w in ps.items()},
                  **{n: mtp[n] for n in ("enorm", "hnorm", "w_eh")}}
        out, c = mtp_module(share_cfg, module, x, rows, seg)
      routed = out - mixed - shared
      np.testing.assert_allclose(
          routed, ref.routed_experts(dict(rcfg, experts_held=(first, 2)), ps,
                                     hf).reshape(mixed.shape), atol=2e-5)
      y, c2 = moe_share(hf, ps["router"], ps["w_gate"], ps["w_up"],
                        ps["w_down"], share_cfg.share, ps["expert_bias"])
      assert int(c["assignments"]) == int(c2["assignments"])
      assigned += int(c["assignments"])
      parts.append(routed)
  assert assigned == hf.shape[0] * cfg.num_experts_per_tok
  scale = float(jnp.max(jnp.abs(whole)))
  np.testing.assert_allclose(mixed + shared + sum(parts), whole,
                             atol=1e-5 * scale)
  # attention or the shared expert counted eight times is another layer
  assert float(jnp.max(jnp.abs(mixed - inp))) > 0.02 * scale
  assert float(jnp.max(jnp.abs(shared))) > 0.02 * scale
  # the chosen weights sum to the routed scaling factor, four a token
  w = ref.router_weights(rcfg, hf, p["router"], p["expert_bias"])
  np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.8, rtol=1e-6)
  assert np.all(np.sum(np.asarray(w) > 0, axis=-1) == 4)


def test_packed_documents_give_what_the_documents_give_alone():
  """The whole model on a packed sequence against each document run alone
  (rotary positions are relative, so a document's place in the sequence
  moves nothing but rounding): both logit arrays, and the module's weights,
  which are 0 at a document's last TWO positions wherever it stands. At its
  last position the module reads the next document's first token (alone:
  zeros); that position has weight 0 in both losses and is left out here."""
  cfg = TOY
  rows, numerical, _ = _batch(cfg, 7, batch=1)
  params = _params(cfg, 7)
  starts = np.asarray(ref.document_starts(_rcfg(cfg), numerical))[0]
  edges = list(np.flatnonzero(starts)) + [cfg.seq_len]
  assert len(edges) >= 3
  with jax.default_matmul_precision("highest"):
    packed = GlmMoeLite(cfg).apply({"params": params}, numerical, None,
                                   emb_acts=[rows])
    scale = float(jnp.max(jnp.abs(packed["logits"])))
    for a, e in zip(edges[:-1], edges[1:]):
      alone = GlmMoeLite(dataclasses.replace(cfg, seq_len=int(e - a))).apply(
          {"params": params}, jnp.ones((1, e - a)), None,
          emb_acts=[rows[:, a:e]])
      np.testing.assert_allclose(packed["logits"][0, a:e],
                                 alone["logits"][0], atol=2e-4 * scale)
      np.testing.assert_allclose(packed["mtp_logits"][0, a:e - 1],
                                 alone["mtp_logits"][0, :-1],
                                 atol=2e-4 * scale)
      for name in ("weight", "mtp_weight"):
        assert np.array_equal(packed[name][0, a:e], alone[name][0]), name
      n = int(e - a)
      assert np.array_equal(alone["mtp_weight"][0],
                            [1.0] * max(n - 2, 0) + [0.0] * min(n, 2))
      assert np.array_equal(alone["weight"][0], [1.0] * (n - 1) + [0.0])


# ---- the sparse train step -------------------------------------------------
def _plan(cfg, batch):
  return DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)


def _ids_and_labels(cfg, seed, batch, vocab):
  rng = np.random.default_rng(seed)
  cats = jnp.asarray(rng.integers(0, vocab, (batch, cfg.seq_len)), jnp.int32)
  return cats, {"targets": jnp.pad(cats[:, 1:], ((0, 0), (0, 1))),
                "targets_2": jnp.pad(cats[:, 2:], ((0, 0), (0, 2)))}


def test_one_step_on_the_sparse_train_step_is_the_references():
  """Token table as ONE sequence input under summed Adam (the module shifts
  the rows itself: no second lookup), the dense leaves under SGD (so that a
  leaf's change IS its gradient): the step's loss, every dense gradient and
  the new token rows against the plain reference's, whose table gradient sums
  both uses of a row; the selection bias is left bit for bit."""
  cfg, batch, lr = TOY, 4, 0.05
  cats, labels = _ids_and_labels(cfg, 4, batch, 12)
  _, numerical, _ = _batch(cfg, 4, batch)
  plan = _plan(cfg, batch)
  assert len(plan.input_table_map) == 1
  model, dense = GlmMoeLite(cfg), _params(cfg, 4)
  rule, opt = adam_rule(lr, summed=True), optax.sgd(1.0)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1))
  (name, buf), = state["fused"].items()
  layout = DistributedLookup(plan).fused_layouts(rule)[name]
  table0 = layout.unpack(buf)[0][:cfg.vocab_size]
  with jax.default_matmul_precision("highest"):
    step = make_sparse_train_step(model, plan, mtp_training_loss, opt, rule,
                                  None, state, (numerical, [cats], labels),
                                  donate=False)
    after, loss = step(state, numerical, [cats], labels)
    want_loss, (g_dense, g_table) = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss(_rcfg(cfg), p, jnp.take(t, cats, axis=0),
                              numerical, labels["targets"],
                              labels["targets_2"]),
        argnums=(0, 1)))(dense, table0)
  assert float(loss) == pytest.approx(float(want_loss), rel=TOL)
  for leaf, g in g_dense.items():
    if leaf.endswith("expert_bias"):
      assert np.array_equal(after["dense"][leaf], dense[leaf]), leaf
      continue
    scale = float(jnp.max(jnp.abs(g)))
    # a latent norm's 12 gains each sum 96 positions: three times TOL
    np.testing.assert_allclose(dense[leaf] - after["dense"][leaf], g,
                               atol=3 * TOL * scale, err_msg=leaf)
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


def test_the_whole_thing_trains_and_a_guarded_step_reports_both_terms():
  """30 guarded steps on one batch through Adam on both sides (the table per
  occurrence: a summed rule has no guarded step): the loss and both of its
  terms fall, ``metrics['loss_terms']`` holds them beside their weighted sum,
  and the bias (zero gradient from zero moments: a zero step) is where it
  was."""
  cfg, batch = TOY, 4
  cats, labels = _ids_and_labels(cfg, 5, batch, cfg.vocab_size)
  _, numerical, _ = _batch(cfg, 5, batch)
  plan = _plan(cfg, batch)
  model = GlmMoeLite(cfg)
  dense = dict(model.init(jax.random.PRNGKey(0), numerical, None, emb_acts=[
      jnp.zeros((batch, cfg.seq_len, cfg.hidden_size))])["params"])
  bias = np.asarray(_params(cfg, 5)["mtp_layer_expert_bias"])
  dense["mtp_layer_expert_bias"] = jnp.asarray(bias)   # the step donates it
  rule, opt = adam_rule(3e-3), optax.adam(3e-3)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1))
  step = make_sparse_train_step(model, plan, mtp_training_loss, opt, rule,
                                None, state, (numerical, [cats], labels),
                                guard=True)
  losses, terms = [], []
  for _ in range(30):
    state, loss, metrics = step(state, numerical, [cats], labels)
    assert int(metrics["bad_step"]) == 0
    losses.append(float(loss))
    terms.append({k: float(v) for k, v in metrics["loss_terms"].items()})
  assert set(terms[0]) == {"next_token_loss", "mtp_loss"}
  for loss, t in zip(losses, terms):
    assert loss == pytest.approx(
        t["next_token_loss"] + MTP_LOSS_WEIGHT * t["mtp_loss"], rel=1e-6)
  log_v = np.log(cfg.vocab_size)
  for name in ("next_token_loss", "mtp_loss"):
    assert 0.7 * log_v < terms[0][name] < 1.5 * log_v
    assert terms[-1][name] < 0.7 * terms[0][name], name
  assert np.array_equal(state["dense"]["mtp_layer_expert_bias"], bias)
  assert not np.asarray(state["dense"]["layer_1_expert_bias"]).any()


def test_a_loss_of_one_term_reports_none():
  """The step builders' contract: a scalar loss gives the metrics they had."""
  from distributed_embeddings_tpu.layers.decoder import next_token_loss
  cfg, batch = TOY, 2
  cats, labels = _ids_and_labels(cfg, 6, batch, cfg.vocab_size)
  _, numerical, _ = _batch(cfg, 6, batch)
  plan = _plan(cfg, batch)
  rule, opt = adam_rule(3e-3), optax.adam(3e-3)
  state = init_sparse_state_direct(plan, rule, _params(cfg, 6), opt,
                                   jax.random.PRNGKey(1))
  step = make_sparse_train_step(GlmMoeLite(cfg), plan, next_token_loss, opt,
                                rule, None, state,
                                (numerical, [cats], labels), guard=True,
                                donate=False)
  _, _, metrics = step(state, numerical, [cats], labels)
  assert "loss_terms" not in metrics and "bad_step" in metrics


# ---- what the configuration refuses ----------------------------------------
def test_without_a_tpu_the_splash_path_raises():
  assert GlmMoeLiteConfig().attention == "splash"
  rows, numerical, _ = _batch(TOY, 1)
  params = _params(TOY, 1)
  with pytest.raises(ValueError, match="is a TPU kernel"):
    GlmMoeLite(dataclasses.replace(TOY, attention="splash")).apply(
        {"params": params}, numerical, None, emb_acts=[rows])
  with pytest.raises(ValueError, match="one sequence input"):
    GlmMoeLite(TOY).apply({"params": params}, numerical, None)
  with pytest.raises(ValueError, match="layers_here names layer 47 of 47"):
    dataclasses.replace(TOY, layers_here=(0, 47))
  with pytest.raises(ValueError, match="one prediction module"):
    dataclasses.replace(TOY, num_nextn_predict_layers=2)
