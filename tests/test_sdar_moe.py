"""SDAR-MoE (`models/sdar_moe.py`) against the plain reference
(`tests/reference_sdar_moe.py`) at toy widths on seeded weights, at
``highest`` matmul precision: logits, loss, every gradient leaf and the
gradient of the table's rows; and the whole model on the sparse train step, a
sequence input under summed Adam, whose loss falls. The attention proper and
its block-diffusion mask: `tests/test_attention.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_sdar_moe as ref
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models.sdar_moe import (
    SDARMoE,
    SDARMoEConfig,
    block_diffusion_loss,
    noise_of,
)
from distributed_embeddings_tpu.ops.packed_table import adam_rule
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

# the published 128 -> 8 routing at toy widths, every expert held
TOY = SDARMoEConfig(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    moe_intermediate_size=16, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=2, vocab_size=50, experts_held=(0, 128),
    block_length=4, seq_len=16, attention="xla")
B = 3


def _batch(cfg, seed=0):
  rng = np.random.default_rng(seed)
  rows = jnp.asarray(rng.normal(size=(B, cfg.seq_len, cfg.hidden_size)) * 0.5,
                     jnp.float32)
  noise = jnp.asarray(rng.random((B, cfg.n_numerical)), jnp.float32)
  targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, cfg.seq_len)),
                        jnp.int32)
  return rows, noise, targets


def _params(cfg, rows, noise, seed=0):
  rng = np.random.default_rng(seed)
  params = SDARMoE(cfg).init(jax.random.PRNGKey(seed), noise, None,
                             emb_acts=[rows])["params"]
  # matrices large enough that every path matters; gains off 1
  return jax.tree_util.tree_map(
      lambda x: x * 5 if x.ndim > 1 else x + 0.1 * jnp.asarray(
          rng.normal(size=x.shape), jnp.float32), params)


def test_the_noise_masks_by_block():
  noise = jnp.asarray(np.random.default_rng(2).random((64, 20)), jnp.float32)
  masked, weight = noise_of(noise, 16, 4, 0.1)
  t = 0.1 + 0.9 * np.repeat(np.asarray(noise[:, 16:]), 4, axis=1)
  assert np.array_equal(np.asarray(masked), np.asarray(noise[:, :16]) < t)
  np.testing.assert_allclose(weight, np.where(masked, 1 / t, 0), rtol=1e-6)
  assert 0.45 < float(jnp.mean(masked)) < 0.65     # E[t] = 0.55


@pytest.mark.parametrize("cfg", [
    TOY, dataclasses.replace(TOY, num_experts=8, num_experts_per_tok=2,
                             experts_held=(2, 4))],
    ids=["all_128_held", "a_share_of_8"])
def test_the_model_is_the_plain_reference(cfg):
  rows, noise, targets = _batch(cfg)
  params = _params(cfg, rows, noise)
  model, rcfg = SDARMoE(cfg), dataclasses.asdict(cfg)

  def ours(p, r):
    out = model.apply({"params": p}, noise, None, emb_acts=[r])
    return block_diffusion_loss(out, {"targets": targets}), out["logits"]

  with jax.default_matmul_precision("highest"):
    (loss, logits), grads = jax.jit(jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True))(params, rows)
    want_logits, _ = ref.forward(rcfg, params, rows, noise)
    want_loss, want_grads = jax.value_and_grad(
        lambda p, r: ref.loss(rcfg, p, r, noise, targets),
        argnums=(0, 1))(params, rows)
  np.testing.assert_allclose(logits, want_logits, atol=1e-5)
  assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
  assert set(grads[0]) == set(want_grads[0]) and len(grads[0]) == 27
  for name, want in want_grads[0].items():
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, name
    np.testing.assert_allclose(grads[0][name], want, atol=1e-5 * scale,
                               err_msg=name)
  # the table rows' gradient: what apply_sparse gets, one row an occurrence
  np.testing.assert_allclose(
      grads[1], want_grads[1], atol=1e-5 * float(jnp.max(jnp.abs(
          want_grads[1]))))


def test_counters_change_no_value():
  cfg = dataclasses.replace(TOY, num_experts=8, num_experts_per_tok=2,
                            experts_held=(0, 4))
  rows, noise, _ = _batch(cfg, 1)
  params = _params(cfg, rows, noise, 1)
  out = SDARMoE(cfg).apply({"params": params}, noise, None, emb_acts=[rows])
  plain = SDARMoE(cfg, with_counters=True).apply(
      {"params": params}, noise, None, emb_acts=[rows])
  assert np.array_equal(out["logits"], plain["logits"])
  assert set(out) == {"logits", "weight"}
  moe = plain["moe"]
  assert moe["loads"].shape == (2, 4) and moe["assignments"].shape == (2,)
  assert np.array_equal(moe["assignments"], moe["computed"])   # none dropped
  positions = 2 * cfg.seq_len * B
  assert 0 < int(moe["assignments"][0]) <= positions * 2
  with pytest.raises(ValueError, match="one sequence input"):
    SDARMoE(cfg).apply({"params": params}, noise, None)


def test_without_a_tpu_the_splash_path_raises():
  """The default names the TPU's kernel, and no other backend computes
  something else in its place."""
  assert SDARMoEConfig().attention == "splash"
  cfg = dataclasses.replace(TOY, attention="splash")
  rows, noise, _ = _batch(cfg, 1)
  params = _params(TOY, rows, noise, 1)
  with pytest.raises(ValueError, match="is a TPU kernel"):
    SDARMoE(cfg).apply({"params": params}, noise, None, emb_acts=[rows])
  with pytest.raises(ValueError, match="splash or xla"):
    SDARMoE(dataclasses.replace(TOY, attention="auto")).apply(
        {"params": params}, noise, None, emb_acts=[rows])


def test_the_whole_thing_trains_on_the_sparse_step():
  """Token table as a sequence input under summed Adam, the model, the
  block-diffusion loss: 30 steps on one batch, and the loss falls."""
  cfg = dataclasses.replace(TOY, num_experts=8, num_experts_per_tok=2,
                            experts_held=(0, 4))
  rng = np.random.default_rng(4)
  batch = 4
  cats = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len)),
                     jnp.int32)
  noise = jnp.asarray(rng.random((batch, cfg.n_numerical)), jnp.float32)
  labels = {"targets": cats}
  plan = DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)
  model = SDARMoE(cfg)
  dense = model.init(
      jax.random.PRNGKey(0), noise, None, emb_acts=[jnp.zeros(
          (batch, cfg.seq_len, cfg.hidden_size))])["params"]
  rule, opt = adam_rule(3e-3, summed=True), optax.adam(3e-3)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1))
  step = make_sparse_train_step(model, plan, block_diffusion_loss, opt, rule,
                                None, state, (noise, [cats], labels))
  losses = []
  for _ in range(30):
    state, loss = step(state, noise, [cats], labels)
    losses.append(float(loss))
  assert np.all(np.isfinite(losses))
  # at seeded weights every position costs about ln V, times its weight
  # (E[masked / t] = 1; 64 positions are few, so only roughly)
  assert 0.4 * np.log(cfg.vocab_size) < losses[0] < 2 * np.log(cfg.vocab_size)
  assert losses[-1] < 0.7 * losses[0]
