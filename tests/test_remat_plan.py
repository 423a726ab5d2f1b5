"""A decoder layer's rematerialisation plan (`layers/remat.py`): under
`checkpoint_layer` a model's gradients are those of the same layers with no
checkpoint at all; the splash kernel's output and log-sum-exp outlive the
layer, so its backward holds no second forward kernel; the expert layer's
head is computed twice, not three times, and its keys are sorted once; and
`tools/step_recompute.py` counts those calls in a compiled step's text."""

import dataclasses
import functools
import inspect
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from distributed_embeddings_tpu.layers import (
    attention,
    latent_attention,
    remat,
    short_conv,
    sparse_index,
)
from distributed_embeddings_tpu.layers.moe import MoEShare, Router, moe_share
from distributed_embeddings_tpu.models import (
    glm_moe_lite,
    keye_sparse,
    laguna,
    lfm2_moe,
    olmo_hybrid,
    sdar_moe,
    solar_open2,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import step_recompute  # noqa: E402

ROPE = {"full_attention": {"rope_type": "default", "rope_theta": 1e4},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}}
# toy widths of each model, every kind of layer it has; `attention="xla"`
TOYS = {
    "sdar_moe": (sdar_moe, sdar_moe.SDARMoE, sdar_moe.SDARMoEConfig(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, moe_intermediate_size=16, num_experts=16,
        num_experts_per_tok=2, num_hidden_layers=2, vocab_size=50,
        experts_held=(4, 4), block_length=4, seq_len=16, attention="xla")),
    "laguna": (laguna, laguna.Laguna, laguna.LagunaConfig(
        hidden_size=32, intermediate_size=48, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=12,
        shared_expert_intermediate_size=12, num_experts=16,
        num_experts_per_tok=3, sliding_window=5, num_hidden_layers=3,
        layer_types=(laguna.FULL, laguna.SLIDING, laguna.FULL),
        mlp_layer_types=(laguna.DENSE, laguna.SPARSE, laguna.SPARSE),
        num_attention_heads_per_layer=(4, 6, 4),
        rope_parameters=laguna.freeze_rope_parameters(ROPE), vocab_size=50,
        experts_held=(4, 4), seq_len=24, mean_document_length=8,
        attention="xla")),
    "olmo_hybrid": (olmo_hybrid, olmo_hybrid.OlmoHybrid,
                    olmo_hybrid.OlmoHybridConfig(
                        hidden_size=32, intermediate_size=48,
                        num_attention_heads=4, head_dim=8,
                        linear_key_head_dim=6, linear_value_head_dim=10,
                        layer_types=(olmo_hybrid.LINEAR, olmo_hybrid.FULL),
                        vocab_size=50, heads_held=(0, 4), seq_len=24,
                        mean_document_length=6, chunk=8, attention="xla")),
    # published layers 1, 2, 3: conv + dense, attention + experts, conv + experts
    "lfm2_moe": (lfm2_moe, lfm2_moe.Lfm2Moe, lfm2_moe.Lfm2MoeConfig(
        hidden_size=32, intermediate_size=48, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, moe_intermediate_size=12,
        num_experts=16, num_experts_per_tok=4, layers_here=(1, 2, 3),
        vocab_size=50, experts_held=(4, 4), seq_len=24,
        mean_document_length=8, attention="xla")),
    # published layers 0, 1 (dense, experts) and the prediction module
    "glm_moe_lite": (glm_moe_lite, glm_moe_lite.GlmMoeLite,
                     glm_moe_lite.GlmMoeLiteConfig(
                         hidden_size=32, intermediate_size=48,
                         moe_intermediate_size=12, num_attention_heads=4,
                         q_lora_rank=10, kv_lora_rank=8, qk_nope_head_dim=6,
                         qk_rope_head_dim=4, v_head_dim=14,
                         n_routed_experts=16, num_experts_per_tok=4,
                         layers_here=(0, 1), vocab_size=50,
                         experts_held=(4, 4), seq_len=24,
                         mean_document_length=8, attention="xla")),
    # published layers 0, 1: gated attention, then the per-channel rule (two
    # chunks of 32, the second padded), both over experts
    "solar_open2": (solar_open2, solar_open2.SolarOpen2,
                    solar_open2.SolarOpen2Config(
                        hidden_size=32, moe_intermediate_size=12,
                        num_attention_heads=4, num_key_value_heads=2,
                        head_dim=8, linear_num_heads=4, linear_head_dim=8,
                        gqa_layers=(0, 4), n_routed_experts=16,
                        num_experts_per_tok=4, num_hidden_layers=8,
                        layers_here=(0, 1), vocab_size=50, heads_held=(0, 4),
                        experts_held=(4, 4), seq_len=40,
                        mean_document_length=10, chunk=32, attention="xla")),
}


def _layers(cfg):
  """How many decoder layers the toy runs (a prediction module is one)."""
  if hasattr(cfg, "layers_here"):
    return len(cfg.layers_here) + getattr(cfg, "num_nextn_predict_layers", 0)
  return len(getattr(cfg, "layer_types", ())) or cfg.num_hidden_layers


def _case(model_cls, cfg, batch=2, seed=0):
  """-> (params with every matrix large enough to matter, numerical, rows)."""
  rng = np.random.default_rng(seed)
  rows = jnp.asarray(rng.normal(size=(batch, cfg.seq_len, cfg.hidden_size))
                     * 0.5, jnp.float32)
  width = getattr(cfg, "n_numerical", cfg.seq_len)
  numerical = jnp.asarray(rng.random((batch, width)), jnp.float32)
  params = model_cls(cfg).init(jax.random.PRNGKey(seed), numerical, None,
                               emb_acts=[rows])["params"]
  params = jax.tree_util.tree_map(
      lambda x: x * 8 if x.ndim > 1 else x + 0.1 * jnp.asarray(
          rng.normal(size=x.shape), jnp.float32), params)
  return params, numerical, rows


def _loss(model_cls, cfg, numerical):
  def loss(params, rows):
    out = model_cls(cfg).apply({"params": params}, numerical, None,
                               emb_acts=[rows])
    total = jnp.sum(jnp.sin(out["logits"]))
    if "mtp_logits" in out:     # a prediction module's leaves are reached
      total = total + jnp.sum(jnp.sin(out["mtp_logits"]))
    return total
  return loss


def _count(jaxpr, wanted, skip=()):
  """Equations of ``jaxpr`` and of everything it calls for which
  ``wanted(eqn)``; the bodies of the primitives named in ``skip`` are not
  entered."""
  n = 0
  for eqn in jaxpr.eqns:
    n += bool(wanted(eqn))
    if eqn.primitive.name in skip:
      continue
    for sub in jax.core.jaxprs_in_params(eqn.params):
      n += _count(sub, wanted, skip)
  return n


def _primitive(name):
  return lambda eqn: eqn.primitive.name == name


# `jax.checkpoint`'s equation, as this JAX names its primitive
_checkpoint = _primitive("remat2")


def _residuals(capsys, f, *args, named=""):
  """The types of the residuals of ``f(*args)``, as `print_saved_residuals`
  prints them; with ``named``, of those it says were kept under that name (it
  says so only of a value named outside any `jit`)."""
  capsys.readouterr()
  print_saved_residuals(f, *args)
  return sorted(line.split(" ")[0]
                for line in capsys.readouterr().out.splitlines()
                if not named or f"named '{named}'" in line)


@pytest.mark.parametrize("name", sorted(TOYS))
def test_gradients_under_the_plan_are_those_with_no_checkpoint(
    name, monkeypatch):
  """Leaf by leaf. Rematerialisation repeats the forward's own operations on
  the forward's own operands, so the two MoE models agree to the bit (op by
  op, outside `jit`). A checkpointed layer is still compiled as one program,
  whose fusions order the recurrent layers' sums otherwise: Olmo-Hybrid's toy
  is compared in float64 (in float32 it amplifies rounding a thousandfold:
  `tests/test_olmo_hybrid.py`), to 1e-12 of a leaf's largest value. So is
  GLM's: under the plan its float32 gradients differ from the plain ones in
  the last bits (1e-6 of a leaf) and in float64 agree to 1e-12, which is what
  sums taken in another order read, not another formula; and Solar-Open2's,
  whose mixers are recurrent as Olmo-Hybrid's."""
  module, model_cls, cfg = TOYS[name]
  exact = name not in ("olmo_hybrid", "glm_moe_lite", "solar_open2")
  with jax.enable_x64(not exact):
    params, numerical, rows = _case(model_cls, cfg)
    if not exact:
      params, numerical, rows = jax.tree_util.tree_map(
          lambda x: x.astype(jnp.float64), (params, numerical, rows))
    loss = _loss(model_cls, cfg, numerical)
    got = jax.grad(loss, argnums=(0, 1))(params, rows)
    monkeypatch.setattr(module, "checkpoint_layer", lambda layer: layer)
    want = jax.grad(loss, argnums=(0, 1))(params, rows)
  flat_got = jax.tree_util.tree_leaves_with_path(got)
  flat_want = jax.tree_util.tree_leaves(want)
  assert len(flat_got) == len(flat_want) > 10
  for (path, g), w in zip(flat_got, flat_want):
    largest = float(np.max(np.abs(w)))
    # a selection bias enters the choice alone: its gradient is zero
    assert (largest > 0) != ("expert_bias" in jax.tree_util.keystr(path)), \
        jax.tree_util.keystr(path)
    if exact:
      assert np.array_equal(g, w), jax.tree_util.keystr(path)
    else:
      assert float(np.max(np.abs(np.asarray(g) - np.asarray(w)))) \
          <= 1e-12 * largest, jax.tree_util.keystr(path)


@pytest.mark.parametrize("name", sorted(TOYS))
def test_every_decoder_layer_runs_under_the_plan(name, monkeypatch):
  """One checkpoint a decoder layer, each with the plan's policy, and none
  beside them: with the helper taken out the backward holds no checkpoint at
  all (the toys' shares have no tail) but the one round the per-channel
  rule's pair products, one a KDA layer
  (`layers/gated_delta.py::_decayed_products`)."""
  module, model_cls, cfg = TOYS[name]
  params, numerical, rows = _case(model_cls, cfg)
  # a function object each: `make_jaxpr` remembers what it traced
  grad = lambda: jax.grad(_loss(model_cls, cfg, numerical))
  tops = [eqn for eqn in jax.make_jaxpr(grad())(params, rows).jaxpr.eqns
          if _checkpoint(eqn)]
  own = sum(kind == solar_open2.KDA for kind in cfg.kinds) \
      if name == "solar_open2" else 0
  # the layers' carry the plan's policy, the rule's own carries none
  planned = [eqn for eqn in tops if eqn.params["policy"] is not None]
  assert len(planned) == _layers(cfg) and len(tops) - len(planned) == own
  monkeypatch.setattr(module, "checkpoint_layer", lambda layer: layer)
  assert _count(jax.make_jaxpr(grad())(params, rows).jaxpr, _checkpoint) \
      == own


def _splash_layer(name):
  """One attention layer of model ``name`` through its own splash path in
  Pallas's interpreter, a projection before it (so that ``q``, ``k``, ``v``
  are rebuilt, not arguments) -> (loss(w, x), w, x, layers)."""
  rng = np.random.default_rng(0)
  # LFM2's head is 64, half a lane tile, GLM's 256 with no group; the
  # others' 128
  length, hkv, group = 128, 1, 2
  hd = {"lfm2_moe": 64, "glm_moe_lite": 256}.get(name, 128)
  x = jnp.asarray(rng.normal(size=(1, length, 32)), jnp.float32)
  w = jnp.asarray(rng.normal(size=(2, 32, (group + 2) * hkv * hd)) * 0.2,
                  jnp.float32)
  seg = jnp.asarray(np.arange(length)[None, :] >= 70, jnp.int32)

  def attend(q, k, v):
    grouped = q.reshape(1, length, hkv, group, hd)
    if name == "sdar_moe":     # [xt ; x0]: 2 L positions of L = 64
      return attention.attention_splash(
          grouped, k, v, attention.BlockDiffusion(4), None, 128,
          interpret=True)
    if name in ("laguna", "lfm2_moe", "solar_open2"):   # a window of 40, and none
      return attention.attention_splash(
          grouped, k, v, attention.Window(40) if name == "laguna"
          else attention.Causal(), seg, 128, interpret=True)
    kv = lambda t: jnp.repeat(t, group, axis=2)   # heads with no group
    return attention.attention_splash(q, kv(k), kv(v), attention.Causal(),
                                      seg, 128, interpret=True)

  def layer(wl, x):
    qkv = (x @ wl).reshape(1, length, (group + 2) * hkv, hd)
    o = attend(qkv[:, :, :group * hkv] * hd ** -0.5,
               qkv[:, :, group * hkv:(group + 1) * hkv],
               qkv[:, :, (group + 1) * hkv:])
    return x + jnp.tanh(o.reshape(1, length, -1))[..., :32]

  def loss(wrap, w, x):
    for wl in w:
      x = wrap(layer)(wl, x)
    return jnp.sum(jnp.sin(x))

  return loss, w, x, len(w)


def _forward_kernel(eqn):
  return eqn.primitive.name == "pallas_call" and "fwd" in str(
      eqn.params.get("name", eqn.params.get("name_and_src_info", "")))


@pytest.mark.parametrize("name", sorted(TOYS))
def test_the_splash_output_outlives_its_layer(name, capsys):
  """Under the plan the backward holds ONE forward kernel a layer (the
  forward pass's own), the two named values are among the layer's residuals,
  and the gradients are those of the layers with no checkpoint."""
  loss, w, x, layers = _splash_layer(name)
  plan = functools.partial(loss, remat.checkpoint_layer)
  bare = functools.partial(loss, jax.checkpoint)
  plain = functools.partial(loss, lambda layer: layer)
  forward_kernels = lambda f: _count(
      jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(w, x).jaxpr,
      _forward_kernel)
  assert forward_kernels(plan) == layers
  assert forward_kernels(bare) == 2 * layers      # what the models ran before
  # a layer's `out` in bfloat16 and its log-sum-exp (a float32 a query and
  # head) are kept by the plan and by no bare checkpoint
  for wrap, times in [(plan, layers), (bare, 0)]:
    kept = _residuals(capsys, wrap, w, x)
    assert sum(k.startswith("bf16[") for k in kept) == times
    assert sum(k.endswith(",2,128]") and k.startswith("f32[")
               for k in kept) == times
  got = jax.jit(jax.grad(plan, argnums=(0, 1)))(w, x)
  want = jax.jit(jax.grad(plain, argnums=(0, 1)))(w, x)
  for g, t in zip(got, want):
    np.testing.assert_allclose(g, t, atol=1e-5 * float(jnp.max(jnp.abs(t))))
    assert float(jnp.max(jnp.abs(t))) > 0


def test_a_convolution_layer_rebuilds_its_mixers_smaller_product(capsys):
  """A layer of a short convolution and a dense MLP (`models/lfm2_moe.py`):
  five products forward and ten backward on any plan. The plan keeps
  ``h W_in`` (`remat.SHORT_CONV_IN`), so the rebuilt forward holds ``W_out``
  (whose output the MLP reads) and the MLP's gate and up (``W_down``'s
  output is needed by nothing); a bare checkpoint runs ``W_in`` again."""
  _, _, cfg = TOYS["lfm2_moe"]
  rng = np.random.default_rng(0)
  p = {n: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
       for n, (shape, _) in lfm2_moe.layer_shapes(
           cfg, lfm2_moe.CONV, lfm2_moe.DENSE).items()}
  x = jnp.asarray(rng.normal(size=(2, cfg.seq_len, cfg.hidden_size)),
                  jnp.float32)
  seg = jnp.asarray(np.arange(cfg.seq_len)[None, :] >= 9, jnp.int32) \
      * jnp.ones((2, 1), jnp.int32)
  layer = lambda p, x: lfm2_moe.decoder_layer(
      cfg, lfm2_moe.CONV, lfm2_moe.DENSE, p, x, seg)[0]
  loss = lambda wrap: lambda p, x: jnp.sum(jnp.sin(wrap(layer)(p, x)))
  products = lambda wrap: _count(jax.make_jaxpr(jax.grad(
      loss(wrap), argnums=(0, 1)))(p, x).jaxpr, _primitive("dot_general"))
  assert products(lambda f: f) == 5 + 10
  assert products(remat.checkpoint_layer) == 5 + 3 + 10
  assert products(jax.checkpoint) == 5 + 4 + 10
  kept = _residuals(capsys, loss(remat.checkpoint_layer), p, x)
  assert kept.count(f"f32[2,{cfg.seq_len},{3 * cfg.hidden_size}]") == 1
  assert f"f32[2,{cfg.seq_len},{3 * cfg.hidden_size}]" not in _residuals(
      capsys, loss(jax.checkpoint), p, x)
  got = jax.grad(loss(remat.checkpoint_layer), argnums=(0, 1))(p, x)
  want = jax.grad(loss(lambda f: f), argnums=(0, 1))(p, x)
  for g, w in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    assert np.array_equal(g, w)


def test_a_latent_layer_keeps_its_latents_and_rebuilds_the_rest(capsys):
  """A layer of latent attention and a dense MLP (`models/glm_moe_lite.py`).
  The plan keeps what the two down products made (`remat.MLA_LATENTS`:
  ``h W_dq`` and ``h W_dkv`` before their norms), so the rebuilt forward
  runs two products fewer than a bare checkpoint's, which runs both again;
  the latent norms, the up products and the rotary pass are rebuilt either
  way."""
  _, _, cfg = TOYS["glm_moe_lite"]
  rng = np.random.default_rng(0)
  p = {n: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
       for n, (shape, _) in glm_moe_lite.layer_shapes(
           cfg, glm_moe_lite.DENSE).items()}
  x = jnp.asarray(rng.normal(size=(2, cfg.seq_len, cfg.hidden_size)),
                  jnp.float32)
  seg = jnp.asarray(np.arange(cfg.seq_len)[None, :] >= 9, jnp.int32) \
      * jnp.ones((2, 1), jnp.int32)
  layer = lambda p, x: glm_moe_lite.decoder_layer(
      cfg, glm_moe_lite.DENSE, p, x, seg)[0]
  loss = lambda wrap: lambda p, x: jnp.sum(jnp.sin(wrap(layer)(p, x)))
  products = lambda wrap: _count(jax.make_jaxpr(jax.grad(
      loss(wrap), argnums=(0, 1)))(p, x).jaxpr, _primitive("dot_general"))
  assert products(jax.checkpoint) - products(remat.checkpoint_layer) == 2
  assert products(remat.checkpoint_layer) > products(lambda f: f)
  latents = [f"f32[2,{cfg.seq_len},{cfg.q_lora_rank}]",
             f"f32[2,{cfg.seq_len},{cfg.kv_lora_rank + cfg.qk_rope_head_dim}]"]
  kept = _residuals(capsys, loss(remat.checkpoint_layer), p, x)
  bare = _residuals(capsys, loss(jax.checkpoint), p, x)
  for latent in latents:
    assert kept.count(latent) == 1 and latent not in bare
  got = jax.grad(loss(remat.checkpoint_layer), argnums=(0, 1))(p, x)
  want = jax.grad(loss(lambda f: f), argnums=(0, 1))(p, x)
  # not to the bit (the test above holds the whole model in float64)
  for g, w in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.max(jnp.abs(w))))


def test_a_kda_layer_keeps_its_chains_latents_and_rebuilds_the_rest(capsys):
  """A layer of the per-channel delta rule over experts
  (`models/solar_open2.py`). The plan keeps what the first halves of the two
  low-rank chains made (`remat.KDA_LATENTS`: ``u W_fa`` and ``u W_ga``), so
  the rebuilt forward runs two products fewer than a bare checkpoint's,
  which runs both again; the three wide projections, the convolutions, the
  chains' second halves and the rule are rebuilt either way, and the rule's
  scan keeps its per-chunk states for its own backward."""
  _, _, cfg = TOYS["solar_open2"]
  rng = np.random.default_rng(0)
  p = {n: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
       for n, (shape, _) in solar_open2.layer_shapes(
           cfg, solar_open2.KDA).items()}
  x = jnp.asarray(rng.normal(size=(2, cfg.seq_len, cfg.hidden_size)),
                  jnp.float32)
  seg = jnp.asarray(np.arange(cfg.seq_len)[None, :] >= 9, jnp.int32) \
      * jnp.ones((2, 1), jnp.int32)
  layer = lambda p, x: solar_open2.decoder_layer(
      cfg, solar_open2.KDA, p, x, seg)[0]
  loss = lambda wrap: lambda p, x: jnp.sum(jnp.sin(wrap(layer)(p, x)))
  products = lambda wrap: _count(jax.make_jaxpr(jax.grad(
      loss(wrap), argnums=(0, 1)))(p, x).jaxpr, _primitive("dot_general"))
  assert products(jax.checkpoint) - products(remat.checkpoint_layer) == 2
  assert products(remat.checkpoint_layer) > products(lambda f: f)
  latent = f"f32[2,{cfg.seq_len},{cfg.linear_head_dim}]"
  kept = _residuals(capsys, loss(remat.checkpoint_layer), p, x)
  bare = _residuals(capsys, loss(jax.checkpoint), p, x)
  assert kept.count(latent) == 2 and latent not in bare
  # no wide projection outlives the layer: [2, L, heads x 8] is the layer's
  # input and nothing else
  wide = f"f32[2,{cfg.seq_len},{cfg.heads_held[1] * cfg.linear_head_dim}]"
  assert kept.count(wide) == bare.count(wide)
  got = jax.grad(loss(remat.checkpoint_layer), argnums=(0, 1))(p, x)
  want = jax.grad(loss(lambda f: f), argnums=(0, 1))(p, x)
  # not to the bit (the test above holds the whole model in float64)
  for g, w in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.max(jnp.abs(w))))


def _moe_weights(seed):
  """`tests/test_moe.py`'s weights on the same seeds."""
  t, d, f, e = 64, 16, 24, 32
  rng = np.random.default_rng(seed)
  f32 = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s,
                                          jnp.float32)
  return (f32(t, d), f32(d, e), f32(e, d, f, s=0.3), f32(e, d, f, s=0.3),
          f32(e, f, d, s=0.3))


@pytest.mark.parametrize("router", [Router(), Router("sigmoid", True, 2.5)],
                         ids=["softmax", "sigmoid"])
def test_the_expert_head_is_computed_twice_and_sorted_once(router, capsys):
  """Under the plan the backward of one expert layer holds 12 grouped
  matmuls outside the tail's `cond` (3 forward, 3 rematerialised, 6
  backward) and one sort; under a bare checkpoint the keys are sorted twice.
  Called with no checkpoint round it the head keeps its residuals: 9 (12
  while the head rematerialised itself)."""
  h, wr, wg, wu, wd = _moe_weights(2)
  share = MoEShare(32, 2, (2, 4), router)
  assert share.head_rows(64 * 2) < 64 * 2          # a tail exists, as in a cell
  args = (h, wr, wg[2:6], wu[2:6], wd[2:6])
  layer = lambda *a: moe_share(*a, share)[0]
  count = lambda wrap, what: _count(jax.make_jaxpr(jax.grad(
      lambda a: jnp.sum(jnp.sin(wrap(layer)(*a)))))(args).jaxpr,
                                    _primitive(what), skip=("cond",))
  assert count(remat.checkpoint_layer, "ragged_dot_general") == 12
  assert count(remat.checkpoint_layer, "sort") == 1
  assert count(jax.checkpoint, "ragged_dot_general") == 12
  assert count(jax.checkpoint, "sort") == 2
  assert count(lambda f: f, "ragged_dot_general") == 9
  assert _residuals(capsys, lambda a: jnp.sum(
      remat.checkpoint_layer(layer)(*a)), args, named=remat.MOE_ROUTE) \
      == ["i32[128]", "i32[4]"]                        # order, loads
  with jax.default_matmul_precision("highest"):
    got = jax.grad(lambda a: jnp.sum(jnp.sin(
        remat.checkpoint_layer(layer)(*a))))(args)
    want = jax.grad(lambda a: jnp.sum(jnp.sin(layer(*a))))(args)
  for g, w in zip(got, want):
    assert np.array_equal(g, w)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_counters_are_what_they_were(seed):
  """`assignments`, `loads`, `computed` on the seeds `tests/test_moe.py`
  uses: the named order and loads are the values they name, and the counters
  come out of a rematerialised layer as out of a plain one."""
  h, wr, wg, wu, wd = _moe_weights(seed)
  top_e = np.asarray(jax.lax.top_k(jax.nn.softmax(jnp.dot(
      h, wr, precision="highest"), axis=-1), 2)[1])
  for first, held in [(0, 32), (2, 4), (24, 8), (3, 1)]:
    share = MoEShare(32, 2, (first, held))
    sl = slice(first, first + held)
    run = lambda wrap: wrap(lambda *a: moe_share(*a, share))(
        h, wr, wg[sl], wu[sl], wd[sl])
    (out, c), (planned, pc) = run(lambda f: f), run(remat.checkpoint_layer)
    loads = np.bincount(top_e.reshape(-1), minlength=32)[sl]
    assert np.array_equal(c["loads"], loads)
    assert int(c["assignments"]) == int(c["computed"]) == int(loads.sum())
    for key in ("assignments", "loads", "computed"):
      assert np.array_equal(c[key], pc[key])
    assert np.array_equal(out, planned)


def _toy_step(wrap):
  """A hand-built step of two expert layers -> its compiled HLO's text."""
  h, wr, wg, wu, wd = _moe_weights(0)
  share = MoEShare(32, 2, (0, 32))
  layer = lambda h, *w: h + moe_share(h, *w, share)[0]

  def loss(w, h):
    for _ in range(2):
      h = wrap(layer)(h, *w)
    return jnp.sum(jnp.sin(h))

  return jax.jit(jax.grad(loss)).lower((wr, wg, wu, wd), h)


def test_the_tool_counts_a_steps_calls():
  """`tools/step_recompute.py::count_ops` on a hand-written module in the
  compiled text's form (kernel names as the TPU compiler gives them; the
  route's sorts with and without the part's scope of PR 38 between; plain
  products by the type of their operands), and on a toy step's sorts as this
  backend compiles them."""
  text = """HloModule jit_step

%compare.1 (a: s32[], b: s32[]) -> pred[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

%branch_walk.3 (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0} parameter(0)
  %ragged-dot-metadata.9 = (s32[17]{0}, s32[1]{0}) custom-call(%p), custom_call_target="tpu_custom_call"
  %de_grouped_dot.9 = f32[8,4]{1,0:T(8,128)} custom-call(%p, %p), custom_call_target="tpu_custom_call"
  ROOT %ragged-dot-none.7 = f32[8,4]{1,0:T(8,128)} custom-call(%p, %p), custom_call_target="tpu_custom_call"
}

%branch_rest.4 (p.1: f32[8,4]) -> f32[8,4] {
  ROOT %p.1 = f32[8,4]{1,0} parameter(0)
}

%fused_dense.6 (p.2: f32[8,4], p.3: f32[4,4]) -> f32[8,4] {
  %p.2 = f32[8,4]{1,0} parameter(0)
  %p.3 = f32[4,4]{1,0} parameter(1)
  %fusion.9 = bf16[8,4]{1,0:T(8,128)(2,1)} fusion(%p.2), kind=kLoop, calls=%branch_rest.4
  %fusion.10 = bf16[4,4]{1,0:T(8,128)(2,1)} fusion(%p.3), kind=kLoop, calls=%branch_rest.4
  %convolution.7 = f32[8,4]{1,0:T(8,128)} convolution(%fusion.9, %fusion.10), dim_labels=bf_io->bf
  %convolution.8 = f32[8,4]{1,0:T(8,128)} convolution(%p.2, %p.3), dim_labels=bf_io->bf, operand_precision={highest,highest}
  %dot.3 = f32[8,4]{1,0} dot(f32[8,4]{1,0:T(8,128)} %p.2, bf16[4,4]{1,0:T(8,128)(2,1)} %fusion.10), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %dot.5 = s32[8,4]{1,0} dot(s8[8,4]{1,0} %p.2, s8[4,4]{1,0} %p.3), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %dot.4 = f32[8,4]{1,0} dot(bf16[8,4]{1,0:T(8,128)(2,1)} %fusion.9, bf16[4,4]{1,0} %fusion.10), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main.5 (x: f32[8,4], k: s32[8]) -> f32[8,4] {
  %x = f32[8,4]{1,0} parameter(0)
  %k = s32[8]{0} parameter(1)
  %iota.1 = s32[8]{0} iota(), iota_dimension=0
  %sort.31 = (s32[8]{0:T(1024)}, s32[8]{0}) sort(%k, %iota.1), dimensions={0}, is_stable=true, to_apply=%compare.1, metadata={op_name="jit(step)/jvp(de_model)/M/de_moe/de_moe_route/jit(argsort)/sort"}
  %sort.30 = (f32[8,4]{0,1}, s32[8,4]{0,1}) sort(%x, %x), dimensions={1}, to_apply=%compare.1, metadata={op_name="jit(step)/jvp(de_model)/M/de_moe/de_moe_route/top_k"}
  %sort.2 = (s32[8]{0}, s32[8]{0}) sort(%k, %iota.1), dimensions={0}, to_apply=%compare.1, metadata={op_name="jit(step)/de_apply/sort"}
  %sort.33 = (s32[8]{0:T(1024)}, s32[8]{0}) sort(%k, %iota.1), dimensions={0}, is_stable=true, to_apply=%compare.1, metadata={op_name="jit(step)/jvp(de_model)/M/de_moe/de_moe_route/de_moe_sort/jit(argsort)/sort"}
  %sort.34 = (f32[8,4]{0,1}, s32[8,4]{0,1}) sort(%x, %x), dimensions={1}, to_apply=%compare.1, metadata={op_name="jit(step)/jvp(de_model)/M/de_moe/de_moe_route/de_moe_router/top_k"}
  %splash_mqa_fwd_segmented_residuals.15 = (bf16[8,4]{1,0}, f32[8]{0}) custom-call(%x), custom_call_target="tpu_custom_call"
  %splash_mqa_dkv_segmented_no_residuals.10 = bf16[8,4]{1,0} custom-call(%x), custom_call_target="tpu_custom_call"
  %splash_mha_fwd_segmented_residuals.2 = (bf16[8,4]{1,0}, f32[8]{0}) custom-call(%x), custom_call_target="tpu_custom_call"
  %de_sparse_attn_fwd.3 = (f32[8,4]{1,0}, f32[1,8,128]{2,1,0}) custom-call(%k, %k, %x), custom_call_target="tpu_custom_call"
  %de_sparse_attn_mean.4 = f32[8,8]{1,0} custom-call(%k, %k, %x), custom_call_target="tpu_custom_call"
  %de_sparse_attn_mean.5 = f32[8,8]{1,0} custom-call(%k, %k, %x), custom_call_target="tpu_custom_call"
  %de_sparse_attn_dkv.6 = (f32[8,4]{1,0}, f32[8,4]{1,0}) custom-call(%k, %k, %x), custom_call_target="tpu_custom_call"
  %de_moe_combine.1 = f32[1,1,8,128]{3,2,1,0} custom-call(%k, %x, %x), custom_call_target="tpu_custom_call"
  %de_moe_combine.2 = f32[1,1,8,128]{3,2,1,0} custom-call(%k, %x, %x), custom_call_target="tpu_custom_call"
  %ragged-dot-metadata.5 = (s32[17]{0}, s32[1]{0}) custom-call(%k), custom_call_target="tpu_custom_call"
  %ragged-dot-none.12 = f32[8,4]{1,0:T(8,128)} custom-call(%x, %x), custom_call_target="tpu_custom_call"
  %x16 = bf16[8,4]{1,0:T(8,128)(2,1)} convert(%x)
  %ragged-dot-none.13 = f32[8,4]{1,0:T(8,128)} custom-call(%k, %ragged-dot-metadata.5, %k, /*index=3*/%x16, %x16), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[8]{0}, s32[17]{0}, s32[8]{0}, bf16[8,4]{1,0}, bf16[8,4]{1,0}}
  %ragged-dot-none.14 = f32[8,4]{1,0:T(8,128)} custom-call(%k, %x16, %x), custom_call_target="tpu_custom_call"
  %de_grouped_dot.1 = f32[8,4]{1,0:T(8,128)} custom-call(%k, %k, %k, %k, %x16, %x16), custom_call_target="tpu_custom_call"
  %de_grouped_dot = f32[8,4]{1,0:T(8,128)} custom-call(%k, %k, %k, %k, %x16, %x16), custom_call_target="tpu_custom_call"
  %de_grouped_dw.3 = f32[2,4,4]{2,1,0:T(8,128)} custom-call(%k, %k, %k, %k, %x16, %x16), custom_call_target="tpu_custom_call"
  %pred = pred[] constant(true)
  ROOT %conditional.1 = f32[8,4]{1,0} conditional(%pred, %ragged-dot-none.12, %x), true_computation=%branch_walk.3, false_computation=%branch_rest.4
}
"""
  assert step_recompute.count_ops(text) == {
      "splash_fwd": 2, "ragged_dot": 3, "ragged_dot_tail": 1, "sort": 5,
      # those of each whose two matrices, the kernel's last operands, are
      # bfloat16: one of the head's three (one float32, one mixed), not the
      # tail's
      "ragged_dot_bf16": 1, "ragged_dot_tail_bf16": 0,
      "route_sort": 2, "route_top_k": 2,
      # plain products by what they are handed, operands by name or with
      # their types beside them: two of bfloat16 alone, two with a float32
      # operand (one of them mixed), an integer one in neither
      "dense_dot_f32": 2, "dense_dot_bf16": 2,
      # the kernels of ops/pallas_sparse_attn.py, each by its name
      "sparse_attn_fwd": 1, "sparse_attn_mean": 2, "sparse_attn_dq": 0,
      "sparse_attn_dkv": 1,
      # the expert layer's combine (ops/pallas_moe_combine.py)
      "moe_combine": 2,
      # the grouped-matmul kernels (ops/pallas_grouped_matmul.py), the tail's apart
      "grouped_dot": 2, "grouped_dot_tail": 1, "grouped_dw": 1,
      "grouped_dw_tail": 0}
  # a toy step as this backend compiles it: the route's argsort once a layer
  # under the plan, twice under a bare checkpoint
  counts = {name: step_recompute.count_ops(_toy_step(wrap).compile().as_text())
            for name, wrap in [("plan", remat.checkpoint_layer),
                               ("bare", jax.checkpoint)]}
  assert counts["plan"]["route_sort"] == 2
  assert counts["bare"]["route_sort"] == 4
  assert counts["plan"]["splash_fwd"] == counts["plan"]["ragged_dot_tail"] == 0
  assert counts["plan"]["moe_combine"] == 0   # this backend runs XLA's form


def test_program_sha_sees_the_program_and_not_where_it_came_from():
  """`program_sha`: names of scopes, files and lines change nothing (the
  instruction's `metadata`, its `frontend_attributes`, the module's tables of
  locations, the locations inside a kernel's body); an instruction does."""
  text = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/somewhere/models/sdar_moe.py"

FunctionNames
1 "decoder_layer"

FileLocations
1 {file_name_id=1 function_name_id=1 line=255 end_line=255 column=4 end_column=20}

StackFrames
1 {file_location_id=1 parent_frame_id=1}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %neg.1 = f32[8]{0} negate(%x), metadata={op_name="jit(step)/de_model/de_attention/neg" source_file="/somewhere/models/sdar_moe.py" source_line=255 stack_frame_id=1}
  ROOT %sin.2 = f32[8]{0} sine(%neg.1), frontend_attributes={_scope="{a}"}, metadata={op_name="jit(step)/de_model/de_attention/sin"}
}
"""
  moved = text.replace("de_attention/", "de_attention/de_attn_qk/") \
      .replace("=255", "=263").replace("/somewhere/", "/elsewhere/") \
      .replace('_scope="{a}"', '_scope="{b}"')
  assert moved != text
  assert step_recompute.program_sha(moved) == step_recompute.program_sha(text)
  bare = step_recompute.without_provenance(text)
  assert "metadata" not in bare and "sdar_moe.py" not in bare \
      and "frontend_attributes" not in bare and "negate(%x)" in bare
  assert step_recompute.program_sha(text.replace("negate(", "abs(")) \
      != step_recompute.program_sha(text)
  # a Pallas kernel's body carries the line of every call on the way to it
  import base64

  from jax._src.interpreters import mlir
  from jax._src.lib.mlir import ir

  def custom_call(line):
    with mlir.make_ir_context() as ctx, ir.Location.file("model.py", line, 1):
      ctx.allow_unregistered_dialects = True
      module = ir.Module.create()
      with ir.InsertionPoint(module.body):
        ir.Operation.create("kernel.body", attributes={
            "block": ir.IntegerAttr.get(ir.IntegerType.get_signless(32), 512)})
      body = base64.b64encode(module.operation.get_asm(
          enable_debug_info=True).encode()).decode()
    return text.replace(
        "negate(%x)", 'custom-call(%x), custom_call_target="tpu_custom_call", '
        'backend_config={"custom_call_config":{"body":"' + body + '"}}')
  assert custom_call(255) != custom_call(263)
  assert step_recompute.program_sha(custom_call(255)) \
      == step_recompute.program_sha(custom_call(263)) \
      != step_recompute.program_sha(text)


def test_kept_is_what_the_code_names():
  """`KEPT` lists the names the kernels' builders and the expert layer give,
  and nothing else: no configuration, option or environment says what is
  kept."""
  assert set(remat.KEPT) == {remat.SPLASH_RESIDUALS, remat.MOE_ROUTE,
                             remat.SPARSE_SELECTION,
                             remat.SPARSE_ATTN_RESIDUALS,
                             remat.SHORT_CONV_IN, remat.MLA_LATENTS,
                             remat.KDA_LATENTS}
  # a short convolution's first product is named where it is made
  assert inspect.getsource(short_conv).count(", SHORT_CONV_IN)") == 1
  # a latent-attention mixer's two down products likewise
  assert inspect.getsource(latent_attention).count(", MLA_LATENTS)") == 2
  # a KDA mixer's two low-rank chains pass one line that names their latent
  assert inspect.getsource(solar_open2).count(", KDA_LATENTS)") == 1
  # the two a learned indexer's attention names are made in one place
  source = inspect.getsource(sparse_index)
  assert source.count("SPARSE_SELECTION)") == 1
  assert source.count("SPARSE_ATTN_RESIDUALS)") == 2
  # (description, positions, heads, grouped): the one builder's kernels
  for args in [(attention.BlockDiffusion(4), 32, 2, True),
               (attention.Causal(), 128, 2, True),
               (attention.Window(40), 128, 2, True),
               (attention.Causal(), 128, 2, False)]:
    kernel = attention._splash_kernel(*args, 128, True)
    assert kernel.kwargs["residual_checkpoint_name"] == remat.SPLASH_RESIDUALS
    # its block maps are host arrays: constants of whatever program calls it
    leaves = jax.tree_util.tree_leaves(kernel)
    assert leaves and all(type(leaf) is np.ndarray for leaf in leaves)
  assert not [f.name for cfg in (sdar_moe.SDARMoEConfig, laguna.LagunaConfig,
                                 olmo_hybrid.OlmoHybridConfig,
                                 keye_sparse.KeyeSparseConfig,
                                 lfm2_moe.Lfm2MoeConfig,
                                 glm_moe_lite.GlmMoeLiteConfig,
                                 solar_open2.SolarOpen2Config)
              for f in dataclasses.fields(cfg)
              if "remat" in f.name or "checkpoint" in f.name]
