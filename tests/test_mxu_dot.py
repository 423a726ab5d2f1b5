"""`layers/dense.py`: the language models' plain dense product.

With the operand type passed as bfloat16 (on this backend the policy keeps
float32, so the test passes it), the forward, `dx` and `dw` are the float32
products of the operands rounded to bfloat16, and all three are float32. With
the policy's own answer here the function is `x @ w`, bit for bit, gradients
too. Over the three models' toys with the operand type forced to bfloat16:
every `dot_general` that takes a weight has bfloat16 operands and a float32
result, the router's and the delta rule's keep float32 operands at `highest`,
and no gradient left float32."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import test_laguna
import test_olmo_hybrid
import test_sdar_moe
from distributed_embeddings_tpu.layers import dense
from distributed_embeddings_tpu.models.laguna import Laguna
from distributed_embeddings_tpu.models.olmo_hybrid import (
    OlmoHybrid,
    next_token_loss,
)
from distributed_embeddings_tpu.models.sdar_moe import (
    SDARMoE,
    block_diffusion_loss,
)

BF16, F32 = jnp.bfloat16, jnp.float32
HIGHEST = lax.Precision.HIGHEST


_leads = pytest.mark.parametrize(
    "lead", [(), (7,), (2, 5)],
    ids=["a_vector", "rows", "a_batch_of_sequences"])


def _case(lead, k=24, n=40, seed=0):
  rng = np.random.default_rng(seed)
  draw = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)
  return draw(*lead, k), draw(k, n), draw(*lead, n)


@_leads
def test_rounded_operands_float32_products_and_results(lead):
  x, w, dy = _case(lead)
  y, vjp = jax.vjp(lambda x, w: dense.dot_rounded(BF16, x, w), x, w)
  dx, dw = vjp(dy)
  # what the MXU's single pass forms: each operand rounded to nearest even,
  # the products exact in float32, float32 sums
  wide = lambda a: a.astype(BF16).astype(F32)
  rows = lambda a: a.reshape(-1, a.shape[-1])
  exact = lambda a, b: jnp.dot(a, b, precision=HIGHEST)
  want = {"y": exact(wide(x), wide(w)),
          "dx": exact(wide(dy), wide(w).T),
          "dw": exact(rows(wide(x)).T, rows(wide(dy)))}
  for name, got in {"y": y, "dx": dx, "dw": dw}.items():
    assert got.dtype == F32, name
    assert got.shape == want[name].shape, name
    # float32 accumulation error over at most 10 x 24 terms of size ~1
    np.testing.assert_allclose(got, want[name], rtol=0, atol=2e-5,
                               err_msg=name)
  # and it is a rounding: the float32 product stands a bfloat16 ulp away
  assert float(jnp.max(jnp.abs(y - exact(x, w)))) > 1e-3


@_leads
def test_on_this_backend_it_is_the_plain_product_bit_for_bit(lead):
  assert dense.mxu_operand_dtype(F32) == F32   # the CPU keeps the dtype
  x, w, dy = _case(lead, seed=1)
  got = jax.vjp(dense.mxu_dot, x, w)
  want = jax.vjp(jnp.dot, x, w)
  np.testing.assert_array_equal(got[0], want[0])
  for g, h in zip(got[1](dy), want[1](dy)):
    np.testing.assert_array_equal(g, h)
  text = str(jax.make_jaxpr(jax.grad(
      lambda x, w: jnp.sum(dense.mxu_dot(x, w)), (0, 1)))(x, w))
  assert "bf16" not in text and "custom_vjp" not in text


def test_it_takes_two_arrays_and_nothing_else_and_refuses_no_dtype(
    monkeypatch):
  assert list(inspect.signature(dense.mxu_dot).parameters) == ["x", "w"]
  x, w, _ = _case((3,))
  # operands of two dtypes, or already narrow: the plain product's own rules
  assert dense.mxu_dot(x.astype(BF16), w).dtype == F32
  assert dense.mxu_dot(x.astype(BF16), w.astype(BF16)).dtype == BF16
  monkeypatch.setattr(dense, "mxu_operand_dtype", _forced)
  assert dense.mxu_dot(x, w).dtype == F32
  assert dense.mxu_dot(x, w.astype(BF16)).dtype == F32
  with jax.enable_x64(True):
    wide = dense.mxu_dot(x.astype(jnp.float64), w.astype(jnp.float64))
    assert wide.dtype == jnp.float64
    np.testing.assert_allclose(wide, np.asarray(x, np.float64)
                               @ np.asarray(w, np.float64), rtol=1e-12)


def _forced(dtype):
  """The policy's answer on a TPU at default precision."""
  return BF16 if dtype == F32 else dtype


def _dots(jaxpr):
  """Every `dot_general` equation of a jaxpr and of the jaxprs inside it."""
  for eqn in jaxpr.eqns:
    if eqn.primitive.name == "dot_general":
      yield eqn
    for value in eqn.params.values():
      for inner in value if isinstance(value, (tuple, list)) else (value,):
        inner = getattr(inner, "jaxpr", inner)
        if hasattr(inner, "eqns"):
          yield from _dots(inner)


def _sdar():
  cfg = test_sdar_moe.TOY
  rows, noise, targets = test_sdar_moe._batch(cfg)
  params = test_sdar_moe._params(cfg, rows, noise)
  return SDARMoE(cfg), params, rows, noise, targets, block_diffusion_loss


def _laguna():
  cfg = test_laguna.TOY
  rows, numerical, targets = test_laguna._batch(cfg)
  return (Laguna(cfg), test_laguna._params(cfg), rows, numerical, targets,
          next_token_loss)


def _olmo():
  cfg = test_olmo_hybrid.TOY
  rows, numerical, targets = test_olmo_hybrid._batch(cfg)
  return (OlmoHybrid(cfg), test_olmo_hybrid._params(cfg), rows, numerical,
          targets, next_token_loss)


# (the toy; its plain products inside decoder layers, which run under a
# checkpoint: forward, rebuilt forward, dx, dw; those of them that end a
# layer, so that their output only enters the residual's sum and JAX drops
# their rebuilt forward. The head stands outside: forward, dx, dw)
@pytest.mark.parametrize("toy,in_layers,end_a_layer", [
    # 2 layers x (wq, wk, wv, wo); the experts end a layer
    (_sdar, 2 * 4, 0),
    # 5 layers x (wq, wk, wv, wg, wo) + the dense MLP's 3 + 4 shared
    # experts x 3; the dense MLP's and the shared experts' w_down end one
    (_laguna, 5 * 5 + 3 + 4 * 3, 5),
    # 3 recurrent layers x (wq, wk, wv, wg, wb, wa, wo) + the full layer's 4
    # + 4 MLPs x 3; a sublayer's output is normalised
    (_olmo, 3 * 7 + 4 + 4 * 3, 0)],
    ids=["sdar_moe", "laguna", "olmo_hybrid"])
def test_every_product_with_a_weight_is_handed_bfloat16(
    monkeypatch, toy, in_layers, end_a_layer):
  monkeypatch.setattr(dense, "mxu_operand_dtype", _forced)
  model, params, rows, numerical, targets, loss = toy()

  def step(p, r):
    out = model.apply({"params": p}, numerical, None, emb_acts=[r])
    return loss(out, {"targets": targets})

  closed = jax.make_jaxpr(jax.value_and_grad(step, argnums=(0, 1)))(
      params, rows)
  # nothing that was float32 came out narrower: the loss, every leaf's
  # gradient, the rows' gradient
  assert all(v.aval.dtype == F32 for v in closed.jaxpr.outvars)
  assert len(closed.jaxpr.outvars) == 2 + len(params)
  rounded, highest = 0, 0
  for eqn in _dots(closed.jaxpr):
    operands = {v.aval.dtype for v in eqn.invars}
    precision = eqn.params["precision"]
    precision = set(precision) if isinstance(precision, tuple) \
        else {precision}
    if operands == {jnp.dtype(BF16)}:
      rounded += 1
      assert eqn.outvars[0].aval.dtype == F32
      assert eqn.params["preferred_element_type"] == F32
      assert precision == {None}
    else:
      # what is left takes no weight of a plain product, or is the
      # router's: float32 operands, and `highest` wherever a model asked
      assert operands == {jnp.dtype(F32)}
      highest += precision == {HIGHEST}
      if precision != {HIGHEST}:
        # the XLA attention's einsums (tests only): activations alone
        assert all(v.aval.ndim > 2 for v in eqn.invars), eqn
  assert rounded == 4 * in_layers - end_a_layer + 3
  # the router's logits (both MoE models), the delta rule's products
  assert highest > 0
