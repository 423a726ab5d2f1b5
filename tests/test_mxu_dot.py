"""`layers/dense.py`: the language models' plain dense product.

With the operand type passed as bfloat16 (on this backend the policy keeps
float32, so the test passes it), the forward, `dx` and `dw` are the float32
products of the operands rounded to bfloat16, and all three are float32. With
the policy's own answer here the function is `x @ w`, bit for bit, gradients
too. Over the three models' toys with the operand type forced to bfloat16:
every `dot_general` that takes a weight has bfloat16 operands and a float32
result, the router's and the delta rule's keep float32 operands at `highest`,
and no gradient left float32. The expert layer's grouped products
(`grouped_mxu_dots`) the same way: against `lax.ragged_dot` and JAX's own
`vjp` on operands rounded beforehand, `lax.ragged_dot` bit for bit on this
backend, and over the three MoE toys twelve bfloat16-in float32-out grouped
products a checkpointed layer with one rounded copy of the rows a pass."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import test_laguna
import test_lfm2_moe
import test_olmo_hybrid
import test_sdar_moe
from distributed_embeddings_tpu.layers import dense
from distributed_embeddings_tpu.layers.decoder import next_token_loss
from distributed_embeddings_tpu.models.laguna import Laguna
from distributed_embeddings_tpu.models.lfm2_moe import Lfm2Moe
from distributed_embeddings_tpu.models.olmo_hybrid import OlmoHybrid
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


def _jaxprs(jaxpr):
  """A jaxpr and every jaxpr inside it."""
  yield jaxpr
  for eqn in jaxpr.eqns:
    for value in eqn.params.values():
      for inner in value if isinstance(value, (tuple, list)) else (value,):
        inner = getattr(inner, "jaxpr", inner)
        if hasattr(inner, "eqns"):
          yield from _jaxprs(inner)


def _eqns(jaxpr, name):
  """Every equation of primitive ``name`` in a jaxpr and the jaxprs inside
  it."""
  return [eqn for inner in _jaxprs(jaxpr) for eqn in inner.eqns
          if eqn.primitive.name == name]


def _grad_jaxpr(monkeypatch, toy):
  """-> (the jaxpr of ``value_and_grad`` of a toy's step over its parameters
  and its rows with the operand type forced to a TPU's, the rows)."""
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
  return closed.jaxpr, rows


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
  jaxpr, _ = _grad_jaxpr(monkeypatch, toy)
  rounded, highest = 0, 0
  for eqn in _eqns(jaxpr, "dot_general"):
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


# --- the expert layer's grouped products (`dense.grouped_mxu_dots`) --------

def _grouped_case(sizes, k=24, n=40, seed=0):
  rng = np.random.default_rng(seed)
  draw = lambda *shape: jnp.asarray(rng.normal(size=shape), F32)
  m, g = int(np.sum(sizes)), len(sizes)
  return (draw(m, k), (draw(g, k, n), draw(g, k, n)),
          jnp.asarray(sizes, jnp.int32), (draw(m, n), draw(m, n)))


@pytest.mark.parametrize("sizes", [
    (10, 7, 23), (10, 0, 7, 23), (0, 5, 0, 3),
    # as `moe.groups_of(..., whole=True)` pads: the zeros past the live rows
    # in the last expert's group
    (6, 2, 0, 8 + 48)],
    ids=["ragged", "an_empty_group", "empty_first", "padded_as_whole_pads"])
def test_the_grouped_products_are_those_of_the_rounded_operands(sizes):
  x, ws, sizes, dys = _grouped_case(sizes)
  if int(sizes[-1]) > 48:
    x = x.at[-48:].set(0)       # the head's rows past the live count
  ys, vjp = jax.vjp(
      lambda x, ws: dense.grouped_dots_rounded(BF16, x, ws, sizes), x, ws)
  dx, dws = vjp(dys)
  # `lax.ragged_dot` and JAX's own transpose of it on the operands rounded
  # beforehand, every product exact in float32
  wide = lambda a: a.astype(BF16).astype(F32)
  exact = lambda x, ws: tuple(
      lax.ragged_dot(x, w, sizes, precision=HIGHEST) for w in ws)
  want_ys, want_vjp = jax.vjp(exact, wide(x), tuple(map(wide, ws)))
  want_dx, want_dws = want_vjp(tuple(map(wide, dys)))
  got = {"y": ys, "dx": (dx,), "dw": dws}
  want = {"y": want_ys, "dx": (want_dx,), "dw": want_dws}
  for name in got:
    assert len(got[name]) == len(want[name])
    for a, b in zip(got[name], want[name]):
      assert a.dtype == F32 and a.shape == b.shape, name
      np.testing.assert_allclose(a, b, rtol=0, atol=2e-5, err_msg=name)
  # an empty group's weights take no row: their gradient is exactly zero
  for dw in dws:
    np.testing.assert_array_equal(np.asarray(dw)[np.asarray(sizes) == 0], 0)
  # and it is a rounding: the float32 products stand a bfloat16 ulp away
  assert float(jnp.max(jnp.abs(ys[0] - exact(x, ws)[0]))) > 1e-3


def test_on_this_backend_the_grouped_products_are_ragged_dot_bit_for_bit():
  assert dense.mxu_operand_dtype(F32) == F32
  x, ws, sizes, dys = _grouped_case((10, 0, 7, 23), seed=1)
  plain = lambda x, ws: tuple(lax.ragged_dot(x, w, sizes) for w in ws)
  got = jax.vjp(lambda x, ws: dense.grouped_mxu_dots(x, ws, sizes), x, ws)
  want = jax.vjp(plain, x, ws)
  for a, b in zip(jax.tree_util.tree_leaves((got[0], got[1](dys))),
                  jax.tree_util.tree_leaves((want[0], want[1](dys)))):
    np.testing.assert_array_equal(a, b)
  text = str(jax.make_jaxpr(jax.grad(
      lambda x, ws: sum(jnp.sum(y) for y in dense.grouped_mxu_dots(
          x, ws, sizes)), (0, 1)))(x, ws))
  assert "bf16" not in text and "custom_vjp" not in text
  # operands of two dtypes: `lax.ragged_dot`'s own rules, on any backend
  assert dense.grouped_mxu_dots(x.astype(BF16), ws, sizes)[0].dtype == F32


def _lfm2():
  cfg = test_lfm2_moe.TOY
  rows, numerical, targets = test_lfm2_moe._batch(cfg)
  return (Lfm2Moe(cfg), test_lfm2_moe._params(cfg), rows, numerical, targets,
          next_token_loss)


@pytest.mark.parametrize("toy,expert_layers", [
    (_sdar, 2), (_laguna, 4), (_lfm2, 4)],
    ids=["sdar_moe", "laguna", "lfm2_moe"])
def test_every_grouped_product_of_the_head_is_handed_bfloat16(
    monkeypatch, toy, expert_layers):
  jaxpr, rows = _grad_jaxpr(monkeypatch, toy)
  grouped = _eqns(jaxpr, "ragged_dot_general")
  # a checkpointed expert layer: 3 forward, 3 rebuilt, 6 backward
  assert len(grouped) == 12 * expert_layers
  for eqn in grouped:
    lhs, rhs, sizes = eqn.invars
    assert lhs.aval.dtype == rhs.aval.dtype == BF16, eqn
    assert sizes.aval.dtype == jnp.int32
    assert eqn.params["preferred_element_type"] == F32
    assert eqn.params["precision"] is None
    assert eqn.outvars[0].aval.dtype == F32
  # ONE rounded copy of the dispatched rows a pass. What is rounded at the
  # head's length: `x [rows, d]` forward and rebuilt and the cotangent of `y`
  # (each read by two to three products), `silu(gate) * up [rows, f]` forward
  # and rebuilt and the cotangents of `gate` and `up`; a copy a product would
  # be 5 and 4
  head, d = grouped[0].invars[0].aval.shape[0], rows.shape[-1]
  written = [eqn.invars[0].aval.shape
             for eqn in _eqns(jaxpr, "convert_element_type")
             if eqn.params["new_dtype"] == BF16
             and eqn.invars[0].aval.shape[0] == head]
  assert written.count((head, d)) == 3 * expert_layers
  assert len(written) == 7 * expert_layers
  # and none of them is rounded twice in one jaxpr, or from a narrower type
  for inner in _jaxprs(jaxpr):
    rounded = [eqn.invars[0] for eqn in inner.eqns
               if eqn.primitive.name == "convert_element_type"
               and eqn.params["new_dtype"] == BF16
               and eqn.invars[0].aval.shape[0] == head]
    assert all(v.aval.dtype == F32 for v in rounded)
    assert len(rounded) == len(set(map(id, rounded)))
