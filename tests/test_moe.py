"""One chip's share of a mixture-of-experts layer (`layers/moe.py`): the
shares of all chips add up to the uncut layer, no assignment is dropped
however skewed the router, and values and gradients are those of a plain
loop over the experts; under the default softmax router, whose bits are
what they were before a share carried a router, and under a scaled sigmoid
one; and under a selection bias, which moves the choice and never a weight,
has no gradient, and without which `route` and `moe_share` trace to what the
parent of the PR that added it traced."""

import gzip
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_embeddings_tpu.layers import dense, moe
from distributed_embeddings_tpu.layers.moe import (
    MoEShare,
    Router,
    moe_share,
    route,
    shared_expert,
)

T, D, F, E, K = 64, 16, 24, 32, 2


def _weights(seed=0):
  rng = np.random.default_rng(seed)
  f32 = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s,
                                          jnp.float32)
  return (f32(T, D), f32(D, E), f32(E, D, F, s=0.3), f32(E, D, F, s=0.3),
          f32(E, F, D, s=0.3))


SIGMOID = Router("sigmoid", True, 2.5)


def plain_layer(h, w_router, w_gate, w_up, w_down, first=0, k=K,
                router=Router()):
  """Every held expert over every token, one by one. ``w_gate`` and the
  others hold experts ``first ..``; the router chooses among all."""
  top_p, top_e = route(h, w_router, k, router)
  out = jnp.zeros_like(h)
  for e in range(w_gate.shape[0]):
    y = (jax.nn.silu(h @ w_gate[e]) * (h @ w_up[e])) @ w_down[e]
    chosen = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
    out = out + chosen[:, None] * y
  return out


def test_route_renormalises_the_chosen_probabilities():
  h, w_router, *_ = _weights()
  top_p, top_e = route(h, w_router, 3)
  assert top_p.shape == top_e.shape == (T, 3)
  np.testing.assert_allclose(np.sum(top_p, axis=-1), 1.0, atol=1e-6)
  probs = jax.nn.softmax(h @ w_router, axis=-1)
  assert np.array_equal(np.asarray(top_e), np.argsort(-probs, axis=-1)[:, :3])


def test_the_softmax_router_traces_to_what_it_did_before_it_was_data():
  """`route` as it stood before PR 35, copied here: the default router's
  jaxpr is that function's, so a model that names no router gets the bits
  it got (no multiply by a scale of 1, no second normalisation)."""
  def before(h, w_router, top_k):
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e
  h, w_router, *_ = _weights()
  assert str(jax.make_jaxpr(lambda h, w: route(h, w, 3))(h, w_router)) \
      == str(jax.make_jaxpr(lambda h, w: before(h, w, 3))(h, w_router))
  assert str(jax.make_jaxpr(lambda h, w: route(h, w, 3, Router()))(
      h, w_router)) == str(jax.make_jaxpr(
          lambda h, w: before(h, w, 3))(h, w_router))
  for got, want in zip(route(h, w_router, 3), before(h, w_router, 3)):
    assert np.array_equal(got, want)
  assert MoEShare(E, K, (0, 4)) == MoEShare(E, K, (0, 4), Router())
  with pytest.raises(ValueError, match="softmax or sigmoid"):
    Router("tanh")


@pytest.mark.parametrize("renormalise,scale", [(True, 2.5), (True, 1.0),
                                               (False, 1.0)])
def test_the_sigmoid_router_scores_each_expert_on_its_own(renormalise, scale):
  h, w_router, *_ = _weights()
  top_p, top_e = route(h, w_router, 3, Router("sigmoid", renormalise, scale))
  s = 1.0 / (1.0 + np.exp(-np.asarray(h @ w_router, np.float64)))
  assert np.array_equal(np.asarray(top_e), np.argsort(-s, axis=-1)[:, :3])
  chosen = np.take_along_axis(s, np.asarray(top_e), axis=-1)
  want = chosen / chosen.sum(-1, keepdims=True) if renormalise else chosen
  np.testing.assert_allclose(top_p, scale * want, rtol=2e-6)
  if renormalise:
    np.testing.assert_allclose(np.sum(top_p, axis=-1), scale, rtol=1e-6)


def test_the_head_is_a_multiple_of_the_expected_load():
  share = MoEShare(128, 8, (0, 16))
  assert share.head_rows(8192 * 8) == moe.HEAD_LOADS * 8192   # the cell's
  # a third expert shape, the same head: 32 of 256 at 8,192 tokens
  assert MoEShare(256, 8, (0, 32), SIGMOID).head_rows(8192 * 8) \
      == moe.HEAD_LOADS * 8192
  assert share.head_rows(10) == 8 and MoEShare(8, 2, (0, 8)).head_rows(128) \
      == 128                                       # never past the stream


@pytest.mark.parametrize("held,head_loads,router", [
    (2, 4, Router()), (2, 1, Router()), (8, 4, Router()), (8, 1, Router()),
    (32, 4, Router()), (4, 4, SIGMOID), (4, 1, SIGMOID), (32, 4, SIGMOID)])
def test_the_shares_add_up_to_the_uncut_layer(held, head_loads, router,
                                              monkeypatch):
  """Nothing is computed by every share alike, so nothing is counted once:
  the plain sum of the shares is the whole layer, whichever router the model
  names. (With a head of one expected load about half the shares walk their
  tail. A shared expert is no part of a share: the next test.)"""
  monkeypatch.setattr(moe, "HEAD_LOADS", head_loads)
  h, wr, wg, wu, wd = _weights()
  # the scaled sigmoid router's weights are 2.5 times as large
  atol = 2e-6 * router.scale
  with jax.default_matmul_precision("highest"):
    whole = plain_layer(h, wr, wg, wu, wd, router=router)
    parts, assigned, walked = [], 0, 0
    for first in range(0, E, held):
      sl = slice(first, first + held)
      share = MoEShare(E, K, (first, held), router)
      out, c = moe_share(h, wr, wg[sl], wu[sl], wd[sl], share)
      np.testing.assert_allclose(
          out, plain_layer(h, wr, wg[sl], wu[sl], wd[sl], first,
                           router=router), atol=atol)
      assert int(c["assignments"]) == int(c["computed"]) == int(
          np.sum(c["loads"]))
      assigned += int(c["assignments"])
      walked += int(c["assignments"]) > share.head_rows(T * K)
      parts.append(out)
  assert assigned == T * K           # every assignment lies on one share
  assert (walked > 0) == (head_loads == 1 and held < E)
  np.testing.assert_allclose(sum(parts), whole, atol=2 * atol)
  assert float(jnp.max(jnp.abs(parts[0]))) > 0.01


def test_a_shared_expert_is_counted_once_beside_the_shares():
  """Every chip computes the shared expert for its own tokens, so adding
  the chips' outputs up would count it once a chip: the layer is the routed
  shares' sum plus the shared expert once."""
  h, wr, wg, wu, wd = _weights(3)
  shared = (wg[0] * 0.5, wu[1] * 0.5, wd[2] * 0.5)
  with jax.default_matmul_precision("highest"):
    whole = plain_layer(h, wr, wg, wu, wd, router=SIGMOID) \
        + (jax.nn.silu(h @ shared[0]) * (h @ shared[1])) @ shared[2]
    chips = [moe_share(h, wr, wg[f:f + 8], wu[f:f + 8], wd[f:f + 8],
                       MoEShare(E, K, (f, 8), SIGMOID))[0]
             + shared_expert(h, *shared) for f in range(0, E, 8)]
    once = shared_expert(h, *shared)
  np.testing.assert_allclose(sum(chips) - 3 * once, whole, atol=2e-5)
  assert float(jnp.max(jnp.abs(once))) > 0.1
  g = jax.grad(lambda s: jnp.sum(jnp.sin(shared_expert(h, *s))))(shared)
  want = jax.grad(lambda s: jnp.sum(jnp.sin(
      (jax.nn.silu(h @ s[0]) * (h @ s[1])) @ s[2])))(shared)
  for a, b in zip(g, want):
    assert np.array_equal(a, b)


def _skewed(seed):
  """Weights whose router gives expert 3 every token with a positive first
  feature, first: about half of them."""
  h, wr, wg, wu, wd = _weights(seed)
  wr = np.array(wr) * 0.1
  wr[:, 3] = 0.0
  wr[0, 3] = 40.0
  return h, jnp.asarray(wr), wg, wu, wd


def test_no_token_is_dropped_under_a_skewed_router():
  """One expert is given half the tokens: at a capacity of 1.25 times the
  mean load it would drop most of them, and its share's load is past the
  head of four expected loads. Here every assignment is computed: the tail
  is walked, and the counter says so from the group sizes the grouped
  matmuls were handed."""
  h, wr, wg, wu, wd = _skewed(1)
  share = MoEShare(E, K, (3, 1))
  with jax.default_matmul_precision("highest"):
    out, c = moe_share(h, wr, wg[3:4], wu[3:4], wd[3:4], share)
    want = plain_layer(h, wr, wg[3:4], wu[3:4], wd[3:4], 3)
  loads = np.asarray(c["loads"])
  positive = int(np.sum(np.asarray(h[:, 0]) > 0.05))
  assert T // 3 < positive <= loads[0]
  assert loads[0] > 1.25 * T * K / E          # over a usual capacity: it
  assert loads[0] > share.head_rows(T * K)    # would have dropped tokens
  assert int(c["computed"]) == int(c["assignments"]) == int(loads.sum())
  np.testing.assert_allclose(out, want, atol=4e-6)


def test_a_tail_not_walked_shows_in_the_counter(monkeypatch):
  """The counter is no arithmetic on the load: with the walk taken out the
  rows past the head are neither computed nor counted."""
  h, wr, wg, wu, wd = _skewed(1)
  real_cond = jax.lax.cond
  monkeypatch.setattr(moe.lax, "cond",
                      lambda pred, walk, rest: real_cond(False, walk, rest))
  share = MoEShare(E, K, (3, 1))
  _, c = moe_share(h, wr, wg[3:4], wu[3:4], wd[3:4], share)
  assert int(c["computed"]) == share.head_rows(T * K) < int(c["assignments"])


@pytest.mark.parametrize("skewed,held,router", [
    (False, (2, 4), Router()), (True, (3, 1), Router()),
    (False, (2, 4), SIGMOID), (True, (3, 1), SIGMOID)],
    ids=["head_only", "tail_walked", "sigmoid_head_only",
         "sigmoid_tail_walked"])
def test_gradients_are_the_plain_loops(skewed, held, router):
  h, wr, wg, wu, wd = _skewed(2) if skewed else _weights(2)
  sl = slice(held[0], held[0] + held[1])
  args = (h, wr, wg[sl], wu[sl], wd[sl])
  share = MoEShare(E, K, held, router)
  with jax.default_matmul_precision("highest"):
    got = jax.jit(jax.grad(
        lambda a: jnp.sum(jnp.sin(moe_share(*a, share)[0]))))(args)
    want = jax.grad(lambda a: jnp.sum(jnp.sin(plain_layer(
        *a, held[0], router=router))))(args)
  for g, w in zip(got, want):
    np.testing.assert_allclose(g, w, atol=3e-5 * router.scale, rtol=1e-4)
  assert float(jnp.max(jnp.abs(got[1]))) > 0   # the router learns too


@pytest.mark.parametrize("held", [(0, 0), (E - 2, 4), (-1, 2)])
def test_a_share_is_a_range_of_the_layers_experts(held):
  with pytest.raises(ValueError, match="no range"):
    MoEShare(E, K, held)


# ---- the selection bias ------------------------------------------------------
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BIASED = Router("sigmoid", True, 1.0, selection_bias=True)


def _recorded(text):
  """``# name`` -> the jaxpr under it, of a file recorded on the parent."""
  parts = re.split(r"^# (\S+)\n", text, flags=re.M)[1:]
  return dict(zip(parts[::2], parts[1::2]))


def test_without_a_bias_route_traces_to_the_parents_text():
  """`tests/data/route_jaxpr_parent.txt`: ``jax.make_jaxpr(route)`` at a toy
  shape under three routers, taken on the parent of PR 42 (commit 77113ce)
  before `route` took a bias: SDAR's, Laguna's and Keye's steps trace as
  they did."""
  with open(os.path.join(DATA, "route_jaxpr_parent.txt")) as f:
    want = _recorded(f.read())
  h = jax.ShapeDtypeStruct((64, 16), jnp.float32)
  w = jax.ShapeDtypeStruct((16, 32), jnp.float32)
  routers = {"softmax": Router(), "sigmoid_2.5": SIGMOID,
             "sigmoid_1": Router("sigmoid", True, 1.0)}
  assert set(want) == set(routers)
  for name, router in routers.items():
    for call in (lambda h, w: route(h, w, 3, router),
                 lambda h, w: route(h, w, 3, router, None),
                 lambda h, w: route(h, w, 3, router, bias=None)):
      assert str(jax.make_jaxpr(call)(h, w)) + "\n" == want[name], name


def test_without_a_bias_moe_share_traces_to_the_parents_text():
  """`tests/data/moe_share_jaxpr_parent.txt.gz`: the whole share at a toy
  shape (4 of 32 experts from the fourth, top 2), recorded on the same
  parent, object addresses struck out."""
  with gzip.open(os.path.join(DATA, "moe_share_jaxpr_parent.txt.gz"),
                 "rt") as f:
    want = _recorded(f.read())
  f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
  args = (f32(64, 16), f32(16, 32), f32(4, 16, 24), f32(4, 16, 24),
          f32(4, 24, 16))
  for name, router in (("softmax", Router()), ("sigmoid_2.5", SIGMOID)):
    share = MoEShare(32, 2, (4, 4), router)
    text = str(jax.make_jaxpr(lambda *a: moe_share(*a, share))(*args))
    assert re.sub(r"0x[0-9a-f]+", "0x", text) + "\n" == want[name], name


@pytest.mark.parametrize("d,dtype,kernel", [
    (128, jnp.float32, True),      # whole lane tiles of float32
    (16, jnp.float32, False),      # the toy width of this file: no lane tile
    (192, jnp.float32, False),
])
def test_on_a_tpu_the_head_takes_the_kernel_only_where_it_fits(
    monkeypatch, d, dtype, kernel):
  """`moe.combine_kernel` reads the backend and `pallas_moe_combine.fits`:
  where either says no, the share traces to the two scatter-adds it always
  had (the jaxpr of the test above), with no kernel call and no `pos`."""
  f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
  args = (jax.ShapeDtypeStruct((64, d), dtype), f32(d, 32), f32(4, d, 24),
          f32(4, d, 24), f32(4, 24, d))
  share = MoEShare(32, 2, (4, 4))
  trace = lambda: str(jax.make_jaxpr(lambda *a: moe_share(*a, share))(*args))
  # the grouped matmuls read the backend too (`dense.grouped_mxu_dots`): held
  # to a TPU's answer on both sides, what is compared is the route
  monkeypatch.setattr(dense, "mxu_operand_dtype",
                      lambda dt: jnp.bfloat16 if dt == jnp.float32 else dt)
  on_cpu = trace()
  assert "pallas_call" not in on_cpu and "cumsum" in on_cpu
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  on_tpu = trace()
  # the kernel sums float32 and nothing else
  assert moe.combine_kernel(
      256, jax.ShapeDtypeStruct((64, 128), jnp.bfloat16), 2) is None
  assert ("pallas_call" in on_tpu) == kernel
  if not kernel:
    strike = lambda text: re.sub(r"0x[0-9a-f]+", "0x", text)
    assert strike(on_tpu) == strike(on_cpu)
  else:
    # the head's scatter-add is gone, the tail's (inside the conditional)
    # stays; the inverse of the order is counted, not sorted or scattered
    assert on_tpu.count("scatter-add") == on_cpu.count("scatter-add") - 1
    assert on_tpu.count("sort[") == on_cpu.count("sort[")


def test_a_bias_moves_the_choice_and_never_a_weight():
  h, w_router, *_ = _weights(4)
  rng = np.random.default_rng(4)
  bias = jnp.asarray(rng.uniform(-0.2, 0.2, E), jnp.float32)
  plain_p, plain_e = route(h, w_router, 3, BIASED)
  top_p, top_e = route(h, w_router, 3, BIASED, bias)
  s = 1.0 / (1.0 + np.exp(-np.asarray(h @ w_router, np.float64)))
  # the choice is the top 3 of s + b ...
  assert np.array_equal(np.asarray(top_e),
                        np.argsort(-(s + np.asarray(bias, np.float64)),
                                   axis=-1)[:, :3])
  moved = np.asarray(top_e) != np.asarray(plain_e)
  assert 0.1 < moved.mean() < 0.9
  # ... and the weights are the chosen experts' UNBIASED scores, renormalised
  chosen = np.take_along_axis(s, np.asarray(top_e), axis=-1)
  np.testing.assert_allclose(top_p, chosen / chosen.sum(-1, keepdims=True),
                             rtol=2e-6)
  # a bias of 0 chooses as no bias does, to the bit
  for got, want in zip(route(h, w_router, 3, BIASED, jnp.zeros(E)),
                       (plain_p, plain_e)):
    assert np.array_equal(got, want)
  # one constant on every expert moves nothing either
  for got, want in zip(route(h, w_router, 3, BIASED, jnp.full(E, 0.25)),
                       (plain_p, plain_e)):
    assert np.array_equal(got, want)
  # without renormalisation the weights are the scores themselves
  raw_p, raw_e = route(h, w_router, 3, Router("sigmoid", False, 1.0, True),
                       bias)
  assert np.array_equal(raw_e, top_e)
  np.testing.assert_allclose(raw_p, chosen, rtol=2e-6)


def test_the_shares_under_a_bias_add_up_and_count_what_it_moved():
  h, wr, wg, wu, wd = _weights(5)
  bias = jnp.asarray(np.random.default_rng(5).uniform(-0.2, 0.2, E),
                     jnp.float32)
  with jax.default_matmul_precision("highest"):
    top_p, top_e = route(h, wr, K, BIASED, bias)
    whole = jnp.zeros_like(h)
    for e in range(E):
      y = (jax.nn.silu(h @ wg[e]) * (h @ wu[e])) @ wd[e]
      whole = whole + jnp.sum(jnp.where(top_e == e, top_p, 0.0),
                              axis=-1)[:, None] * y
    parts, moved = [], set()
    for first in range(0, E, 4):
      sl = slice(first, first + 4)
      out, c = moe_share(h, wr, wg[sl], wu[sl], wd[sl],
                         MoEShare(E, K, (first, 4), BIASED), bias)
      assert int(c["assignments"]) == int(c["computed"])
      parts.append(out)
      moved.add(int(c["moved"]))   # of all experts' choices: every share's
  np.testing.assert_allclose(sum(parts), whole, atol=4e-6)
  _, plain_e = route(h, wr, K, BIASED)
  want = sum(int(e not in plain_e[t]) for t in range(T) for e in top_e[t])
  assert moved == {want} and 0 < want < T * K
  with pytest.raises(ValueError, match="selection_bias=True and no bias"):
    moe_share(h, wr, wg[:4], wu[:4], wd[:4], MoEShare(E, K, (0, 4), BIASED))
  with pytest.raises(ValueError, match="selection_bias=False and a bias"):
    moe_share(h, wr, wg[:4], wu[:4], wd[:4], MoEShare(E, K, (0, 4), SIGMOID),
              bias)


def test_the_bias_has_no_gradient_and_an_adam_step_leaves_it_bit_for_bit():
  h, wr, wg, wu, wd = _weights(6)
  bias = jnp.asarray(np.random.default_rng(6).uniform(-0.2, 0.2, E),
                     jnp.float32)
  share = MoEShare(E, K, (8, 8), BIASED)
  params = {"router": wr, "bias": bias, "w_gate": wg[8:16], "w_up": wu[8:16],
            "w_down": wd[8:16]}
  loss = lambda p: jnp.sum(jnp.sin(moe_share(
      h, p["router"], p["w_gate"], p["w_up"], p["w_down"], share,
      p["bias"])[0]))
  grads = jax.jit(jax.grad(loss))(params)
  assert grads["bias"].shape == (E,) and grads["bias"].dtype == jnp.float32
  assert not np.asarray(grads["bias"]).any()     # exactly zero
  assert float(jnp.max(jnp.abs(grads["router"]))) > 0
  tx = optax.adam(1e-2)
  updates, _ = tx.update(grads, tx.init(params), params)
  after = optax.apply_updates(params, updates)
  assert np.array_equal(np.asarray(after["bias"]).view(np.uint32),
                        np.asarray(bias).view(np.uint32))
  assert not np.array_equal(after["router"], wr)


# ---- all four fields at once, beside a shared expert -------------------------
ALL_FOUR = Router("sigmoid", True, 1.8, selection_bias=True)


def test_a_biased_scaled_sigmoid_router_beside_a_shared_expert():
  """`Router("sigmoid", True, 1.8, selection_bias=True)` with
  `shared_expert`, what `models/glm_moe_lite.py` sets: against the plain
  equations written out (``s = sigmoid(h W_r)``; the top 2 of ``s + b``;
  ``p_e = 1.8 s_e / sum of the chosen s``; every expert over every token;
  the shared expert once), the eight shares and the shared expert counted
  once adding up to them; the bias's gradient exactly zero, and one Adam step
  leaves it bit for bit while the router and the shared expert move."""
  h, wr, wg, wu, wd = _weights(7)
  bias = jnp.asarray(np.random.default_rng(7).uniform(-0.2, 0.2, E),
                     jnp.float32)
  shared = (wg[0] * 0.5, wu[1] * 0.5, wd[2] * 0.5)
  swiglu = lambda h, a, b, c: (jax.nn.silu(h @ a) * (h @ b)) @ c
  with jax.default_matmul_precision("highest"):
    s = jax.nn.sigmoid(h @ wr)
    _, top_e = jax.lax.top_k(s + bias, K)
    chosen = jnp.sum(jax.nn.one_hot(top_e, E), axis=1)
    p = 1.8 * s * chosen / jnp.sum(s * chosen, axis=-1, keepdims=True)
    whole = sum(p[:, e, None] * swiglu(h, wg[e], wu[e], wd[e])
                for e in range(E)) + swiglu(h, *shared)
    routed = [moe_share(h, wr, wg[f:f + 4], wu[f:f + 4], wd[f:f + 4],
                        MoEShare(E, K, (f, 4), ALL_FOUR), bias)[0]
              for f in range(0, E, 4)]
    once = shared_expert(h, *shared)
  np.testing.assert_allclose(jnp.sum(p, axis=-1), 1.8, rtol=1e-6)
  assert np.any(np.asarray(top_e) != np.asarray(jax.lax.top_k(s, K)[1]))
  np.testing.assert_allclose(sum(routed) + once, whole, atol=2e-5)
  # the shared expert counted once a chip is another layer
  assert float(jnp.max(jnp.abs(once))) > 0.1
  # what route itself returns is that p, to rounding
  top_p, got_e = route(h, wr, K, ALL_FOUR, bias)
  assert np.array_equal(got_e, top_e)
  np.testing.assert_allclose(
      top_p, jnp.take_along_axis(p, top_e, axis=-1), rtol=1e-5)

  share = MoEShare(E, K, (8, 8), ALL_FOUR)
  params = {"router": wr, "bias": bias, "w_gate": wg[8:16], "w_up": wu[8:16],
            "w_down": wd[8:16], "shared": shared}
  loss = lambda q: jnp.sum(jnp.sin(
      moe_share(h, q["router"], q["w_gate"], q["w_up"], q["w_down"], share,
                q["bias"])[0] + shared_expert(h, *q["shared"])))
  grads = jax.jit(jax.grad(loss))(params)
  assert not np.asarray(grads["bias"]).any()     # exactly zero
  tx = optax.adam(1e-2)
  updates, _ = tx.update(grads, tx.init(params), params)
  after = optax.apply_updates(params, updates)
  assert np.array_equal(np.asarray(after["bias"]).view(np.uint32),
                        np.asarray(bias).view(np.uint32))
  assert not np.array_equal(after["router"], wr)
  assert not np.array_equal(after["shared"][0], shared[0])
