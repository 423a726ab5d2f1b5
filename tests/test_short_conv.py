"""The double-gated short convolution (`layers/short_conv.py`) against the
plain reference's (`tests/reference_lfm2_moe.py`): values and every
gradient; the reset at a document's first token, forward and backward, tap by
tap; packed documents give what the documents give alone; what the
rematerialisation plan keeps of it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

import reference_lfm2_moe as ref
from distributed_embeddings_tpu.layers import remat
from distributed_embeddings_tpu.layers.decoder import segment_ids
from distributed_embeddings_tpu.layers.gated_delta import causal_conv
from distributed_embeddings_tpu.layers.short_conv import (
    gate_chain,
    short_conv_mixer,
)

B, L, D, TAPS = 2, 24, 16, 3


def _case(seed=0, starts_at=((5, 6, 17), (11,)), taps=TAPS):
  rng = np.random.default_rng(seed)
  f32 = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s,
                                          jnp.float32)
  p = {"w_in": f32(D, 3 * D, s=0.3), "conv": f32(taps, D, s=0.5),
       "w_out": f32(D, D, s=0.3)}
  starts = np.zeros((B, L), bool)
  starts[:, 0] = True
  for b, at in enumerate(starts_at):
    starts[b, list(at)] = True
  return p, f32(B, L, D), jnp.asarray(starts)


@pytest.mark.parametrize("taps", [3, 1, 4])
def test_the_mixer_is_the_plain_reference(taps):
  p, h, starts = _case(taps=taps)
  seg = segment_ids(starts)
  f = lambda fn: jax.value_and_grad(
      lambda p, h: jnp.sum(jnp.sin(fn(p, h))), argnums=(0, 1))(p, h)
  with jax.default_matmul_precision("highest"):
    got = f(lambda p, h: short_conv_mixer(p, h, seg))
    want = f(lambda p, h: ref.short_conv(p, h, starts))
  assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
  for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                  jax.tree_util.tree_leaves(want[1])):
    np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))))


def test_a_tap_reads_nothing_before_its_documents_first_token():
  """Documents start at 0, 5, 6 and 17: position 6 (a document of its own
  first token, after a document of one token) sees itself alone, position 7
  sees 6 and 7, position 8 sees all three taps; forward by the output's
  dependence on ``u``, backward by the cotangent that reaches ``u``."""
  p, h, starts = _case()
  seg = segment_ids(starts)
  bcu = jnp.dot(h, p["w_in"])
  out = lambda bcu: gate_chain(bcu, p["conv"], seg)
  u_at = lambda t: np.flatnonzero(np.asarray(jnp.any(jax.grad(
      lambda x: jnp.sum(out(x)[0, t]))(bcu)[0, :, 2 * D:] != 0, axis=-1)))
  assert list(u_at(4)) == [2, 3, 4]      # inside the first document
  assert list(u_at(5)) == [5]            # a first token
  assert list(u_at(6)) == [6]            # and the next document's
  assert list(u_at(7)) == [6, 7]
  assert list(u_at(8)) == [6, 7, 8]
  assert list(u_at(17)) == [17] and list(u_at(18)) == [17, 18]
  assert list(u_at(0)) == [0] and list(u_at(1)) == [0, 1]
  # the backward: a cotangent at 6, 7, 8 reaches no position before 6
  g = jax.grad(lambda x: jnp.sum(out(x)[0, 6:9]))(bcu)
  assert not np.asarray(g[0, :6]).any() and np.asarray(g[0, 6:9]).any()
  # and the taps' gradient at a first token is the last tap's alone
  g_w = jax.grad(lambda w: jnp.sum(gate_chain(bcu, w, seg)[0, 5]))(p["conv"])
  assert not np.asarray(g_w[:2]).any() and np.asarray(g_w[2]).any()
  # by hand: c_7 = w_1 z_6 + w_2 z_7
  gate_in, gate_out, u = np.split(np.asarray(bcu), 3, axis=-1)
  z, w = gate_in * u, np.asarray(p["conv"])
  np.testing.assert_allclose(
      out(bcu)[0, 7], gate_out[0, 7] * (w[1] * z[0, 6] + w[2] * z[0, 7]),
      rtol=1e-5, atol=1e-6)
  np.testing.assert_allclose(
      out(bcu)[0, 8],
      gate_out[0, 8] * (w[0] * z[0, 6] + w[1] * z[0, 7] + w[2] * z[0, 8]),
      rtol=1e-5, atol=1e-6)


def test_packed_documents_give_what_the_documents_give_alone():
  p, h, starts = _case(3)
  seg = segment_ids(starts)
  packed = short_conv_mixer(p, h, seg)
  for b in range(B):
    edges = list(np.flatnonzero(np.asarray(starts[b]))) + [L]
    for a, e in zip(edges[:-1], edges[1:]):
      alone = short_conv_mixer(p, h[b:b + 1, a:e],
                               jnp.zeros((1, e - a), jnp.int32))
      np.testing.assert_allclose(packed[b, a:e], alone[0], atol=1e-6)
  # without the reset the first tokens after a start differ
  merged = short_conv_mixer(p, h, jnp.zeros((B, L), jnp.int32))
  assert float(jnp.max(jnp.abs(merged[0, 6] - packed[0, 6]))) > 1e-3
  np.testing.assert_allclose(merged[0, :5], packed[0, :5], atol=1e-6)


def test_the_convolution_is_causal_convs_at_any_number_of_taps():
  """The shared function (`layers/gated_delta.py`, Olmo-Hybrid's at 4 taps)
  at this model's 3: tap ``K-1`` is the token itself."""
  rng = np.random.default_rng(1)
  x = jnp.asarray(rng.normal(size=(1, 6, 2)), jnp.float32)
  w = jnp.asarray([[100.0, 100.0], [10.0, 10.0], [1.0, 1.0]])
  seg = jnp.asarray([[0, 0, 0, 1, 1, 1]])
  y, xs = np.asarray(causal_conv(x, w, seg)), np.asarray(x)
  np.testing.assert_allclose(y[0, 2], xs[0, 2] + 10 * xs[0, 1]
                             + 100 * xs[0, 0], rtol=1e-6)
  np.testing.assert_allclose(y[0, 3], xs[0, 3], rtol=1e-6)
  np.testing.assert_allclose(y[0, 4], xs[0, 4] + 10 * xs[0, 3], rtol=1e-6)


def test_the_plan_keeps_the_first_product_and_rebuilds_the_gate_chain(
    capsys, monkeypatch):
  """Under `checkpoint_layer` the mixer's residuals are its arguments and
  the product named ``short_conv_in``; the gradients are those of the mixer
  with no checkpoint."""
  p, h, starts = _case(2)
  seg = segment_ids(starts)
  assert remat.SHORT_CONV_IN in remat.KEPT
  loss = lambda f: lambda p, h: jnp.sum(jnp.sin(f(p, h, seg)))
  kept = remat.checkpoint_layer(short_conv_mixer)
  print_saved_residuals(loss(kept), p, h)
  said = capsys.readouterr().out
  # nothing outlives the mixer but its arguments, the documents, the loss's
  # cosine and one value made in `short_conv_mixer`: `h W_in`
  made = [ln for ln in said.splitlines() if ln.strip()
          and "argument" not in ln and "constant" not in ln
          and "output of cos" not in ln]
  assert len(made) == 1 and "layers/short_conv.py" in made[0], said
  assert made[0].startswith(f"f32[{B},{L},{3 * D}] ")
  monkeypatch.setattr(remat, "KEPT", tuple(
      n for n in remat.KEPT if n != remat.SHORT_CONV_IN))
  print_saved_residuals(loss(remat.checkpoint_layer(short_conv_mixer)), p, h)
  assert "layers/short_conv.py" not in capsys.readouterr().out
  got = jax.grad(loss(kept), argnums=(0, 1))(p, h)
  want = jax.grad(loss(short_conv_mixer), argnums=(0, 1))(p, h)
  for g, w in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)
