"""The chunked gated delta rule (`layers/gated_delta.py`) against the
one-token recurrence of `tests/reference_olmo_hybrid.py`, at toy widths that
keep ``d_k != d_v``, on seeded inputs, at ``highest`` matmul precision:
outputs, the last state and the gradient of every input, for lengths that are
no multiple of the chunk and documents that start in the middle of one; the
reset (two packed documents are the two run apart, the convolution's window
included); the written-out backward of the chunk-to-chunk scan; and bfloat16
inside the recurrence, which the tolerance refuses. The same of the rule
whose decay is per key channel (`chunk_kda_rule`, against the one-token
recurrence of `tests/reference_solar_open2.py`), at decays up to 8 nats a
token, where ``e^{-gamma}`` overflows inside a chunk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_olmo_hybrid as ref
import reference_solar_open2 as ref_kda
from distributed_embeddings_tpu.layers import gated_delta
from distributed_embeddings_tpu.layers.decoder import segment_ids
from distributed_embeddings_tpu.layers.gated_delta import (
    causal_conv,
    chunk_gated_delta_rule,
    chunk_kda_rule,
    linear_state_scan,
)

B, H, DK, DV = 2, 3, 6, 10
# float32 at highest precision: both forms round every product and sum to
# 2^-24 of its value, and a chunk's unit triangular solve adds up to `chunk`
# such roundings a row; 5e-6 of the largest value is ten times what any case
# here reads (the largest: 4.5e-7, on the CPU), and a thousandth of what
# bfloat16 inside the recurrence reads (4.9e-3 and 6.5e-3: the last test)
TOL = 5e-6


def _inputs(length, seed, starts_at=()):
  rng = np.random.default_rng(seed)
  f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
  q = ref.l2norm(f(B, length, H, DK)) * DK ** -0.5
  k = ref.l2norm(f(B, length, H, DK))
  v = f(B, length, H, DV)
  g = -jnp.exp(f(B, length, H) - 1.0)          # decays of 0.2 .. 0.99 a token
  beta = 2.0 * jax.nn.sigmoid(f(B, length, H))  # 0 .. 2: negative eigenvalues
  starts = np.zeros((B, length), bool)
  starts[:, 0] = True
  for b, at in starts_at:
    starts[b, at] = True
  return (q, k, v, g, beta), jnp.asarray(starts)


def _close(got, want, what, tol=TOL):
  scale = float(jnp.max(jnp.abs(want)))
  assert scale > 0, what
  gap = float(jnp.max(jnp.abs(got - want))) / scale
  assert gap < tol, (what, gap)
  return gap


CASES = {
    "one_document_whole_chunks": (32, 8, ()),
    "ragged_length": (37, 8, ()),
    "shorter_than_a_chunk": (5, 8, ()),
    "starts_mid_chunk": (37, 8, ((0, 3), (0, 20), (1, 13))),
    "starts_on_and_beside_a_chunks_edge": (40, 8, ((0, 8), (0, 9), (1, 15),
                                                   (1, 16), (1, 39))),
    "three_starts_in_one_chunk": (24, 16, ((0, 17), (0, 18), (0, 21))),
}


@pytest.mark.parametrize("length,chunk,starts_at", CASES.values(),
                         ids=CASES.keys())
def test_chunked_is_the_one_token_recurrence(length, chunk, starts_at):
  args, starts = _inputs(length, length + chunk, starts_at)
  seg = segment_ids(starts)
  mix = jnp.asarray(np.random.default_rng(9).normal(size=(B, length, H, DV)),
                    jnp.float32)
  mix_s = jnp.asarray(np.random.default_rng(8).normal(size=(B, H, DK, DV)),
                      jnp.float32)
  # one scalar of the outputs and of the last state, so that one gradient
  # carries both
  scalar = lambda o, s: jnp.sum(o * mix) + jnp.sum(jnp.tanh(s) * mix_s)

  def chunked(*a):
    o, s = chunk_gated_delta_rule(*a, seg, chunk)
    return scalar(o, s), (o, s)

  def one_token(q, k, v, g, beta):
    o, s = ref.delta_rule(q, k, v, jnp.exp(g), beta, starts)
    return scalar(o, s), (o, s)

  with jax.default_matmul_precision("highest"):
    (_, (o, s)), grads = jax.jit(jax.value_and_grad(
        chunked, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    (_, (o_ref, s_ref)), want = jax.value_and_grad(
        one_token, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
  _close(o, o_ref, "outputs")
  _close(s, s_ref, "last state")
  for name, got, w in zip(("q", "k", "v", "g", "beta"), grads, want):
    _close(got, w, f"gradient of {name}")
  # g at a document's first token is never read
  g_grad = np.asarray(grads[3])
  assert not g_grad[np.asarray(starts)].any()


def test_two_packed_documents_are_the_two_run_apart():
  """Rule and convolution: nothing crosses a document's first token."""
  length, cut, chunk, taps = 29, 11, 8, 4
  args, starts = _inputs(length, 3, ((0, cut), (1, cut)))
  seg = segment_ids(starts)
  with jax.default_matmul_precision("highest"):
    o, last = chunk_gated_delta_rule(*args, seg, chunk)
    apart = []
    for a, e in ((0, cut), (cut, length)):
      zero = jnp.zeros((B, e - a), jnp.int32)
      apart.append(chunk_gated_delta_rule(*(x[:, a:e] for x in args), zero,
                                          chunk))
  _close(o, jnp.concatenate([apart[0][0], apart[1][0]], axis=1), "outputs")
  _close(last, apart[1][1], "last state")
  rng = np.random.default_rng(4)
  x = jnp.asarray(rng.normal(size=(B, length, 7)), jnp.float32)
  w = jnp.asarray(rng.normal(size=(taps, 7)), jnp.float32)
  y = causal_conv(x, w, seg)
  for a, e in ((0, cut), (cut, length)):
    zero = jnp.zeros((B, e - a), jnp.int32)
    np.testing.assert_allclose(y[:, a:e], causal_conv(x[:, a:e], w, zero),
                               atol=1e-6)
  # and by hand: a document's second token sees two taps
  np.testing.assert_allclose(
      y[:, cut + 1], x[:, cut + 1] * w[3] + x[:, cut] * w[2], atol=1e-6)


@pytest.mark.parametrize("starts_at", [(), ((0, 1), (0, 2), (1, 9), (1, 10))],
                         ids=["one_document", "starts_inside_the_window"])
def test_the_convolution_is_the_tap_by_tap_one(starts_at):
  length = 12
  rng = np.random.default_rng(5)
  x = jnp.asarray(rng.normal(size=(B, length, 5)), jnp.float32)
  w = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
  starts = np.zeros((B, length), bool)
  starts[:, 0] = True
  for b, at in starts_at:
    starts[b, at] = True
  starts = jnp.asarray(starts)
  ours = lambda x, w: jnp.sum(jnp.sin(causal_conv(x, w, segment_ids(starts))))
  plain = lambda x, w: jnp.sum(jnp.sin(ref.short_conv(x, w, starts)))
  got, want = (jax.value_and_grad(f, argnums=(0, 1))(x, w)
               for f in (ours, plain))
  assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
  for g, t in zip(got[1], want[1]):
    np.testing.assert_allclose(g, t, atol=1e-5)


def test_segment_ids_count_documents():
  starts = jnp.asarray([[0, 0, 1, 0, 1, 1], [1, 0, 0, 0, 0, 0]], bool)
  assert np.array_equal(segment_ids(starts),
                        [[0, 0, 1, 1, 2, 3], [0, 0, 0, 0, 0, 0]])


def test_the_state_scans_written_out_backward_is_autodiffs():
  rng = np.random.default_rng(6)
  n = 5
  m = jnp.asarray(rng.normal(size=(n, B, H, DK, DK)) * 0.4, jnp.float32)
  b = jnp.asarray(rng.normal(size=(n, B, H, DK, DV)), jnp.float32)
  s0 = jnp.asarray(rng.normal(size=(B, H, DK, DV)), jnp.float32)
  mix = jnp.asarray(rng.normal(size=(n + 1, B, H, DK, DV)), jnp.float32)

  def plain(m, b, s0):
    def step(s, mb):
      return mb[0] @ s + mb[1], s
    last, before = jax.lax.scan(step, s0, (m, b))
    return before, last
  scalar = lambda f: lambda *a: sum(
      jnp.sum(jnp.sin(x) * w) for x, w in zip(
          (lambda o: (o[0], o[1][None]))(f(*a)), (mix[:n], mix[n:])))
  with jax.default_matmul_precision("highest"):
    got = jax.value_and_grad(scalar(linear_state_scan), argnums=(0, 1, 2))(
        m, b, s0)
    want = jax.value_and_grad(scalar(plain), argnums=(0, 1, 2))(m, b, s0)
  assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
  for g, w, name in zip(got[1], want[1], ("m", "b", "s0")):
    _close(g, w, name, 1e-5)


def test_bfloat16_inside_the_recurrence_fails_the_tolerance():
  """The nearest precision below: the state, the decays and the products of
  the one-token recurrence in bfloat16 (8 bits of mantissa, 2^-9 a rounding,
  carried across the sequence) read 6.5e-3 of the largest output and 4.9e-3
  of the largest state value, a thousand times `TOL`."""
  args, starts = _inputs(37, 7, ((0, 3), (0, 20), (1, 13)))
  seg = segment_ids(starts)
  with jax.default_matmul_precision("highest"):
    o, s = chunk_gated_delta_rule(*args, seg, 8)
    q, k, v, g, beta = (x.astype(jnp.bfloat16) for x in args)
    o_low, s_low = ref.delta_rule(q, k, v, jnp.exp(g), beta, starts)
  assert o_low.dtype == jnp.bfloat16
  for got, want in ((o_low, o), (s_low, s)):
    scale = float(jnp.max(jnp.abs(want)))
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / scale
    assert 200 * TOL < gap < 0.1, gap


# ---- the rule with a decay a key channel (KDA) -----------------------------
def _kda_inputs(length, seed, starts_at=(), dtype=jnp.float32):
  """As `_inputs`, ``g [B, L, H, DK]``: log-uniform decays of 0.01 to 8 nats
  a token and a channel. At 8 nats a token ``e^{-gamma}`` passes float32's
  largest number 11 tokens into a chunk and float64's after 89."""
  (q, k, v, _, beta), starts = _inputs(length, seed, starts_at)
  rng = np.random.default_rng(seed + 1000)
  g = -np.exp(rng.uniform(np.log(0.01), np.log(8.0), (B, length, H, DK)))
  g[:, ::5, :, 0] = -8.0          # the overflow case in every chunk
  return tuple(x.astype(dtype) for x in (q, k, v, jnp.asarray(g), beta)), \
      starts


KDA_CASES = {
    **{f"{name}_chunk_{chunk}": (length, chunk, starts_at)
       for name, (length, chunk, starts_at) in CASES.items()},
    # sub-blocks of 16 inside the chunk: a reset inside a sub-block, on a
    # sub-block's edge and in the chunk's last sub-block
    "sub_blocks_no_reset": (150, 64, ()),
    "sub_blocks_resets": (150, 64, ((0, 5), (0, 16), (0, 70), (1, 31),
                                    (1, 127), (1, 128), (1, 149))),
    "sub_blocks_chunk_32": (70, 32, ((0, 17), (1, 33), (1, 40))),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float64, 1e-10),
                                       (jnp.float32, 4 * TOL)],
                         ids=["float64", "float32"])
@pytest.mark.parametrize("length,chunk,starts_at", KDA_CASES.values(),
                         ids=KDA_CASES.keys())
def test_kda_chunked_is_the_one_token_recurrence(length, chunk, starts_at,
                                                 dtype, tol):
  """float64 to 1e-10: the chunked form is the recurrence, not near it.
  float32 at four times the scalar rule's tolerance: the decay of a pair is
  the product of two rounded factors where the scalar rule rounds one, and
  each enters a sum over ``d_k`` channels (the largest reading 3.6e-6)."""
  with jax.enable_x64(dtype == jnp.float64):
    args, starts = _kda_inputs(length, length + chunk, starts_at, dtype)
    seg = segment_ids(starts)
    rng = np.random.default_rng(9)
    mix = jnp.asarray(rng.normal(size=(B, length, H, DV)), dtype)
    mix_s = jnp.asarray(rng.normal(size=(B, H, DK, DV)), dtype)
    scalar = lambda o, s: jnp.sum(o * mix) + jnp.sum(jnp.tanh(s) * mix_s)

    def chunked(*a):
      o, s = chunk_kda_rule(*a, seg, chunk)
      return scalar(o, s), (o, s)

    def one_token(*a):
      o, s = ref_kda.kda_rule(*a, starts)
      return scalar(o, s), (o, s)

    with jax.default_matmul_precision("highest"):
      (_, (o, s)), grads = jax.jit(jax.value_and_grad(
          chunked, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
      (_, (o_ref, s_ref)), want = jax.value_and_grad(
          one_token, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    assert o.dtype == dtype and np.isfinite(np.asarray(o)).all()
    _close(o, o_ref, "outputs", tol)
    _close(s, s_ref, "last state", tol)
    for name, got, w in zip(("q", "k", "v", "g", "beta"), grads, want):
      assert np.isfinite(np.asarray(got)).all(), name
      _close(got, w, f"gradient of {name}", tol)
    # g at a document's first token is never read
    assert not np.asarray(grads[3])[np.asarray(starts)].any()


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_kda_with_one_decay_a_head_is_the_scalar_rule(chunk):
  args, starts = _inputs(70, 3, ((0, 9), (0, 40), (1, 33)))
  q, k, v, g, beta = args
  seg = segment_ids(starts)
  with jax.default_matmul_precision("highest"):
    o, s = chunk_kda_rule(q, k, v, jnp.broadcast_to(
        g[..., None], g.shape + (DK,)), beta, seg, chunk)
    o_scalar, s_scalar = chunk_gated_delta_rule(*args, seg, chunk)
  _close(o, o_scalar, "outputs")
  _close(s, s_scalar, "last state")


def test_kda_forms_no_exponent_above_zero_and_no_pair_tensor_of_a_chunk():
  """What the docstring promises: no array of the traced program holds
  ``chunk x chunk x d_k`` values a chunk (the pairs inside a sub-block are
  ``SUB x SUB x d_k``, the largest it makes), and the outputs are finite on
  decays whose ``e^{-gamma}`` is not."""
  length, chunk = 128, 64
  args, starts = _kda_inputs(length, 5)
  seg = segment_ids(starts)
  jaxpr = jax.make_jaxpr(lambda *a: chunk_kda_rule(*a, seg, chunk))(*args)
  chunks = length // chunk
  pair_tensor = B * chunks * H * chunk * chunk * DK
  sub = gated_delta.SUB

  def walk(jaxpr):
    for eqn in jaxpr.eqns:
      yield eqn
      for sub_jaxpr in jax.core.jaxprs_in_params(eqn.params):
        yield from walk(sub_jaxpr)
  sizes = [int(np.prod(v.aval.shape)) for eqn in walk(jaxpr.jaxpr)
           for v in eqn.outvars]
  assert max(sizes) < pair_tensor
  assert max(sizes) >= B * chunks * H * (chunk // sub) * sub * sub * DK
  g = np.asarray(args[3])
  with np.errstate(over="ignore"):
    assert np.isinf(np.exp(-np.cumsum(g[:, :chunk], axis=1),
                           dtype=np.float32)).any()
  o, s = chunk_kda_rule(*args, seg, chunk)
  assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()


def test_kda_bfloat16_inside_the_recurrence_fails_the_tolerance():
  """As the scalar rule's last test: the one-token recurrence in bfloat16
  stands a hundred times over the float32 tolerance."""
  args, starts = _kda_inputs(70, 7, ((0, 3), (0, 20), (1, 13)))
  seg = segment_ids(starts)
  with jax.default_matmul_precision("highest"):
    o, s = chunk_kda_rule(*args, seg, 32)
    o_low, s_low = ref_kda.kda_rule(
        *(x.astype(jnp.bfloat16) for x in args), starts)
  assert o_low.dtype == jnp.bfloat16
  for got, want in ((o_low, o), (s_low, s)):
    scale = float(jnp.max(jnp.abs(want)))
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / scale
    assert 100 * 4 * TOL < gap < 0.1, gap
