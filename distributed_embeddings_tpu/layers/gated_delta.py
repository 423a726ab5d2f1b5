"""The gated delta rule in chunked form, and the short causal convolution
that feeds it, over sequences in which several documents are packed.

Per head, with a state ``S [d_k, d_v]`` that starts at 0, the rule reads

    S'_t = alpha_t S_{t-1}          (S'_t = 0 at a document's first token)
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

with ``alpha_t = exp(g_t)``. A token at a time that is ``L`` dependent steps
of a matrix-vector product; :func:`chunk_gated_delta_rule` computes the same
numbers ``chunk`` tokens at a time (the WY / UT-transform form of Yang et al.,
"Gated Delta Networks", as ``fla``'s ``chunk_gated_delta_rule`` does).

Inside a chunk, with ``gamma_i`` the running sum of ``g`` from the chunk's
first token and ``w_i = beta_i (v_i - S'_i^T k_i)``, unrolling gives
``S_i = e^{gamma_i} S_0 + sum_{j<=i} e^{gamma_i - gamma_j} k_j w_j^T`` and so
``(I + A) W = diag(beta) (V - diag(e^gamma) K S_0)`` with the strictly lower
triangular ``A_ij = beta_i e^{gamma_i - gamma_j} (k_i . k_j)``: one unit
triangular solve a chunk gives ``U = T diag(beta) V`` and
``Wk = T diag(beta e^gamma) K`` (``T = (I + A)^-1``), ``W = U - Wk S_0``. Then

    O       = P U + (diag(e^gamma) Q - P Wk) S_0,  P_ij = e^{gamma_i-gamma_j} (q_i . k_j), j <= i
    S_next  = (e^{gamma_C} I - Kd^T Wk) S_0 + Kd^T U,  Kd_i = e^{gamma_C - gamma_i} k_i

so everything but the last line is computed for all chunks at once, and what
runs chunk after chunk is ``S <- M_n S + B_n`` (:func:`linear_state_scan`:
one ``[d_k, d_k] x [d_k, d_v]`` product a step and a head; its backward is
written out, keeps the per-chunk states once, as the forward's own output,
and recomputes nothing).

A document's first token resets the state. In the chunked form that is a
mask: a pair ``(i, j)`` counts only inside one document, the chunk's incoming
state only reaches the tokens before the chunk's first reset, and only the
tokens after its last reset reach the outgoing state. ``g`` at a reset is
never read.

With ONE DECAY A KEY CHANNEL (:func:`chunk_kda_rule`, Kimi Delta Attention:
``S'_t = Diag(exp(g_t)) S_{t-1}``, ``g_t [d_k]``) the same algebra holds with
``gamma [C, d_k]``: the decay of a pair no longer factors out of its dot
product, ``A_ij = beta_i sum_c k_ic k_jc e^{gamma_ic - gamma_jc}`` (``P`` the
same with ``q_i``), ``e^gamma`` multiplies ``K`` and ``Q`` a channel and
``M = Diag(e^{gamma_C}) - Kd^T Wk``. :func:`_decayed_products` says how the
pairs are taken without an exponent above 0.

Every product of the rule runs at ``highest`` matmul precision: the state
carries rounding across thousands of tokens, and the rule is a hundredth of
a layer's arithmetic (the projections around it are the caller's, at the
caller's precision).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..telemetry import scopes

_HIGHEST = jax.lax.Precision.HIGHEST


def causal_conv(x, w, seg):
  """Depthwise causal convolution over time: ``x [B, L, C]``, ``w [K, C]``
  (``w[K-1]`` multiplies the token itself), ``y_t = sum_j w_j x_{t-(K-1)+j}``.
  A tap that would read before the document's first token reads 0."""
  taps = w.shape[0]
  out = x * w[taps - 1]
  for back in range(1, taps):
    shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :x.shape[1]]
    # -1 is no document's number: the padding is outside every document
    same = jnp.pad(seg, ((0, 0), (back, 0)), constant_values=-1
                   )[:, :x.shape[1]] == seg
    out = out + jnp.where(same[..., None], shifted, 0) * w[taps - 1 - back]
  return out


def l2_norm(x, eps: float = 1e-6):
  """``x / sqrt(sum(x^2) + eps)`` over the last axis: what a model makes of
  a head's ``q`` and ``k`` before the rule."""
  return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                           + eps)


@jax.custom_vjp
def linear_state_scan(m, b, s0):
  """``S_{n+1} = M_n S_n + B_n`` from ``S_0 = s0``: ``m [N, ..., dk, dk]``,
  ``b [N, ..., dk, dv]`` -> (``[N, ..., dk, dv]`` the state BEFORE each
  step, the state after the last)."""
  def step(s, mb):
    return jnp.matmul(mb[0], s, precision=_HIGHEST) + mb[1], s
  last, before = jax.lax.scan(step, s0, (m, b))
  return before, last


def _scan_fwd(m, b, s0):
  before, last = linear_state_scan(m, b, s0)
  return (before, last), (m, before)


def _scan_bwd(res, cts):
  m, before = res
  d_before, d_last = cts

  def step(lam, x):
    m_n, s_n, d_n = x
    d_m = jnp.matmul(lam, jnp.swapaxes(s_n, -1, -2), precision=_HIGHEST)
    back = jnp.matmul(jnp.swapaxes(m_n, -1, -2), lam, precision=_HIGHEST)
    return back + d_n, (d_m, lam)
  d_s0, (d_m, d_b) = jax.lax.scan(step, d_last, (m, before, d_before),
                                  reverse=True)
  return d_m, d_b, d_s0


linear_state_scan.defvjp(_scan_fwd, _scan_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, seg, chunk: int = 64):
  """``q, k [B, L, H, dk]`` (``q`` scaled and ``k`` normalised by the
  caller), ``v [B, L, H, dv]``, ``g [B, L, H]`` the log of the decay,
  ``beta [B, L, H]``, ``seg [B, L]`` the document's number (it never falls)
  -> (``o [B, L, H, dv]``, the state after the last token
  ``[B, H, dk, dv]``). ``L`` need not be a multiple of ``chunk``."""
  with jax.named_scope(scopes.DELTA_RULE):
    return _chunked(q, k, v, g, beta, seg, chunk)


def chunk_kda_rule(q, k, v, g, beta, seg, chunk: int = 64):
  """:func:`chunk_gated_delta_rule` with ONE LOG-DECAY A KEY CHANNEL (Kimi
  Delta Attention): ``g [B, L, H, dk]``, never above 0, and
  ``S'_t = Diag(exp(g_t)) S_{t-1}``; everything else as there."""
  with jax.named_scope(scopes.DELTA_RULE):
    return _chunked_kda(q, k, v, g, beta, seg, chunk)


_mm = functools.partial(jnp.matmul, precision=_HIGHEST)


def _to_chunks(q, k, v, g, beta, seg, chunk):
  """The arguments in whole chunks, ``[B, N, H, C, ...]`` and never below
  float32 -> (``q, k, v, g, beta``, ``seg [B, N, C]``, the document of the
  token before each, whether a token is its document's first
  ``[B, N, 1, C]``)."""
  b, length = seg.shape
  n = -(-length // chunk)
  pad = n * chunk - length
  if pad:
    # a padded token writes nothing (k, beta 0), decays nothing (g 0) and
    # belongs to the last document
    tail = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    q, k, v, g, beta = (tail(x) for x in (q, k, v, g, beta))
    seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
  dt = jnp.promote_types(q.dtype, jnp.float32)   # never below float32
  # [B, N, H, C, ...]
  heads = lambda x: jnp.moveaxis(
      x.reshape((b, n, chunk) + x.shape[2:]), 3, 2).astype(dt)
  q, k, v, g, beta = (heads(x) for x in (q, k, v, g, beta))
  seg = seg.reshape(b, n, chunk)
  before = jnp.pad(seg.reshape(b, -1), ((0, 0), (1, 0)),
                   constant_values=-1)[:, :-1].reshape(b, n, chunk)
  reset = (seg != before)[:, :, None, :]                   # [B, N, 1, C]
  return (q, k, v, g, beta), seg, before, reset


def _masks(seg, before):
  """The resets as masks: a pair ``(i, j <= i)`` counts inside one document
  ``[B, N, 1, C, C]``; the incoming state reaches a token with no reset at
  or before it in the chunk; a token reaches the outgoing state with no
  reset after it (both ``[B, N, 1, C]``)."""
  chunk = seg.shape[-1]
  same = (seg[..., :, None] == seg[..., None, :])[:, :, None]
  incoming = (seg == before[..., :1])[:, :, None, :]
  outgoing = (seg == seg[..., -1:])[:, :, None, :]
  lower = jnp.tril(jnp.ones((chunk, chunk), bool))
  return same & lower, incoming, outgoing


def _chunked(q, k, v, g, beta, seg, chunk):
  # Op for op and in the order this function has had since PR 33, so that a
  # model of the scalar rule compiles to the program it did
  # (`tools/step_recompute.py`'s `program_sha`); the per-channel rule below
  # shares what comes before the products and the scan
  b, length, h, dk = q.shape
  dv = v.shape[-1]
  n = -(-length // chunk)
  (q, k, v, g, beta), seg, before, reset = _to_chunks(
      q, k, v, g, beta, seg, chunk)
  mm, dt = _mm, q.dtype
  gamma = jnp.cumsum(jnp.where(reset, 0.0, g), axis=-1)    # [B, N, H, C]
  pair, incoming, outgoing = _masks(seg, before)
  # e^{gamma_i - gamma_j}, j <= i: the exponent is masked before exp, whose
  # other half would overflow
  decay = jnp.where(pair, jnp.exp(jnp.where(
      pair, gamma[..., :, None] - gamma[..., None, :], 0.0)), 0.0)
  k_t = jnp.swapaxes(k, -1, -2)
  a = beta[..., None] * decay * mm(k, k_t) * jnp.tril(
      jnp.ones((chunk, chunk), dt), -1)
  into = jnp.where(incoming, jnp.exp(gamma), 0.0)          # e^{gamma_i}
  rhs = jnp.concatenate([beta[..., None] * v,
                         (beta * into)[..., None] * k], axis=-1)
  # (I + A) X = rhs: the diagonal is taken as 1 and never read
  solved = jax.lax.linalg.triangular_solve(
      a, rhs, left_side=True, lower=True, unit_diagonal=True)
  u, wk = solved[..., :dv], solved[..., dv:]               # [.., C, dv|dk]
  p = decay * mm(q, k_t)
  kd_t = jnp.swapaxes(k * jnp.where(
      outgoing, jnp.exp(gamma[..., -1:] - gamma), 0.0)[..., None], -1, -2)
  # the incoming state outlives a chunk that holds no reset
  carried = jnp.where(incoming[..., -1], jnp.exp(gamma[..., -1]), 0.0)
  m = carried[..., None, None] * jnp.eye(dk, dtype=dt) - mm(kd_t, wk)
  states, last = linear_state_scan(
      jnp.moveaxis(m, 1, 0), jnp.moveaxis(mm(kd_t, u), 1, 0),
      jnp.zeros((b, h, dk, dv), dt))
  states = jnp.moveaxis(states, 0, 1)                      # [B, N, H, dk, dv]
  o = mm(p, u) + mm(into[..., None] * q - mm(p, wk), states)
  o = jnp.moveaxis(o, 2, 3).reshape(b, n * chunk, h, dv)[:, :length]
  return o, last


# tokens of a sub-block of the per-channel rule's chunk: between two
# sub-blocks the decay factors into a matmul, inside one it is taken pair by
# pair, ``[SUB, SUB, dk]`` a sub-block
SUB = 16


@jax.checkpoint
def _decayed_products(q, k, gamma):
  """``sum_c x_ic k_jc e^{gamma_ic - gamma_jc}`` for ``x = k`` and ``x = q``
  and every pair ``j <= i`` of a chunk (the others hold no meaning: the
  caller masks them): ``q, k, gamma [.., C, dk]``, ``gamma`` never rising
  along ``C`` -> two ``[.., C, C]``.

  ``e^{-gamma}`` may not be formed: at a decay of a few nats a token it
  overflows inside a chunk. Between sub-blocks of ``SUB`` tokens the LATER
  block's first token is the reference of both factors (as ``fla``'s
  ``chunk_kda``): ``e^{gamma_i - ref}`` on the later block's rows and
  ``e^{ref - gamma_j}`` on every earlier token, neither exponent above 0,
  and the sum over channels is a matmul. Inside a sub-block the exponent
  ``gamma_i - gamma_j`` is formed pair by pair. Rematerialised: its
  backward rebuilds the factors from ``q, k, gamma`` and keeps neither the
  ``[nb, C, dk]`` factors nor the ``[SUB, SUB, dk]`` pairs."""
  lead, (chunk, dk) = gamma.shape[:-2], gamma.shape[-2:]
  sub = SUB if chunk % SUB == 0 else chunk
  nb = chunk // sub
  blocks = lambda x: x.reshape(lead + (nb, sub, dk))
  gs = blocks(gamma)
  ref = gs[..., :1, :]                                     # [.., nb, 1, dk]
  rows = jnp.exp(gs - ref)
  # a token before block ``i``'s first: e^{ref_i - gamma_j}
  earlier = (jnp.arange(chunk)[None, :]
             < sub * jnp.arange(nb)[:, None])[..., None]   # [nb, C, 1]
  cols = jnp.where(earlier, jnp.exp(jnp.where(
      earlier, ref - gamma[..., None, :, :], 0.0)), 0.0) * k[..., None, :, :]
  between = _mm(jnp.concatenate([blocks(k) * rows, blocks(q) * rows],
                                axis=-2), jnp.swapaxes(cols, -1, -2))
  # inside a sub-block: [.., nb, sub, sub, dk] summed over the channels
  lower = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
  inside = blocks(k)[..., None, :, :] * jnp.exp(jnp.where(
      lower, gs[..., :, None, :] - gs[..., None, :, :], 0.0))
  eye = jnp.eye(nb, dtype=gamma.dtype)[:, None, :, None]

  def whole(between, x):
    within = jnp.sum(blocks(x)[..., :, None, :] * inside, axis=-1)
    return between.reshape(lead + (chunk, chunk)) + (
        within[..., :, :, None, :] * eye).reshape(lead + (chunk, chunk))
  return whole(between[..., :sub, :], k), whole(between[..., sub:, :], q)


def _chunked_kda(q, k, v, g, beta, seg, chunk):
  b, length, h, dk = q.shape
  dv = v.shape[-1]
  (q, k, v, g, beta), seg, before, reset = _to_chunks(
      q, k, v, g, beta, seg, chunk)
  n, dt = seg.shape[1], q.dtype
  # [B, N, H, C, dk]; g at a reset is never read
  gamma = jnp.cumsum(jnp.where(reset[..., None], 0.0, g), axis=-2)
  pair, incoming, outgoing = _masks(seg, before)
  kk, qk = _decayed_products(q, k, gamma)
  strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
  a = beta[..., None] * jnp.where(pair & strict, kk, 0.0)
  p = jnp.where(pair, qk, 0.0)
  into = jnp.where(incoming[..., None], jnp.exp(gamma), 0.0)   # e^{gamma_i}
  rhs = jnp.concatenate([beta[..., None] * v,
                         beta[..., None] * into * k], axis=-1)
  # (I + A) X = rhs: the diagonal is taken as 1 and never read
  solved = jax.lax.linalg.triangular_solve(
      a, rhs, left_side=True, lower=True, unit_diagonal=True)
  u, wk = solved[..., :dv], solved[..., dv:]               # [.., C, dv|dk]
  kd_t = jnp.swapaxes(k * jnp.where(
      outgoing[..., None], jnp.exp(gamma[..., -1:, :] - gamma), 0.0), -1, -2)
  # the incoming state outlives a chunk that holds no reset, a factor a
  # key channel: Diag(e^{gamma_C})
  carried = jnp.where(incoming[..., -1:], jnp.exp(gamma[..., -1, :]), 0.0)
  m = carried[..., None] * jnp.eye(dk, dtype=dt) - _mm(kd_t, wk)
  states, last = linear_state_scan(
      jnp.moveaxis(m, 1, 0), jnp.moveaxis(_mm(kd_t, u), 1, 0),
      jnp.zeros((b, h, dk, dv), dt))
  states = jnp.moveaxis(states, 0, 1)                      # [B, N, H, dk, dv]
  o = _mm(p, u) + _mm(into * q - _mm(p, wk), states)
  o = jnp.moveaxis(o, 2, 3).reshape(b, n * chunk, h, dv)[:, :length]
  return o, last
