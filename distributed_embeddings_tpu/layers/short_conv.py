"""The double-gated short convolution: a token mixer that is neither
attention nor a recurrence, over sequences in which several documents are
packed.

From the mixer's normalised input ``h [B, L, d]``, with ``W_in [d, 3 d]``
and ``W_out [d, d]`` without bias:

    [B, C, u] = split3(h W_in)
    z         = B * u                                  the input gate
    c_t       = sum_j w_j * z_{t - (K-1) + j}          per channel, K taps
    y         = (C * c) W_out                          the output gate

``w [K, d]`` is a depthwise causal convolution (``w[K-1]`` multiplies the
token itself; the published ``conv_L_cache`` is ``K``, 3): a tap that would
read before its document's first token reads 0
(:func:`..layers.gated_delta.causal_conv`, whose reset it is). No activation
inside: the two gates are the nonlinearity.

Two products bound by the MXU (:func:`..layers.dense.mxu_dot`: on a TPU
handed bfloat16 operands, float32 out of both passes) round an elementwise
chain bound by memory: at the least three reads and a write of ``[T, d]``
float32 forward, four reads and three writes backward. The product ``h W_in`` is named for the caller's
rematerialisation plan (``layers/remat.py::SHORT_CONV_IN``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import scopes
from .dense import mxu_dot
from .gated_delta import causal_conv
from .remat import SHORT_CONV_IN


def gate_chain(bcu, w, seg):
  """``bcu [B, L, 3 d]`` (``B``, ``C``, ``u`` side by side), ``w [K, d]``,
  ``seg [B, L]`` the document of each position -> ``C * conv(B * u)``,
  ``[B, L, d]``."""
  gate_in, gate_out, u = jnp.split(bcu, 3, axis=-1)
  return gate_out * causal_conv(gate_in * u, w, seg)


def short_conv_mixer(p, h, seg):
  """One mixer on its normalised input ``h [B, L, d]`` with its parameters
  ``p`` (``w_in [d, 3 d]``, ``conv [K, d]``, ``w_out [d, d]``) ->
  ``[B, L, d]``."""
  with jax.named_scope(scopes.CONV_PROJ):
    bcu = checkpoint_name(mxu_dot(h, p["w_in"]), SHORT_CONV_IN)
  with jax.named_scope(scopes.CONV_GATE):
    g = gate_chain(bcu, p["conv"], seg)
  with jax.named_scope(scopes.CONV_PROJ):
    return mxu_dot(g, p["w_out"])
