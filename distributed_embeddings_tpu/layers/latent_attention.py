"""Multi-head latent attention on the training path: queries, keys and values
that pass through a low-rank latent with a norm in the middle, and a key made
of a part of its own a head and a rotary part that every head shares.

On the normalised input ``h [B, S, d]`` of a layer, with the leaves ``p``:

  ``c_q  = rms(h W_dq; q_a_norm)``                       ``[B, S, q_rank]``
  ``[q_n ; q_r] = c_q W_uq`` a head                      ``[B, S, H, nope + rope]``
  ``[c_kv ; k_r] = h W_dkv``                             ``[B, S, kv_rank + rope]``
  ``c_kv = rms(c_kv; kv_a_norm)``
  ``[k_n ; v] = c_kv W_ukv`` a head                      ``[B, S, H, nope + v]``

``k_r`` is ONE head of ``rope`` dimensions, the same for all ``H``. RoPE
(:func:`.attention.rope`, rotate-half over all ``rope`` dimensions) turns
``q_r`` of every head and ``k_r``; ``q_n``, ``k_n`` and ``v`` get none.
``q = [q_n ; rope(q_r)] / sqrt(nope + rope)``, ``k = [k_n ; rope(k_r)]`` with
the shared part broadcast to every head, and the attention proper is
:mod:`.attention`'s pair at ``[B, S, H, nope + rope]`` (multi-head layout)
under ``Causal()`` with the documents as segment ids; ``W_o`` takes the
``H x v`` outputs back to ``d``. No biases. This is the EXPANDED form, what
the published modelling code runs outside decoding; the absorbed form (the
latent itself as the key of every head) is serving's.

Every product is :func:`.dense.mxu_dot`. The two down products' results, the
raw latents, are named ``MLA_LATENTS`` (:mod:`.remat`): a rematerialised layer
rebuilds the latent norms, the up products and the rotary pass from them and
runs neither down product again.

Scopes (inside the caller's ``de_attention``): the down products and the two
latent norms under ``de_mla_down``; the up products, the split, the broadcast
and the join of the shared key under ``de_mla_up``; the rotary pass and the
scaling under ``de_attn_qk``; the kernel's call under ``de_attn_core``;
``W_o`` under ``de_attn_proj``.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import scopes
from .attention import Causal, rope, rope_frequencies
from .decoder import rms_norm
from .dense import mxu_dot
from .remat import MLA_LATENTS


@dataclasses.dataclass(frozen=True)
class LatentShapes:
  """The sizes of a latent-attention mixer, as a config publishes them."""
  heads: int            # num_attention_heads, all held here
  q_rank: int           # q_lora_rank
  kv_rank: int          # kv_lora_rank
  nope: int             # qk_nope_head_dim: a head's own part of q and k
  rope: int             # qk_rope_head_dim: the rotary part, shared by k's heads
  v: int                # v_head_dim
  eps: float            # rms_norm_eps, of the two latent norms
  theta: float          # rope_theta

  def leaves(self, hidden: int):
    """name -> (shape, ``matrix`` or ``gain``) of the mixer's parameters."""
    h = self.heads
    return {"w_dq": ((hidden, self.q_rank), "matrix"),
            "q_a_norm": ((self.q_rank,), "gain"),
            "w_uq": ((self.q_rank, h * (self.nope + self.rope)), "matrix"),
            "w_dkv": ((hidden, self.kv_rank + self.rope), "matrix"),
            "kv_a_norm": ((self.kv_rank,), "gain"),
            "w_ukv": ((self.kv_rank, h * (self.nope + self.v)), "matrix"),
            "w_o": ((h * self.v, hidden), "matrix")}


def latent_attention(shapes: LatentShapes, p, h, positions, seg, attend):
  """``h [B, S, d]`` -> ``[B, S, d]``; ``positions [S]`` the rotary pass's,
  ``seg [B, S]`` the documents' numbers (or ``None``), ``attend`` one of
  :func:`.attention.attention_splash` / :func:`.attention.attention_xla`."""
  b, length, _ = h.shape
  heads, nope = shapes.heads, shapes.nope
  inv_freq = rope_frequencies(shapes.theta, shapes.rope)

  with jax.named_scope(scopes.MLA_DOWN):
    c_q = checkpoint_name(mxu_dot(h, p["w_dq"]), MLA_LATENTS)
    c_kv = checkpoint_name(mxu_dot(h, p["w_dkv"]), MLA_LATENTS)
    c_q = rms_norm(c_q, p["q_a_norm"], shapes.eps)
    c_kv, k_r = c_kv[..., :shapes.kv_rank], c_kv[..., shapes.kv_rank:]
    c_kv = rms_norm(c_kv, p["kv_a_norm"], shapes.eps)
  with jax.named_scope(scopes.MLA_UP):
    q = mxu_dot(c_q, p["w_uq"]).reshape(b, length, heads, nope + shapes.rope)
    kv = mxu_dot(c_kv, p["w_ukv"]).reshape(b, length, heads, nope + shapes.v)
    q_n, q_r = q[..., :nope], q[..., nope:]
    k_n, v = kv[..., :nope], kv[..., nope:]
  with jax.named_scope(scopes.ATTN_QK):
    scale = (nope + shapes.rope) ** -0.5
    q_n, q_r = q_n * scale, rope(q_r, positions, inv_freq) * scale
    k_r = rope(k_r[:, :, None, :], positions, inv_freq)     # one head
  with jax.named_scope(scopes.MLA_UP):
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, (b, length, heads, shapes.rope))],
        axis=-1)
  with jax.named_scope(scopes.ATTN_CORE):
    a = attend(q, k, v, Causal(), seg)
  with jax.named_scope(scopes.ATTN_PROJ):
    return mxu_dot(a.reshape(b, length, heads * shapes.v), p["w_o"])
