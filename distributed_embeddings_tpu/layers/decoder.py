"""What the decoders of this package share beside their attention
(:mod:`.attention`): the norm, what a packed batch's documents are (the
batch's numerical features are ``L`` uniforms in [0, 1) a sequence; position 0
starts a document, and position ``i > 0`` one where
``u_i < 1 / mean_document_length``), and the loss over them.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
  """In float32 at least (a float64 test stays float64)."""
  dt = jnp.promote_types(x.dtype, jnp.float32)
  wide = x.astype(dt)
  var = jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
  return (wide * jax.lax.rsqrt(var + eps) * gain.astype(dt)).astype(x.dtype)


def segment_ids(starts):
  """``starts [B, L]`` bool, true at a document's first token (position 0
  always is one) -> the document's number at every position, ``[B, L]``
  int32, from 0."""
  starts = starts.at[:, 0].set(True)
  return jnp.cumsum(starts.astype(jnp.int32), axis=1) - 1


def document_segments(numerical, mean_document_length: int):
  """The batch's numerical features ``[B, L]`` -> the document's number at
  every position, ``[B, L]`` int32."""
  return segment_ids(numerical < 1.0 / mean_document_length)


def next_token_loss(outputs, labels):
  """Mean over the positions that are not a document's last of
  ``CE(logits_t, targets_t)``: ``outputs["logits"] [B, L, V]``,
  ``outputs["weight"] [B, L]`` (1 where the next token is the same
  document's), ``labels["targets"] [B, L]`` the ids shifted by one."""
  logits = outputs["logits"]
  logits = logits.astype(jnp.promote_types(logits.dtype, jnp.float32))
  logp = jax.nn.log_softmax(logits, axis=-1)
  nll = -jnp.take_along_axis(logp, labels["targets"][..., None], -1)[..., 0]
  weight = outputs["weight"]
  return jnp.sum(weight * nll) / jnp.maximum(jnp.sum(weight), 1.0)
