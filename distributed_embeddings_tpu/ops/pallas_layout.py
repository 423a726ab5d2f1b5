"""Layout pinning via a Pallas identity copy (round 5).

XLA's layout assignment keeps the sparse cotangent pipeline batch-minor
(the model backward's convolution-form matmuls prefer it) and only
transposes to row-major at the scatter's operand — i.e. at the EXPANDED
per-occurrence delta stream, after the hotness broadcast and the window
expansion have multiplied the bytes ~17x (Tiny: ~9 ms/step of
[1.4M, 128] {0,1}->{1,0} copies in a round-5 device trace; what the zoo
cell's trace reads of them now: PERF.md section 5).

`row_major(x)` forces a tensor into default row-major layout at a chosen
point: pallas_call operands and results use default layouts, so an
identity kernel is a layout pin the JAX API does not otherwise offer.
Pinning the small per-sample cotangent re-anchors everything downstream
(broadcasts, window expansion, delta math are elementwise and follow
their input layout) and the scatter-side copies vanish at ~17x less
copy traffic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_util import out_struct

_MAX_BLOCK_ELEMS = 1 << 19  # ~2 MiB f32 per block INCLUDING tile padding


def _id_kernel(x_ref, o_ref):
  o_ref[...] = x_ref[...]


def row_major(x: jax.Array) -> jax.Array:
  """Identity that pins ``x`` to default (row-major) layout on TPU.

  Blocks over the sublane (second-to-last) dim with leading dims at 1,
  sizing by the PADDED block (last dim pads to 128 lanes, sublanes to 8 —
  a [1, S, 8] f32 block is S x 128 x 4 bytes in VMEM, not S x 8 x 4).
  No-op off-TPU or when no even blocking fits the budget (the pin is an
  optimization, never a semantic requirement)."""
  if jax.default_backend() != "tpu":
    return x
  if x.ndim < 2 or x.size == 0:
    return x
  nd = x.ndim
  sub = x.shape[-2]
  last = x.shape[-1]
  plast = -(-last // 128) * 128
  s = min(sub, max(1, _MAX_BLOCK_ELEMS // plast))
  if s >= 8:
    s -= s % 8
  while s > 1 and sub % s:
    s -= 1
  spad = -(-s // 8) * 8
  if sub % s or spad * plast > _MAX_BLOCK_ELEMS:
    return x
  block = (1,) * (nd - 2) + (s, last)
  grid = tuple(x.shape[:nd - 2]) + (sub // s,)

  def imap(*idx):
    return idx[:nd - 2] + (idx[-1], 0)

  return pl.pallas_call(
      _id_kernel,
      grid=grid,
      in_specs=[pl.BlockSpec(block, imap)],
      out_specs=pl.BlockSpec(block, imap),
      out_shape=out_struct(x.shape, x.dtype, x),
      name="de_row_major_pin",
  )(x)
