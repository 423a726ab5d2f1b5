"""Fused Pallas DLRM pairwise-interaction kernels (round 5).

TPU equivalent of the reference's dot-interaction
(`examples/dlrm/utils.py:92-113`), replacing the XLA matmul-form pair
(`models/dlrm.py:_tril_products`) on the hot path. Motivation (traced,
`tools/trace_dlrm.py`, B=64k, F=27, D=128): XLA lowers the per-sample
product einsum "bpd,bqd->bpq" to a convolution that wants BATCH-MINOR
operand layouts, and the selection matmuls re-infect the graph with
row-major, so the step pays ~7.5 ms of pure [B,27,128]/[B,3456] layout
copies around ~5.7 ms of real work. These kernels consume feats in their
natural row-major layout and keep every intermediate (the [S,F,F] pair
products, the scattered selection cotangent) in VMEM, so the copies and
the HBM round-trip of `inter` vanish entirely. Measured (round 5):
single-flat-input kernels standalone fwd 1.31 + bwd 1.80 ms
(`tools/proto_pallas_interact.py`, B=64k); the production per-part
variants in the real step trace run fwd 2.47 + bwd 4.04 ms (the VMEM
concat/split costs ~1/2 ms) but delete ALL surrounding copies — the
DLRM interaction block fell ~13.2 -> ~6.5 ms and the whole step
52.3 -> 44.1 ms, taking f32 to ~1.19x and AMP to 1.08-1.18x of the
per-A100 baselines (docs/BENCHMARKS.md).

Shapes/limits (guarded by `use_pallas_interact`):
  * feats [B, F, D] bfloat16, D % 128 == 0, F <= 32 (F pads to one
    sublane tile; the selection constants pad F*F lanes to 128-multiples)
  * B % block == 0 (block = 256 fwd / 128 bwd)
  * Mosaic cannot shape-cast [S,F,F] -> [S,F*F], so the selection matmul
    unrolls over the p axis (F small matmuls against M[p] slices) and the
    backward scatters the cotangent through an f32 VMEM scratch
    (bf16 [S,1,F] stores are an unsupported shape cast; f32 works).

The selection tensor M is `models.dlrm._tril_select_np`'s half-weight
symmetric form: acts == einsum("bpd,bqd,pqn->bn", feats, feats, M) and
d_feats == 2 * einsum("bn,pqn,bqd->bpd", d_acts, M, feats) exactly (the
kernels run the same one-bf16-pass MXU products as the XLA form under
DEFAULT matmul precision — same precision class, docs/BENCHMARKS.md).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

FWD_BLOCK = 256
BWD_BLOCK = 128

# the kernels' names in HLO (the Mosaic custom calls) and in device traces
PARTS_FWD_NAME = "de_interact_parts_fwd"
PARTS_BWD_NAME = "de_interact_parts_bwd"
FWD_NAME = "de_interact_fwd"
BWD_NAME = "de_interact_bwd"


def use_pallas_interact(b: int, f: int, d: int, dtype) -> bool:
  """Static (trace-time) gate for the fused interaction kernels."""
  if os.environ.get("DE_TPU_PALLAS_INTERACT", "1") != "1":
    return False
  if dtype != jnp.bfloat16:
    return False  # jax_default_matmul_precision=float32 keeps the XLA form
  if f < 2 or f > 32 or d % 128 != 0 or f * d > 4096:
    return False  # f=1 with k=-1 has zero pairs: XLA handles the empty einsum
  if b % FWD_BLOCK != 0 or b % BWD_BLOCK != 0:
    return False
  return jax.default_backend() == "tpu"


def xla_reference(flat: jax.Array, m_np, f: int) -> jax.Array:
  """Explicit XLA einsum form of the interaction — the independent
  reference for the kernels (used by tests/test_pallas_interact.py and
  tools/smoke_pallas_interact.py). Deliberately NOT `_tril_products`:
  that entry dispatches to the flat-input kernel on TPU, and a
  kernel-vs-kernel comparison would hide a shared miscompile."""
  b = flat.shape[0]
  d = flat.shape[1] // f
  feats = flat.reshape(b, f, d)
  m = jnp.asarray(m_np, jnp.bfloat16)
  inter = jnp.einsum("bpd,bqd->bpq", feats, feats,
                     preferred_element_type=jnp.float32)
  return jnp.einsum("bpq,pqn->bn", inter.astype(jnp.bfloat16), m,
                    preferred_element_type=jnp.float32)


def _acts_of(x, m_ref, f, npair):
  """Shared fwd body: [S, F, D] feats -> [S, npair] f32 activations."""
  inter = jax.lax.dot_general(
      x, x, (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)  # [S, F, F] in VMEM only
  i16 = inter.astype(jnp.bfloat16)
  acc = jnp.zeros((x.shape[0], npair), jnp.float32)
  for p in range(f):
    acc = acc + jnp.dot(i16[:, p, :], m_ref[p],
                        preferred_element_type=jnp.float32)
  return acc


def _dfeats_of(da, x, mt_ref, dsym_ref, f):
  """Shared bwd body: cotangent scatter through the f32 dsym scratch, then
  one batched MXU dot -> [S, F, D] f32 (caller applies the factor 2)."""
  for p in range(f):
    row = jnp.dot(da, mt_ref[p], preferred_element_type=jnp.float32)
    dsym_ref[:, pl.dslice(p, 1), :] = row[:, None, :]
  return jax.lax.dot_general(
      dsym_ref[...].astype(jnp.bfloat16), x, (((2,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)


def _fwd_kernel(f, npair, m_ref, feats_ref, acts_ref):
  acts_ref[...] = _acts_of(feats_ref[...], m_ref, f, npair)


def _bwd_kernel(f, mt_ref, dacts_ref, feats_ref, dfeats_ref, dsym_ref):
  da = dacts_ref[...].astype(jnp.bfloat16)  # [S, npair]
  d = _dfeats_of(da, feats_ref[...], mt_ref, dsym_ref, f)
  dfeats_ref[...] = (2.0 * d).astype(dfeats_ref.dtype)


def _parts_fwd_kernel(f, npair, m_ref, *refs):
  # refs = f part refs, acts_ref
  acts_ref = refs[-1]
  x = jnp.concatenate(
      [refs[p][...][:, None, :] for p in range(f)], axis=1)  # [S, F, D]
  acts_ref[...] = _acts_of(x, m_ref, f, npair)


def _parts_bwd_kernel(f, mt_ref, dacts_ref, *refs):
  # refs = f part refs, then f cotangent out refs; scratch dsym last
  dsym_ref = refs[-1]
  part_refs = refs[:f]
  out_refs = refs[f:2 * f]
  da = dacts_ref[...].astype(jnp.bfloat16)
  x = jnp.concatenate(
      [part_refs[p][...][:, None, :] for p in range(f)], axis=1)
  d = _dfeats_of(da, x, mt_ref, dsym_ref, f)
  for p in range(f):
    out_refs[p][...] = (2.0 * d[:, p, :]).astype(out_refs[p].dtype)


def interact_parts_fwd(parts, m3: jax.Array,
                       interpret: bool = False) -> jax.Array:
  """f x [B, D] bf16 parts -> [B, P] f32 pair activations.

  The per-table slices enter in their natural row-major layout and the
  feature concat happens in VMEM — the XLA-level lane concat's B-minor
  layout oscillation (~5.9 ms of copies at B=64k, traced) never exists.
  """
  f = len(parts)
  b, d = parts[0].shape
  npair = m3.shape[-1]
  return pl.pallas_call(
      functools.partial(_parts_fwd_kernel, f, npair),
      grid=(b // FWD_BLOCK,),
      in_specs=[pl.BlockSpec((f, f, npair), lambda i: (0, 0, 0))] + [
          pl.BlockSpec((FWD_BLOCK, d), lambda i: (i, 0)) for _ in range(f)
      ],
      out_specs=pl.BlockSpec((FWD_BLOCK, npair), lambda i: (i, 0)),
      out_shape=out_struct((b, npair), jnp.float32, parts),
      interpret=interpret,
      name=PARTS_FWD_NAME,
  )(m3, *parts)


def interact_parts_bwd(d_acts: jax.Array, parts, m3t: jax.Array,
                       interpret: bool = False):
  """[B, P] cotangent -> per-part [B, D] bf16 cotangents (split in VMEM)."""
  f = len(parts)
  b, d = parts[0].shape
  npair = m3t.shape[1]
  outs = pl.pallas_call(
      functools.partial(_parts_bwd_kernel, f),
      grid=(b // BWD_BLOCK,),
      in_specs=[
          pl.BlockSpec((f, npair, f), lambda i: (0, 0, 0)),
          pl.BlockSpec((BWD_BLOCK, npair), lambda i: (i, 0)),
      ] + [
          pl.BlockSpec((BWD_BLOCK, d), lambda i: (i, 0)) for _ in range(f)
      ],
      out_specs=[
          pl.BlockSpec((BWD_BLOCK, d), lambda i: (i, 0)) for _ in range(f)
      ],
      out_shape=[out_struct((b, d), jnp.bfloat16, d_acts, parts)
                 for _ in range(f)],
      scratch_shapes=[pltpu.VMEM((BWD_BLOCK, f, f), jnp.float32)],
      interpret=interpret,
      name=PARTS_BWD_NAME,
  )(m3t, d_acts, *parts)
  return tuple(outs)


def interact_fwd(feats: jax.Array, m3: jax.Array,
                 interpret: bool = False) -> jax.Array:
  """[B, F, D] bf16 feats x M [F, F, P] -> [B, P] f32 pair activations."""
  b, f, d = feats.shape
  npair = m3.shape[-1]
  return pl.pallas_call(
      functools.partial(_fwd_kernel, f, npair),
      grid=(b // FWD_BLOCK,),
      in_specs=[
          pl.BlockSpec((f, f, npair), lambda i: (0, 0, 0)),
          pl.BlockSpec((FWD_BLOCK, f, d), lambda i: (i, 0, 0)),
      ],
      out_specs=pl.BlockSpec((FWD_BLOCK, npair), lambda i: (i, 0)),
      out_shape=out_struct((b, npair), jnp.float32, feats),
      interpret=interpret,
      name=FWD_NAME,
  )(m3, feats)


def interact_bwd(d_acts: jax.Array, feats: jax.Array,
                 m3t: jax.Array, interpret: bool = False) -> jax.Array:
  """[B, P] cotangent x feats -> [B, F, D] bf16 feature cotangent."""
  b, f, d = feats.shape
  npair = m3t.shape[1]
  return pl.pallas_call(
      functools.partial(_bwd_kernel, f),
      grid=(b // BWD_BLOCK,),
      in_specs=[
          pl.BlockSpec((f, npair, f), lambda i: (0, 0, 0)),
          pl.BlockSpec((BWD_BLOCK, npair), lambda i: (i, 0)),
          pl.BlockSpec((BWD_BLOCK, f, d), lambda i: (i, 0, 0)),
      ],
      out_specs=pl.BlockSpec((BWD_BLOCK, f, d), lambda i: (i, 0, 0)),
      out_shape=out_struct((b, f, d), jnp.bfloat16, d_acts, feats),
      scratch_shapes=[pltpu.VMEM((BWD_BLOCK, f, f), jnp.float32)],
      interpret=interpret,
      name=BWD_NAME,
  )(m3t, d_acts, feats)
