"""DLRM (Deep Learning Recommendation Model), TPU-native.

Functional equivalent of the reference example model
(`/root/reference/examples/dlrm/main.py:76-147` and ``dot_interact`` in
`/root/reference/examples/dlrm/utils.py:92-113`): bottom MLP over numerical
features, embeddings over categorical features (hybrid-parallel via
``DistributedEmbedding`` when world > 1), pairwise dot-product feature
interaction (lower triangle), top MLP to one logit.

TPU notes: the interaction is a [B, F, D] x [B, D, F] batched matmul — MXU
work — and the lower-triangle selection uses a static gather index (no
boolean_mask / dynamic shapes). ``compute_dtype=bfloat16`` runs the MLPs and
interaction in bf16 with fp32 params/accumulation (the AMP configuration of
the reference's headline benchmark).
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..layers.dist_model_parallel import DistributedEmbedding
from ..layers.embedding import TableConfig
from ..ops.packed_table import mxu_operand_dtype as _mxu_operand_dtype
from ..ops.pallas_interact import (
    interact_parts_bwd,
    interact_parts_fwd,
    use_pallas_interact,
)
from ..telemetry import scopes


class MLP(nn.Module):
  features: Sequence[int]
  activate_final: bool = False
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x):
    for i, width in enumerate(self.features):
      x = nn.Dense(width, dtype=self.dtype, name=f"dense_{i}")(x)
      if i < len(self.features) - 1 or self.activate_final:
        x = nn.relu(x)
    return x


@functools.lru_cache(maxsize=None)
def _tril_select_np(f: int, k: int):
  """Half-weight symmetric selection tensor ``M [f, f, p]``.

  ``einsum("bpq,pqn->bn", inter, M)`` extracts the lower-triangle pairs
  from the full pairwise product: both mirrored cells carry weight 0.5
  (diagonal pairs 1.0), and ``inter`` is bitwise symmetric (each mirrored
  pair is the same dot product with the same reduction order), so
  ``0.5*a + 0.5*a`` reproduces the pair value exactly. The selection is a
  matmul — MXU work — instead of the flat ``jnp.take`` an index map needs,
  whose lane-crossing gather + reshape cost ~4 ms of relayout copies per
  step at F=27, B=64k (traced round 4)."""
  rows, cols = np.tril_indices(f, k=k)
  p = len(rows)
  m = np.zeros((f, f, p), np.float32)
  for n, (i, j) in enumerate(zip(rows, cols)):
    if i == j:  # self-interaction diagonal: single cell, full weight
      m[i, j, n] = 1.0
    else:
      m[i, j, n] = 0.5
      m[j, i, n] = 0.5
  return m, p


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _tril_products(flat: jax.Array, f: int, k: int) -> jax.Array:
  """Flat ``[B, F*D]`` features -> [B, P] lower-triangle pairwise dots.

  Takes the lane-concatenated flat array (reshaped to [B, F, D]
  internally — see _tril_fwd's layout note) with ``f`` static.

  Both directions are pure matmuls (no gathers, no index maps): forward is
  the pairwise product einsum followed by the ``M``-selection einsum; the
  hand-written VJP exploits the symmetry of the selection cotangent
  (``d_sym = einsum(d_acts, M)`` is symmetric by construction) to compute
  ``d_feats = (G + G^T) @ feats`` as ONE product einsum scaled by 2, where
  XLA's autodiff would run two. Equivalent of the reference's
  ``boolean_mask`` interaction (`examples/dlrm/utils.py:92-113`)."""
  out, _ = _tril_fwd(flat, f, k)
  return out


def _tril_fwd(flat, f, k):
  # the [B, F*D] -> [B, F, D] reshape lives INSIDE the custom-vjp
  # boundary: placed outside, XLA's layout assignment round-trips the
  # concat through a {0,1} layout and back (~2.7 ms/step of copies at
  # F=27, B=64k, traced round 4)
  b = flat.shape[0]
  d = flat.shape[1] // f
  feats = flat.reshape(b, f, d)
  m_np, _ = _tril_select_np(f, k)
  cd = _mxu_operand_dtype(feats.dtype)
  m = jnp.asarray(m_np, cd)
  inter = jnp.einsum("bpd,bqd->bpq", feats.astype(cd), feats.astype(cd),
                     preferred_element_type=jnp.float32)
  acts = jnp.einsum("bpq,pqn->bn", inter.astype(cd), m,
                    preferred_element_type=jnp.float32)
  return acts, feats


def _tril_bwd(f, k, feats, d_acts):
  b, _, d = feats.shape
  m_np, _ = _tril_select_np(f, k)
  # under bf16 compute (AMP) the cotangent is rounded to bf16 before the
  # grad einsums — the AMP convention (the reference's fp16 backward does
  # the same); on-TPU f32 parity with autodiff holds because DEFAULT MXU
  # precision rounds einsum operands to bf16 either way (_mxu_operand_dtype)
  cd = _mxu_operand_dtype(feats.dtype)
  m = jnp.asarray(m_np, cd)
  d_sym = jnp.einsum("bn,pqn->bpq", d_acts.astype(cd), m,
                     preferred_element_type=jnp.float32)
  # d(F F^T) needs (G + G^T) @ F; d_sym = (G + G^T)/2 is symmetric by
  # construction (M weights both mirrored cells), so one einsum x2 does it
  d_feats = 2.0 * jnp.einsum("bqp,bqd->bpd", d_sym.astype(cd),
                             feats.astype(cd),
                             preferred_element_type=jnp.float32)
  return (d_feats.astype(feats.dtype).reshape(b, f * d),)


_tril_products.defvjp(_tril_fwd, _tril_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _pair_products_pallas(parts, f: int, k: int) -> jax.Array:
  """Per-part fused-kernel form of :func:`_tril_products` (bf16, TPU).

  Takes the f per-table [B, D] slices directly — no flat concat exists
  at the XLA level in either direction; inside the kernels the parts land
  in a sample-major VMEM scratch by strided stores and four samples (at
  f = 27) share one MXU tile (ops/pallas_interact.py; what it costs a
  step: PERF.md section 5, `interact_ms`)."""
  out, _ = _pair_fwd(parts, f, k)
  return out


def _pair_fwd(parts, f, k):
  return interact_parts_fwd(parts, _tril_select_np(f, k)[0]), parts


def _pair_bwd(f, k, parts, d_acts):
  return (interact_parts_bwd(d_acts, parts, _tril_select_np(f, k)[0]),)


_pair_products_pallas.defvjp(_pair_fwd, _pair_bwd)


@jax.named_scope(scopes.INTERACT)
def dot_interact(bottom_out: jax.Array, emb_outs: Sequence[jax.Array],
                 self_interaction: bool = False,
                 pack: int = 1) -> jax.Array:
  """Pairwise dot-product interaction + bottom-MLP passthrough.

  Equivalent of `examples/dlrm/utils.py:92-113`, with the dynamic
  ``boolean_mask`` replaced by the matmul-form triangle selection
  (:func:`_tril_products`). Output: [B, F*(F-1)/2 + D] where
  F = num embeddings + 1. On a TPU, with bfloat16 MXU operands and a batch
  the kernels' blocks divide, the parts go to the fused Pallas kernels
  instead (:func:`_pair_products_pallas`; what they cost a step:
  `interact_ms` in PERF.md section 5).

  ``pack`` is accepted for API compatibility and ignored: the matmul-form
  selection has no pack concept (the round-2 pack study measured pack=1
  fastest anyway — the product bytes grow pack^2).
  """
  if pack < 1:
    raise ValueError(f"pack must be >= 1, got {pack}")
  if pack > 1:
    # FutureWarning: shown under default filters (DeprecationWarning is
    # suppressed outside __main__, so library callers would never see it)
    warnings.warn(
        "dot_interact(pack>1) is ignored: the matmul-form selection has no "
        "pack concept (pack=1 measured fastest; product bytes grow pack^2)",
        FutureWarning, stacklevel=2)
  # 2-D lane-axis concat, then a row-major (free) reshape: the backward of
  # this build is F clean [B, D] lane-window slices, where a stack's
  # backward slices [B, 1, D] pieces in T(1,128) layouts (~3 ms/step of
  # relayout at F=27, B=64k, traced round 4)
  parts = [bottom_out] + list(emb_outs)
  b, d = parts[0].shape
  bad = [p.shape for p in parts if p.shape != (b, d)]
  if bad:  # the concat+reshape build would silently scramble lanes
    raise ValueError(
        f"dot_interact needs equal [B, D] features; got {bad} vs ({b}, {d})")
  # cast the einsum operands at the source (see _mxu_operand_dtype: a
  # numerics no-op for the products on TPU, where DEFAULT MXU precision
  # rounds operands to bf16 anyway) so the concat, its relayout copies,
  # and the backward split all move half the bytes. The casts' VJP
  # returns the feature cotangents in their original dtype; the one real
  # divergence is a single bf16 rounding of each cotangent value, within
  # the precision class the TF32 reference computes its backward in.
  cd = _mxu_operand_dtype(parts[0].dtype)
  k = 0 if self_interaction else -1
  if use_pallas_interact(b, len(parts), d, cd):
    # per-part kernel I/O: the slices keep their natural row-major layout
    # and are assembled and split in VMEM (ops/pallas_interact.py)
    activations = _pair_products_pallas(
        tuple(p.astype(cd) for p in parts), len(parts), k)
  else:
    flat = jnp.concatenate([p.astype(cd) for p in parts], axis=1)
    activations = _tril_products(flat, len(parts), k)
  return jnp.concatenate([activations, bottom_out.astype(activations.dtype)],
                         axis=1)


class DLRM(nn.Module):
  """DLRM with hybrid-parallel embeddings.

  Args:
    vocab_sizes: per categorical feature, its vocabulary size (26 for Criteo).
    embedding_dim: embedding width (128 for the MLPerf config).
    bottom_mlp / top_mlp: dense stack widths; top ends in 1 logit.
    world_size / strategy / column_slice_threshold / dp_input: forwarded to
      :class:`DistributedEmbedding`.
    compute_dtype: dtype for MLP/interaction compute (bf16 = AMP-equivalent).
  """

  vocab_sizes: Sequence[int]
  embedding_dim: int = 128
  bottom_mlp: Tuple[int, ...] = (512, 256, 128)
  top_mlp: Tuple[int, ...] = (1024, 1024, 512, 256, 1)
  world_size: int = 1
  strategy: str = "basic"
  column_slice_threshold: Optional[int] = None
  row_slice: Optional[int] = None
  dp_input: bool = True
  compute_dtype: Any = jnp.float32
  # small-vocab tables ride the MXU one-hot path (see planner); 4096 is
  # the measured crossover on v5e where the windowed one-hot matmul
  # (fwd + bwd) still beats gather + scatter-apply for a 65k batch
  dense_row_threshold: int = 4096
  # expected global batch (feeds the planner's scatter-regime cost model);
  # pass the same value to dlrm_embedding_plan for a matching plan
  batch_hint: Optional[int] = None

  def setup(self):
    if self.bottom_mlp[-1] != self.embedding_dim:
      raise ValueError(
          f"bottom MLP must end at embedding_dim ({self.embedding_dim}), "
          f"got {self.bottom_mlp}")
    tables = tuple(
        TableConfig(input_dim=int(v), output_dim=self.embedding_dim,
                    initializer=_dlrm_initializer(int(v)))
        for v in self.vocab_sizes)
    self.embeddings = DistributedEmbedding(
        embeddings=tables,
        strategy=self.strategy,
        column_slice_threshold=self.column_slice_threshold,
        row_slice=self.row_slice,
        dp_input=self.dp_input,
        world_size=self.world_size,
        dense_row_threshold=self.dense_row_threshold,
        batch_hint=self.batch_hint,
        name="embeddings")
    self.bottom = MLP(self.bottom_mlp, activate_final=True,
                      dtype=self.compute_dtype, name="bottom_mlp")
    self.top = MLP(self.top_mlp, dtype=self.compute_dtype, name="top_mlp")

  def __call__(self, numerical, categorical, emb_acts=None):
    """numerical [B, num_numerical]; categorical: list of [B] int ids (or
    the packed dict in mp-input mode). Returns [B] logits.

    ``emb_acts`` overrides the embedding lookup with precomputed activations
    (the sparse-gradient training path computes them outside autodiff; see
    ``training.make_sparse_train_step``).
    """
    bottom_out = self.bottom(numerical.astype(self.compute_dtype))
    emb_outs = emb_acts if emb_acts is not None \
        else self.embeddings(categorical)
    emb_outs = [e.astype(self.compute_dtype) for e in emb_outs]
    x = dot_interact(bottom_out, emb_outs)
    logit = self.top(x.astype(self.compute_dtype))
    return jnp.squeeze(logit, -1).astype(jnp.float32)


def dlrm_embedding_plan(vocab_sizes, embedding_dim: int = 128,
                        world_size: int = 1, strategy: str = "basic",
                        column_slice_threshold: Optional[int] = None,
                        dense_row_threshold: int = 4096,
                        row_slice: Optional[int] = None,
                        batch_hint: Optional[int] = None):
  """The placement plan a :class:`DLRM`'s embeddings use (for
  get_weights/set_weights on the ``embeddings`` param subtree)."""
  from ..layers.planner import DistEmbeddingStrategy

  tables = [TableConfig(input_dim=int(v), output_dim=embedding_dim)
            for v in vocab_sizes]
  return DistEmbeddingStrategy(tables, world_size, strategy,
                               column_slice_threshold=column_slice_threshold,
                               dense_row_threshold=dense_row_threshold,
                               row_slice_threshold=row_slice,
                               batch_hint=batch_hint)


def _dlrm_initializer(rows: int):
  """Uniform(-1/sqrt(rows), 1/sqrt(rows)) per table
  (reference ``DLRMInitializer``, `examples/dlrm/utils.py:27-41`)."""
  scale = 1.0 / np.sqrt(rows)

  def init(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, minval=-scale, maxval=scale)

  init.scale = scale  # enables direct packed init (init_sparse_state_direct)
  return init


def bce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
  """Mean sigmoid binary cross-entropy (reference trains with
  ``BinaryCrossentropy(from_logits=True)``, `examples/dlrm/main.py:195-199`)."""
  labels = labels.astype(jnp.float32)
  return jnp.mean(
      jnp.maximum(logits, 0) - logits * labels +
      jnp.log1p(jnp.exp(-jnp.abs(logits))))
