"""The sanctioned wire module: every dp<->mp exchange rides here.

The exchange payloads of the distributed lookup path (routed ids dp->mp,
activations mp->dp, and the autodiff-inserted reverse cotangent exchange)
are a cross-cutting contract: the routing layer, the combiner, the
backward apply, and the jaxpr audit all assume one wire format. This
module is that format's single home — graftlint GL109 flags a raw
``lax.all_to_all`` OR ``lax.ppermute`` in trace-reachable step-builder
code anywhere else, so a new exchange cannot silently bypass the plan's
wire knobs.

Four plan knobs (``DistEmbeddingStrategy``) govern the format:

- ``wire_dtype='f32' | 'bf16' | 'fp8'``: float payloads (activations and
  their reverse cotangents) travel the wire in this dtype. The payload
  is narrowed immediately before the exchange and widened right after on
  the receiving side — tables, combiners, the optimizer rules, and the
  one-scatter-add backward all stay f32 master precision; only the bytes
  in flight shrink. Integer payloads (ids, lengths, inverse maps) always
  travel int32. The narrowing is wrapped in a ``jax.custom_vjp`` so the
  REVERSE exchange (the cotangent exchange autodiff inserts) is narrowed
  the same way: cotangents are computed (and, under ``dedup_exchange``,
  segment-summed per unique id) in f32, then narrowed for the wire, then
  widened on the owning side. ``'fp8'`` (float8_e4m3) additionally ships
  ONE f32 amax scale per destination block (per chunk under the
  pipelined/fused wire), bit-packed into the block's own payload (4 fp8
  lanes carry the f32 bits), so the quantization window tracks each
  block's dynamic range and no second collective is needed for the
  scales.
- ``dedup_exchange=True``: see ``lookup_engine.DedupRouted`` — the id
  exchange ships sorted-unique id blocks and the float exchanges ship one
  row per unique id instead of one per sample/occurrence.
- ``overlap='pipelined'``: the monolithic ``all_to_all`` is rewritten as
  ``world - 1`` rounds of ``lax.ppermute`` per chunk — round ``k`` ships
  the block for rank ``(i + k) % world`` — with the payload split into
  ``exchange_chunks`` column chunks. Chunk ``k``'s blocks land while
  chunk ``k + 1``'s rounds are still in flight, which is what lets the
  receiving side's fused gather/combine overlap the residual exchange
  (PAPERS.md, fused computation-collective operations); the reverse
  cotangent exchange is pipelined identically through the ``custom_vjp``
  below. The permutation is pure data movement, so the f32 pipelined
  wire is BIT-EXACT against the monolithic one.
- ``overlap='fused'``: the just-in-time form of the pipelined schedule.
  The engine no longer gathers ALL routed rows in one monolithic
  pre-pass before the rounds start: each round's payload is gathered
  (and, under ``dedup_exchange``, expanded/segment-summed) immediately
  before its own :func:`fused_block_send`, and the rounds are emitted as
  independent gather -> encode -> ppermute -> decode chains whose only
  data dependence is the rows that round actually ships — which is what
  lets XLA's scheduler (and, on a real TPU, the
  ``ops/pallas_exchange.py`` double-buffered remote-DMA kernel) overlap
  round ``k``'s collective with round ``k + 1``'s gather. Integer
  payloads and the dense-class float exchanges still ride the pipelined
  schedule (there is no per-round gather to fuse). f32 stays BIT-exact
  vs both the monolithic and the pipelined forms — the per-round gather
  slices rows per destination before the elementwise gather/combine
  instead of after it, and every placement step is pure data movement.
- ``exchange_chunks=N``: chunk count of the pipelined split (along the
  flattened per-destination payload, so every shape — padded, ragged
  value streams, dedup'd unique blocks — chunks uniformly and chunk
  counts that do not divide the payload pad the tail). The traced
  program carries exactly ``(world - 1) * N`` ppermute rounds per
  exchange, which the jaxpr audit pins per artifact. Under
  ``overlap='fused'`` the sparse-class chunks split along gathered ROWS
  instead of the flattened payload (rows gather whole), capped at the
  block's row count — fp8 scales are still one per (destination block,
  chunk), now computed over each just-gathered row chunk.

One choice is not a knob. A class of small tables crosses the mesh either
as ROWS (ids dp->mp, looked-up rows mp->dp, cotangents back: the
exchanges above) or as TABLES (:func:`gather_tables`: the class block
all-gathered forward, its gradient reduce-scattered backward, the lookup
run on each rank's own samples, no padded slot). :func:`dense_class_side`
counts both in bytes leaving a chip each way a step, from static shapes,
and the engine takes the smaller; the knobs above then govern only what
still crosses as rows. Dense-kind (MXU one-hot) classes, and in the fused
training step sparse-kind classes too: their packed block, optimizer
lanes and all, is gathered, read by a row gather on the local samples,
and the per-occurrence deltas, scatter-added locally into zeros of the
gathered shape, come home through :func:`scatter_tables`.

With ``world_size == 1`` there is no wire: nothing is exchanged, nothing
is narrowed, and every knob is inert (numerics stay bit-identical to the
single-device f32 path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# plan knob value -> payload dtype for FLOAT exchanges. f32 is the
# identity wire (no casts are inserted at all, so the traced program is
# unchanged from the pre-knob build). fp8 payloads additionally carry a
# per-block f32 amax scale (see _fp8_encode).
WIRE_DTYPES = {
    "f32": jnp.float32,
    "bf16": jnp.bfloat16,
    "fp8": jnp.float8_e4m3fn,
}

# canonical dtype-string key of the fp8 wire inside the custom_vjp
# dispatch (nondiff args must be hashable, so dtypes travel as strings)
_FP8_WIRE = str(jnp.dtype(jnp.float8_e4m3fn))

# largest finite float8_e4m3fn value: per-block payloads are scaled so
# the block's amax maps exactly onto it (full use of the 4-bit exponent
# window; e4m3fn has no inf, so saturation at +-448 is the overflow mode)
FP8_MAX = 448.0

# fp8 lanes appended per destination block to carry the block's f32 amax
# scale (4 bytes bitcast into 4 single-byte fp8 slots)
_FP8_SCALE_LANES = 4


def plan_wire_dtype(plan):
  """The plan's wire dtype (``None`` = f32 identity wire).

  Reads ``plan.wire_dtype`` leniently (plans pickled before the knob
  existed default to f32)."""
  name = getattr(plan, "wire_dtype", "f32")
  if name not in WIRE_DTYPES:
    raise ValueError(
        f"unknown wire_dtype {name!r}; have {sorted(WIRE_DTYPES)}")
  return None if name == "f32" else WIRE_DTYPES[name]


def plan_dedup_exchange(plan) -> bool:
  """The plan's ``dedup_exchange`` knob (default False for old plans)."""
  return bool(getattr(plan, "dedup_exchange", False))


def plan_overlap(plan) -> str:
  """The plan's ``overlap`` knob (default 'none' for old plans)."""
  name = getattr(plan, "overlap", "none")
  if name not in ("none", "pipelined", "fused"):
    raise ValueError(
        f"unknown overlap mode {name!r}; have ['none', 'pipelined', "
        f"'fused']")
  return name


def plan_exchange_chunks(plan) -> int:
  """The plan's ``exchange_chunks`` knob (default 1 for old plans)."""
  return int(getattr(plan, "exchange_chunks", 1) or 1)


def dense_class_side(world: int, dp_input: bool, padded_slots: int,
                     global_batch: int, class_rows: int, width: int,
                     row_value_bytes: int = 4,
                     table_width: Optional[int] = None):
  """Which side of a class of small tables crosses the mesh.

  A small table can be looked up where it is owned, its rows crossing
  the mesh (ids dp->mp, ``[slot, sample, width]`` rows mp->dp, their
  cotangents back), or be gathered to every rank and looked up on the
  local samples (the class block all-gathered forward, its gradient
  reduce-scattered backward). Both costs are static at trace time, in
  bytes leaving one chip each way a step:

  - rows: ``padded_slots * global_batch * width`` values in the wire's
    dtype, of which ``(world - 1) / world`` leave the chip.
    ``padded_slots`` is the sum of the buckets' ``n_b``: the program is
    SPMD-uniform, so every rank ships the LARGEST slot count of any rank
    per (hotness, window) bucket (a sequence input counts its hotness
    times, its rows travel side by side);
  - tables: ``(world - 1) * class_rows * table_width`` float32 values
    (the table is not narrowed: that would change every row read, not
    only what is in flight).

  The name is from the dense (MXU one-hot) kind, which came first; a
  sparse-kind class is counted the same way on its PACKED block, whose
  rows are what its all-gather carries: ``class_rows`` physical rows of
  ``table_width`` lanes, optimizer lanes and all (``PackedLayout``'s
  ``phys_rows`` and ``phys_width``; ``table_width`` defaults to
  ``width``, the dense kind's plain ``[rows, width]`` block), against
  the same ``width`` values a sample that a slot ships.

  Returns ``(side, rows_bytes, tables_bytes)``; ``side`` is ``"tables"``
  where that is strictly cheaper. Rows travel whenever there is nothing
  to choose: one rank, or model-parallel inputs (``dp_input=False``: a
  rank holds the global batch's ids for ITS tables only, so no rank has
  its local samples' ids for the others)."""
  rows_bytes = (padded_slots * global_batch * width * row_value_bytes
                * (world - 1)) // world
  tables_bytes = ((world - 1) * class_rows
                  * (width if table_width is None else table_width) * 4)
  tables = world > 1 and dp_input and tables_bytes < rows_bytes
  return ("tables" if tables else "rows"), rows_bytes, tables_bytes


def gather_tables(block: jax.Array, axis_name: str) -> jax.Array:
  """``[rows, w]`` local class block -> ``[world * rows, w]``, every rank's
  block on every rank, rank r's at rows ``[r * rows, (r + 1) * rows)``: the
  global class param as its ``PartitionSpec(axis, None)`` lays it out.
  Linear, so autodiff's transpose is the reduce-scatter that lands each
  owner's summed gradient on its own block; no custom rule, no narrowing
  (see :func:`dense_class_side`).

  A sparse-kind class comes through here as its packed block
  ``[phys_rows, phys_width]``, optimizer lanes beside the rows, outside
  autodiff: the local samples gather their fused rows from the result at
  ``owner * phys_rows + row``, and the way back is :func:`scatter_tables`.

  Two-dimensional on purpose. As ``[world, rows, w]`` the windows the
  one-hot lookup stacks are ``x[r, o : o + n]``, and the TPU compiler of
  this installation merges two such slices of neighbouring ranks into ONE
  slice ``x[r : r + 2, o : o + n]`` at the first one's offset: the second
  window then holds another table's rows, silently (PERF.md, PR 30; the
  CPU compiler does not). Slices of the flat rows are not merged
  (``tests/test_dense_table_gather.py`` compiles them for the chip)."""
  return lax.all_gather(block, axis_name, axis=0, tiled=True)


def scatter_tables(gathered: jax.Array, axis_name: str) -> jax.Array:
  """``[world * rows, w]``, one rank's additive contribution to every
  rank's block, laid out as :func:`gather_tables` returns them -> ``[rows,
  w]``, the sum over ranks of the contributions to THIS rank's block. What
  autodiff writes as the transpose of :func:`gather_tables`, by hand for
  the sparse-kind classes, whose update is built outside autodiff: every
  rank scatter-adds its local samples' per-occurrence deltas into zeros of
  the gathered shape, and the owner adds the sum to its block."""
  return lax.psum_scatter(gathered, axis_name, scatter_dimension=0,
                          tiled=True)


def exchange_ids(x: jax.Array, axis_name: str) -> jax.Array:
  """Integer payload exchange (routed ids / unique blocks / ragged
  lengths). Always travels at the payload's integer dtype — the routing
  layer has already narrowed localized ids to int32 for the wire."""
  return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)


def float_all_to_all(x: jax.Array, axis_name: str,
                     wire_dtype=None) -> jax.Array:
  """Float payload exchange under the plan's wire dtype.

  ``wire_dtype=None`` (or equal to ``x.dtype``) is the identity wire: a
  plain differentiable ``all_to_all`` whose reverse exchange autodiff
  inserts natively. Otherwise the payload is narrowed to ``wire_dtype``
  for the flight and widened back to ``x.dtype`` on arrival, in BOTH
  directions (the reverse cotangent exchange is narrowed identically via
  the ``custom_vjp`` below). The fp8 wire scales each destination block
  by its own amax and ships the f32 scale inside the block
  (:func:`_fp8_encode`)."""
  if wire_dtype is None or jnp.dtype(wire_dtype) == x.dtype:
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)
  return _wire_all_to_all(axis_name, str(jnp.dtype(wire_dtype)),
                          str(x.dtype), x)


# ---------------------------------------------------------------------------
# fp8 block codec: per-destination-block amax scale, shipped IN the block
# ---------------------------------------------------------------------------


def _fp8_encode(blocks: jax.Array) -> jax.Array:
  """``[world, m]`` float -> ``[world, m + 4]`` fp8 wire blocks.

  Each destination block is scaled by its own amax (mapped onto
  ``FP8_MAX``, the largest finite e4m3 value) before the cast, so the
  3-bit mantissa spends its range on the block's actual dynamic range;
  the f32 scale is bitcast into 4 trailing fp8 lanes and travels WITH
  the block — the receiving side never needs a second exchange to
  dequantize. All-zero blocks keep scale 1 (nothing to quantize)."""
  amax = jnp.max(jnp.abs(blocks.astype(jnp.float32)), axis=1)
  scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0).astype(jnp.float32)
  q = (blocks.astype(jnp.float32) / scale[:, None]).astype(
      jnp.float8_e4m3fn)
  scale_lanes = lax.bitcast_convert_type(
      lax.bitcast_convert_type(scale, jnp.uint8), jnp.float8_e4m3fn)
  return jnp.concatenate([q, scale_lanes], axis=1)


def _fp8_decode(blocks: jax.Array, compute_dtype) -> jax.Array:
  """``[world, m + 4]`` fp8 wire blocks -> ``[world, m]`` compute dtype."""
  q = blocks[:, :-_FP8_SCALE_LANES]
  scale = lax.bitcast_convert_type(
      lax.bitcast_convert_type(blocks[:, -_FP8_SCALE_LANES:], jnp.uint8),
      jnp.float32)
  return (q.astype(jnp.float32) * scale[:, None]).astype(compute_dtype)


def _chunk_encode(wire_name: str, xc: jax.Array) -> jax.Array:
  """The ONE wire codec (monolithic and pipelined paths both dispatch
  here): identity for the f32 wire, a cast for bf16-style narrowing,
  the amax-scaled block form for fp8. fp8 blocks must arrive 2-D
  ``[world, m]`` (the scale lanes append per destination block)."""
  if wire_name == "none":
    return xc
  if wire_name == _FP8_WIRE:
    return _fp8_encode(xc)
  return xc.astype(wire_name)


def _chunk_decode(wire_name: str, compute_dtype, y: jax.Array) -> jax.Array:
  if wire_name == "none":
    return y
  if wire_name == _FP8_WIRE:
    return _fp8_decode(y, compute_dtype)
  return y.astype(compute_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _wire_all_to_all(axis_name: str, wire_dtype: str, compute_dtype: str,
                     x: jax.Array) -> jax.Array:
  out, _ = _wire_fwd(axis_name, wire_dtype, compute_dtype, x)
  return out


def _wire_mono(axis_name, wire_dtype, compute_dtype, x):
  """One monolithic narrowed exchange through the shared codec. Only
  the fp8 wire flattens (its scale lanes append per destination block);
  the bf16 path keeps the payload's shape, so its traced program is
  unchanged from the pre-fp8 build."""
  if wire_dtype == _FP8_WIRE:
    enc = _chunk_encode(wire_dtype, x.reshape(x.shape[0], -1))
    got = lax.all_to_all(enc, axis_name, split_axis=0, concat_axis=0)
    return _chunk_decode(wire_dtype, compute_dtype, got).reshape(x.shape)
  y = lax.all_to_all(_chunk_encode(wire_dtype, x), axis_name,
                     split_axis=0, concat_axis=0)
  return _chunk_decode(wire_dtype, compute_dtype, y)


def _wire_fwd(axis_name, wire_dtype, compute_dtype, x):
  return _wire_mono(axis_name, wire_dtype, compute_dtype, x), None


def _wire_bwd(axis_name, wire_dtype, compute_dtype, res, ct):
  # The split0/concat0 block permutation is an involution, so the reverse
  # exchange is the same all_to_all; the cotangent (already reduced in
  # f32 by the producer — e.g. the dedup path's per-unique segment-sum)
  # is narrowed for the flight exactly like the forward payload (fp8:
  # re-scaled by the COTANGENT blocks' own amax).
  del res
  return (_wire_mono(axis_name, wire_dtype, compute_dtype, ct),)


_wire_all_to_all.defvjp(_wire_fwd, _wire_bwd)


# ---------------------------------------------------------------------------
# pipelined exchange: (world - 1) ppermute rounds per chunk
# ---------------------------------------------------------------------------


def _pipelined_rounds(xf: jax.Array, axis_name: str, chunks: int,
                      wire_name: str = "none",
                      compute_dtype=None) -> jax.Array:
  """Chunked ppermute equivalent of ``all_to_all(split0, concat0)``.

  ``xf [world, m]`` is the flattened dest-major payload. Per chunk the
  schedule is ``world - 1`` rotation rounds — round ``k`` sends the
  block for rank ``(i + k) % world`` over the static rotate-by-k
  permutation, so every round is a uniform neighbor pattern (on a TPU
  ring these are the single-hop ICI steps an all_to_all decomposes
  into). The rank-dependent block selection is one ``roll`` before the
  rounds and one gather after, both pure data movement, so the f32 path
  reproduces the monolithic exchange bit-for-bit; chunk ``c + 1``'s
  rounds have no data dependency on chunk ``c``'s consumers, which is
  the overlap the scheduler exploits. Exactly ``(world - 1) * chunks``
  ppermute equations per call — the jaxpr audit pins that count.

  Chunking happens on the flattened per-destination axis: a chunk count
  that does not divide the payload pads the tail of the LAST chunk with
  zeros (sliced back off after reassembly), so any chunk count is legal
  for any payload shape."""
  world, m = xf.shape
  chunks = max(1, int(chunks))
  mc = -(-m // chunks)
  pad = chunks * mc - m
  if pad:
    xf = jnp.concatenate(
        [xf, jnp.zeros((world, pad), xf.dtype)], axis=1)
  i = lax.axis_index(axis_name)
  # xr[k] = my block destined for rank (i + k) % world
  xr = jnp.roll(xf, -i, axis=0)
  # received round k came from rank (i - k) % world; out[j] must hold
  # source j's block, so out[j] = rounds[(i - j) % world]
  src_pos = jnp.mod(i - jnp.arange(world, dtype=jnp.int32), world)
  outs = []
  for c in range(chunks):
    enc = _chunk_encode(wire_name, xr[:, c * mc:(c + 1) * mc])
    rounds = [enc[0]]  # round 0: the self block, no wire
    for k in range(1, world):
      perm = [(s, (s + k) % world) for s in range(world)]
      rounds.append(lax.ppermute(enc[k], axis_name, perm))
    dec = _chunk_decode(wire_name, compute_dtype, jnp.stack(rounds))
    outs.append(jnp.take(dec, src_pos, axis=0))
  out = outs[0] if chunks == 1 else jnp.concatenate(outs, axis=1)
  return out[:, :m] if pad else out


def pipelined_exchange_ids(x: jax.Array, axis_name: str,
                           chunks: int = 1) -> jax.Array:
  """Integer payload exchange as a chunked ppermute pipeline.

  Same permutation semantics as :func:`exchange_ids` (and bit-identical
  output — ids are pure data movement); the payload chunks along the
  flattened per-destination axis so routed id tensors, ragged value
  streams / lengths, and dedup'd unique blocks all pipeline uniformly."""
  world = x.shape[0]
  if world == 1:
    return x
  out = _pipelined_rounds(x.reshape(world, -1), axis_name, chunks)
  return out.reshape(x.shape)


def pipelined_float_exchange(x: jax.Array, axis_name: str,
                             wire_dtype=None, chunks: int = 1) -> jax.Array:
  """Float payload exchange as a chunked ppermute pipeline.

  The pipelined counterpart of :func:`float_all_to_all`: the payload is
  narrowed to ``wire_dtype`` per chunk (fp8 blocks carry their per-chunk
  amax scales, :func:`_fp8_encode`), flown over ``(world - 1) * chunks``
  ppermute rounds, and widened on arrival. Wrapped in a ``custom_vjp``
  whose backward runs the SAME pipeline on the cotangent — the reverse
  exchange mirrors the forward schedule chunk for chunk, so the
  one-scatter-add backward receives exactly the cotangents the
  monolithic wire would have delivered (bit-exact under f32)."""
  world = x.shape[0]
  if world == 1:
    return x
  if wire_dtype is None or jnp.dtype(wire_dtype) == x.dtype:
    wire_name = "none"
  else:
    wire_name = str(jnp.dtype(wire_dtype))
  return _pipelined_float(axis_name, wire_name, str(x.dtype), int(chunks),
                          x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _pipelined_float(axis_name: str, wire_name: str, compute_dtype: str,
                     chunks: int, x: jax.Array) -> jax.Array:
  out, _ = _pipe_fwd(axis_name, wire_name, compute_dtype, chunks, x)
  return out


def _pipe_fwd(axis_name, wire_name, compute_dtype, chunks, x):
  out = _pipelined_rounds(x.reshape(x.shape[0], -1), axis_name, chunks,
                          wire_name, compute_dtype)
  return out.reshape(x.shape).astype(compute_dtype), None


def _pipe_bwd(axis_name, wire_name, compute_dtype, chunks, res, ct):
  # the permutation is an involution (out[j] = x_j[i]), so the reverse
  # pipeline is the same rounds on the cotangent — narrowed per chunk
  # exactly like the forward payload (fp8: the cotangent chunks' own
  # amax scales)
  del res
  g = _pipelined_rounds(ct.reshape(ct.shape[0], -1), axis_name, chunks,
                        wire_name, compute_dtype)
  return (g.reshape(ct.shape).astype(compute_dtype),)


_pipelined_float.defvjp(_pipe_fwd, _pipe_bwd)


# ---------------------------------------------------------------------------
# fused exchange: one send per just-gathered block, no monolithic pre-pass
# ---------------------------------------------------------------------------


def fused_round_perm(k: int, world: int):
  """Round ``k``'s rotate-by-k permutation (the pipelined schedule's)."""
  return [(s, (s + k) % world) for s in range(world)]


def fused_block_send(x: jax.Array, axis_name: str, k: int, world: int,
                     wire_dtype=None) -> jax.Array:
  """Ship ONE just-gathered block over round ``k``'s rotation.

  ``x`` is the payload this rank gathered for rank ``(i + k) % world``
  (one chunk of it); the return value is the block rank
  ``(i - k) % world`` gathered for me. Round 0 is the self block and
  never crosses the wire (but is still narrowed/widened under a narrow
  wire, exactly like the pipelined schedule's round 0). f32 rides a
  native ``lax.ppermute`` — linear, so autodiff's transpose is the
  inverse rotation on the cotangent and the reverse exchange fuses per
  round for free; narrow wires go through a ``custom_vjp`` that encodes
  the cotangent chunk with its OWN amax scale, mirroring
  :func:`pipelined_float_exchange`.

  Unlike :func:`pipelined_float_exchange` this takes one block, not the
  ``[world, ...]`` dest-major stack — the caller gathers each block
  immediately before its send, so the traced round body depends only on
  the rows it ships and XLA can overlap round ``k``'s collective with
  round ``k + 1``'s gather."""
  if world == 1:
    return x
  if wire_dtype is None or jnp.dtype(wire_dtype) == x.dtype:
    if k == 0:
      return x
    return lax.ppermute(x, axis_name, fused_round_perm(k, world))
  return _fused_block(axis_name, str(jnp.dtype(wire_dtype)), str(x.dtype),
                      int(k), int(world), x)


def _fused_block_send_raw(axis_name, wire_name, compute_dtype, k, world, x):
  """encode -> (rotate-by-k) -> decode for one narrow-wire block."""
  enc = _chunk_encode(wire_name, x.reshape(1, -1))
  if k:
    enc = lax.ppermute(enc, axis_name, fused_round_perm(k, world))
  dec = _chunk_decode(wire_name, compute_dtype, enc)
  return dec.reshape(x.shape).astype(compute_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _fused_block(axis_name: str, wire_name: str, compute_dtype: str,
                 k: int, world: int, x: jax.Array) -> jax.Array:
  return _fused_block_send_raw(axis_name, wire_name, compute_dtype, k,
                               world, x)


def _fused_fwd(axis_name, wire_name, compute_dtype, k, world, x):
  return _fused_block_send_raw(axis_name, wire_name, compute_dtype, k,
                               world, x), None


def _fused_bwd(axis_name, wire_name, compute_dtype, k, world, res, ct):
  # the rotate-by-k rotation's transpose is rotate-by-(world - k): my
  # forward round-k block went to (i + k) % world, so my cotangent for it
  # comes back FROM (i + k) % world — narrowed with the cotangent chunk's
  # own amax, exactly like the pipelined backward
  del res
  return (_fused_block_send_raw(axis_name, wire_name, compute_dtype,
                                (world - k) % world, world, ct),)


_fused_block.defvjp(_fused_fwd, _fused_bwd)
