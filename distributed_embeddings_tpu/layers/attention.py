"""Attention under a static mask, and the rotary pass before it: what every
decoder of this package shares, once. A model's mixer keeps what is its own
(projections, a gate, which of norm and rotary it applies and in what order)
and hands this layer ``q`` (already scaled), ``k``, ``v``, a mask description
and, over packed documents, their segment ids ``seg [B, S]`` (data, so no part
of a description). ``q [B, S, Hkv, G, hd]`` with ``k, v [B, S, Hkv, hd]`` is
``G`` query heads a key-value head; ``q, k, v [B, S, H, hd]`` heads with no
group: the layout is read off ``q``'s rank.

:func:`attention_splash` is JAX's splash-attention kernel, what every cell
runs: a TPU kernel that never holds ``[S, S]`` scores and skips the blocks of
keys the mask empties and, over packed documents, those that hold another
document's keys alone (:func:`documents_in_block_maps`).
:func:`attention_xla` is the same attention in XLA, a tile of queries at a
time: tests, counting tools on any backend, and the kernel's oracle. A
configuration's ``attention`` names one (:func:`attention_path`).

A mask description (:class:`Causal`, :class:`Window`, :class:`BlockDiffusion`:
frozen, hashable) answers two questions and nothing else: ``splash(length)``,
the kernel's mask object, and ``reach(a, e, length)``, for the tile of queries
``[a, e)`` the key ranges ``((first, last), ...)`` it can see and the static
``allowed [e - a, keys of the ranges]`` over them. Both are one statement of
who sees whom (``tests/test_attention.py`` holds them to each other), so the
kernel and its oracle cannot drift.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .remat import SPLASH_RESIDUALS


def rope_frequencies(theta: float, rotary_dim: int) -> np.ndarray:
  """``theta ** (-2 i / rotary_dim)``, ``i < rotary_dim / 2``: the inverse
  frequencies of plain RoPE over ``rotary_dim`` dimensions, float32."""
  half = rotary_dim // 2
  return 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))


def rope(x, positions, inv_freq, attention_factor: float = 1.0):
  """``x [..., S, heads, head_dim]``, rotate-half over the first
  ``2 len(inv_freq)`` dimensions of a head (the rotated width; the rest pass
  as they are), angles in float32; cos and sin times ``attention_factor``
  where a scaled table (YaRN) has one."""
  half = len(inv_freq)
  ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [S, half]
  cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
  sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
  if attention_factor != 1.0:
    cos, sin = cos * attention_factor, sin * attention_factor
  whole = 2 * half == x.shape[-1]
  turned = x if whole else x[..., :2 * half]
  rotated = jnp.concatenate([-turned[..., half:], turned[..., :half]],
                            axis=-1)
  turned = turned * cos + rotated * sin
  return turned if whole \
      else jnp.concatenate([turned, x[..., 2 * half:]], axis=-1)


def _tile(sees, a: int, e: int, ranges):
  """``reach``'s answer from a description's ``sees(i, j)`` (query ``i``
  sees key ``j``, elementwise) and the key ranges of the tile ``[a, e)``."""
  keys = np.concatenate([np.arange(first, last) for first, last in ranges])
  return tuple(ranges), sees(np.arange(a, e)[:, None], keys[None, :])


@dataclasses.dataclass(frozen=True)
class Causal:
  """A query sees itself and every key before it."""

  def splash(self, length: int):
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    return sa.CausalMask((length, length))

  def reach(self, a: int, e: int, length: int):
    return _tile(lambda i, j: j <= i, a, e, [(0, e)])


@dataclasses.dataclass(frozen=True)
class Window:
  """Causal and ``i - j < window``: a query sees itself and the
  ``window - 1`` keys before it."""
  window: int

  def splash(self, length: int):
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    return sa.LocalMask((length, length), (self.window - 1, 0), 0)

  def reach(self, a: int, e: int, length: int):
    return _tile(lambda i, j: (j <= i) & (i - j < self.window), a, e,
                 [(max(0, a - self.window + 1), e)])


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
  """Over ``[xt ; x0]``, ``length = 2 L`` positions, both halves in blocks
  of ``block_length``: with ``b(i) = (i mod L) // block_length``, a noisy
  query sees the noisy keys of its own block and the clean keys of earlier
  blocks; a clean query sees the clean keys of its own and earlier blocks;
  nothing else."""
  block_length: int

  def _sees(self, i, j, half: int):
    bi, bj = i % half // self.block_length, j % half // self.block_length
    own = np.where(i < half, bi, -1)      # the noisy block a query sees
    upto = bi + (i >= half)               # the clean blocks it sees: below
    return np.where(j < half, bj == own, bj < upto)

  def splash(self, length: int):
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    at = np.arange(length, dtype=np.int32)
    return sa.NumpyMask(self._sees(at[:, None], at[None, :], length // 2))

  def reach(self, a: int, e: int, length: int):
    half, bl = length // 2, self.block_length
    whole = lambda n: min(-(-n // bl) * bl, half)   # the end of n's block
    ranges, clean = [], 0
    if a < half:      # noisy queries: their blocks' noisy keys, clean before
      ranges, clean = [(a // bl * bl, whole(min(e, half)))], min(e, half)
    if e > half:      # clean queries: clean keys to the end of their block
      clean = max(clean, whole(e - half))
    return _tile(functools.partial(self._sees, half=half), a, e,
                 ranges + [(half, half + clean)])


# Queries and keys a block of the splash kernel, and queries a tile of the XLA
# path: 256 cost a quarter more time, 1024 no less (and its fused backward
# does not fit VMEM); the kernel's fused backward (dq inside dkv) was no
# faster (my chip runs, PR 29)
ATTENTION_BLOCK = 512


def splash_block_sizes(block: int):
  """One block size for queries and keys, forward and both backward kernels
  (no fused backward: see ``ATTENTION_BLOCK``)."""
  from jax.experimental.pallas.ops.tpu import splash_attention as sa
  return sa.BlockSizes(
      block_q=block, block_kv=block, block_kv_compute=block,
      block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
      block_q_dq=block, block_kv_dq=block)


@functools.lru_cache(maxsize=None)
def _splash_kernel(mask, length: int, heads: int, grouped: bool, block: int,
                   interpret: bool):
  """The kernel of ``mask`` over ``length`` positions: multi-query over a
  group of ``heads`` query heads where ``grouped``, multi-head over ``heads``
  otherwise. One a (description, shape) a process."""
  from jax.experimental.pallas.ops.tpu import splash_attention as sa
  make = sa.make_splash_mqa_single_device if grouped \
      else sa.make_splash_mha_single_device
  # the kernel's block maps as host arrays, so that they are constants of
  # whatever program calls it. The factory makes ``jnp`` arrays of them: in
  # the middle of a trace (where this is first called) those would be that
  # trace's tracers, kept here for the next one
  with jax.ensure_compile_time_eval():
    kernel = make(
        sa.MultiHeadMask([mask.splash(length)] * heads),
        block_sizes=splash_block_sizes(min(block, length)),
        residual_checkpoint_name=SPLASH_RESIDUALS, interpret=interpret)
  return jax.tree_util.tree_map(np.asarray, kernel)


@functools.partial(jax.jit, static_argnums=2)
def documents_in_block_maps(kernel, seg, block: int):
  """``kernel`` (:func:`_splash_kernel`'s) with one sample's documents folded
  into its block maps: of the grid steps the static mask keeps, those whose
  block of queries and block of keys share no document are skipped too, so
  the block is neither fetched nor multiplied. ``seg [S]`` does not decrease
  along a packed sequence (``layers/decoder.py::document_segments``), so a
  block holds every document from its first position's to its last's, and
  two blocks share one where those ranges meet. The maps become arrays of
  the device, which the kernels take by scalar prefetch as they take the
  static ones; what a step computes inside a live block is untouched (the
  segment ids still mask it by element)."""
  ends = seg.reshape(-1, min(block, seg.shape[0]))
  first, last = ends[:, 0], ends[:, -1]
  shared = (last[:, None] >= first[None, :]) \
      & (last[None, :] >= first[:, None])           # [blocks, blocks]

  def fold(info, steps: int):
    """``steps``: the axis a kernel walks, keys (2) in the forward and dq,
    queries (1) in dkv; the other holds every block it keeps resident, a
    row here. A step's partner block is the one ``data_next`` names, not the
    step's place: a shrunk grid left-aligns a row's live steps. Written as
    comparisons and reductions over ``[rows, steps, steps]``, which XLA
    fuses (5 to 14 microseconds a layer on a v5e where gathers and
    cumulative maxima took 10 to 50: PERF.md, PR 48)."""
    by_row = (lambda x: jnp.swapaxes(x, 1, 2)) if steps == 1 else (lambda x: x)
    static, fetch = by_row(info.block_mask), by_row(info.data_next)
    partner = fetch[..., None] == jnp.arange(len(first))
    together = jnp.any(partner & shared[:, None, :], axis=-1)
    live = (static > 0) & together
    # a dead step names the block of the nearest live step before it in its
    # row (after it, for those a row starts with: the rule, and the reason,
    # of ops/pallas_sparse_attn.py::block_plan), so it starts no copy
    at = jnp.arange(static.shape[-1])
    upto = at[:, None] >= at                       # [step, a step up to it]
    before = jnp.max(jnp.where(live[..., None, :] & upto, at, -1), axis=-1)
    after = jnp.min(jnp.where(live[..., None, :] & ~upto, at, len(at)),
                    axis=-1)
    near = jnp.where(before >= 0, before, jnp.where(after < len(at), after, at))
    named = jnp.sum(jnp.where(near[..., None] == at, fetch[..., None, :], 0),
                    axis=-1)
    # a row that loses no step keeps the static map's names
    lost = jnp.any((static > 0) & ~together, axis=-1, keepdims=True)
    return info._replace(
        block_mask=by_row(jnp.where(live, static, 0).astype(static.dtype)),
        data_next=by_row(jnp.where(lost, named, fetch).astype(fetch.dtype)))

  (fwd, dq, dkv), static = kernel.tree_flatten()
  return type(kernel).tree_unflatten(
      static, (fold(fwd, 2), fold(dq, 2), fold(dkv, 1)))


def attention_splash(q, k, v, mask, seg=None, block: int = ATTENTION_BLOCK,
                     interpret: bool = False):
  """``q``, ``k``, ``v`` in either layout -> ``q``'s shape, through the
  splash-attention kernel under ``mask`` and, where ``seg`` is given, inside
  the query's document: the kernel's segment ids, and its block maps with
  the sample's documents folded in (:func:`documents_in_block_maps`), so
  another document's blocks are not walked; ``seg`` does not decrease along
  a sequence. Its operands are rounded to bfloat16, which is what the MXU's
  default precision makes of a float32 operand; scores, softmax and
  accumulation are float32. For its backward the kernel keeps its output and
  the scores' log-sum-exp, under the name ``SPLASH_RESIDUALS``: a layer
  rematerialised by ``checkpoint_layer`` runs the forward kernel once.
  ``interpret``: Pallas's interpreter (tests)."""
  from jax.experimental.pallas.ops.tpu import splash_attention as sa
  grouped = q.ndim == 5
  kernel = _splash_kernel(mask, q.shape[1], q.shape[3 if grouped else 2],
                          grouped, block, interpret)

  def samples(q, k, v, s):
    """The samples that share ``s``: one where it is given, all of them
    where it is ``None``."""
    if s is None:
      call = lambda q, k, v: kernel(q, k, v, segment_ids=None)
    else:
      planned = documents_in_block_maps(kernel, s, block)
      call = lambda q, k, v: planned(
          q, k, v, segment_ids=sa.SegmentIds(q=s, kv=s))
    # grouped: a key-value head at a time, all under their sample's ids
    return jax.vmap(jax.vmap(call) if grouped else call)(q, k, v)

  heads_first = lambda x: jnp.moveaxis(x, 1, -2).astype(jnp.bfloat16)
  operands = heads_first(q), heads_first(k), heads_first(v)
  if seg is None:
    out = samples(*operands, None)
  else:
    # a sample at a time, each still under a ``vmap`` of its own: its maps
    # are the kernels' scalar-prefetch operands, over which ``vmap`` is a
    # loop inside the program, and without the leading axis XLA relays the
    # kernel's log-sum-exp out whole (PERF.md, PR 48)
    out = jnp.concatenate([
        samples(*(x[b:b + 1] for x in operands), seg[b])
        for b in range(q.shape[0])])
  return jnp.moveaxis(out, -2, 1).astype(q.dtype)


def attention_xla(q, k, v, mask, seg=None, tile: int = ATTENTION_BLOCK):
  """Same contract as :func:`attention_splash`, in XLA: one tile of queries
  at a time against the keys ``mask`` lets it reach, nothing beyond is
  computed; scores in float32 at least."""
  heads = q.ndim == 4           # [B, S, H, hd]: a group of one
  if heads:
    q = q[:, :, :, None]
  length = q.shape[1]
  tile = min(tile, length)
  out = []
  for a in range(0, length, tile):
    e = min(a + tile, length)
    ranges, allowed = mask.reach(a, e, length)
    reached = lambda x: jnp.concatenate(
        [x[:, first:last] for first, last in ranges], axis=1)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q[:, a:e], reached(k))
    s = s.astype(jnp.promote_types(s.dtype, jnp.float32))
    allowed = jnp.asarray(allowed)[None]
    if seg is not None:
      allowed = allowed & (seg[:, a:e, None] == reached(seg)[:, None, :])
    s = jnp.where(allowed[:, None, None], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out.append(jnp.einsum("bkgqs,bskd->bqkgd", prob, reached(v)))
  out = jnp.concatenate(out, axis=1)
  return out[:, :, :, 0] if heads else out


def attention_path(name: str, xla, splash):
  """The function a configuration's ``attention`` names: ``splash`` is the
  TPU's kernel and raises on any other backend, ``xla`` is for tests and
  counting tools."""
  if name == "xla":
    return xla
  if name == "splash":
    if jax.default_backend() != "tpu":
      raise ValueError(
          'attention="splash" is a TPU kernel and this backend is '
          f'{jax.default_backend()!r}; a test or a counting tool on another '
          'backend names attention="xla" itself')
    return splash
  raise ValueError(f"attention={name!r}: splash or xla")
