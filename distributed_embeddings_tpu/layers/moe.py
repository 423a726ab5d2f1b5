"""One chip's share of a mixture-of-experts layer.

Under expert parallelism each chip of a group holds a contiguous range of a
layer's experts. :func:`moe_share` is the part of the layer one such chip
computes: it routes every token over ALL ``num_experts`` (the router keeps its
published width and its experts per token), keeps the assignments that fall
on the experts held here, computes those and only those, and returns
``sum over the chosen, held e of p_e * expert_e(h)``. What the absent
experts would add is left out; the shares of all chips, added, are the whole
layer (``tests/test_moe.py`` holds that). ``held=(0, num_experts)`` is the
whole layer. No code stands in for the absent chips or their exchange.

How the chosen experts are weighted is the model's, a :class:`Router` on
the share: softmax over every expert and the chosen renormalised (the
default), or a sigmoid an expert, the chosen renormalised and scaled; and
where the model chooses under a selection bias (``Router.selection_bias``),
the choice is made on ``score + bias`` and the weights are gathered from the
unbiased scores: the bias, one value an expert, is a leaf of the model that
enters nothing differentiable. A
shared expert, which every chip computes for its own tokens, is no part of
a share: :func:`shared_expert` is the plain dense product, and a model adds
it to ``moe_share``'s sum (counted once when shares are added up).

Dropless and without a capacity. The assignments are sorted by expert, held
ones first, so the rows an expert computes are contiguous, and the grouped
matmuls (``layers/dense.py::grouped_mxu_dots``) go over them. On a TPU at
default precision they are handed what the MXU multiplies anyway: the rows,
``silu(gate) * up`` and the cotangents each rounded to bfloat16 once, where
they are made, and read by every product that wants them; float32 sums,
float32 ``y``, ``dx`` and ``dw`` from a written-out backward; and they run in
the kernels of ``ops/pallas_grouped_matmul.py``, tiled to each product's
shape, which neither multiply a row that belongs to no group nor leave it unwritten (XLA's
own ``lax.ragged_dot`` kernel walks every shape under one tiling: PERF.md, PR
53). Everywhere else the three ``lax.ragged_dot`` stand as they were. How many
assignments land here is data, not a shape: the expected count is
``tokens * top_k * held / num_experts``, the worst case ``num_experts / held``
times that. The sorted stream's head, ``HEAD_LOADS`` times the expected
count, is computed in one piece; the tail, the rest of the worst case, is
walked in pieces of the same size under one ``lax.cond`` that is taken only
when a layer's load passes the head: a skewed router costs time, never a
token. Memory: the head's residuals (its gathered rows, the two projections,
their product) live as long as the caller lets them, so a training model runs
the layer under ``layers/remat.py::checkpoint_layer`` and they are one layer's
backward's; the tail rematerialises itself, a piece at a time.

Two sums add the head's rows up by token: the layer's output (``out[tok[r]]
+= p[r] * y[r]``) and, transposed, the cotangent of ``h`` through the gather
that made the stream. XLA runs each as a scatter-add, a read-modify-write a
row (81-94 ns a row of 8 KiB on a v5e where its gather takes 37: PERF.md, PR
43). On a TPU, for shapes it takes, both are the token-major kernel of
``ops/pallas_moe_combine.py``: every token owns ``top_k`` positions of the
sorted stream (:func:`sorted_positions`, the inverse of the order), fetches
its rows below the head and sums them once, the weight and the select riding
the read. :func:`combine_kernel` decides by what it can observe (the backend,
the shapes); everywhere else, and in the tail, the scatter-adds stand as they
were, and are the kernel's oracle.

The head is computed WHOLE: its rows past the live count (zeros) are put in
the last expert's group and multiplied like the others, so that a step's
time does not follow the load. That costs time, and what it buys is
measured (PERF.md, PR 29, review round). On seeded weights a router
collapses: identical tokens route alike (in a block-diffusion model the mask
token is a quarter of all positions), and after one layer of attention most
positions look alike, so a layer's load on 16 of 128 experts is near
``0.8 k`` expected counts, ``k`` the number of the eight popular experts held
here: a draw per layer AND per batch (17 to 16,418 assignments against 8,192
expected, on one seed of the softmax router; a sigmoid router's draws on 32
of 256 experts stay within 0.59 to 1.40 of the expected count: PERF.md, PR
35). Computed live on the same buffers a step is 5-11% faster and follows
that lottery batch by batch, while most layers time an expert layer with
nothing to do; a trained router sends every chip about the expected count.
With a head of two expected counts, live, a layer past it walks the tail in
every step and the step is slower than this one.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import pallas_moe_combine
from ..telemetry import scopes
from .dense import grouped_mxu_dots, mxu_dot
from .remat import MOE_ROUTE


# Expected loads the head holds. On seeded weights 96 layers of 12 seeds'
# batches, counted on the CPU, held 0.00 to 3.41; at 3.5 one batch in twelve
# of one seed walked the tail on the chip (PERF.md, PR 29)
HEAD_LOADS = 4


@dataclasses.dataclass(frozen=True)
class Router:
  """How a model scores its experts and weights the chosen ones: data of
  the model, as its config publishes them."""
  score: str = "softmax"      # over every expert | "sigmoid": an expert each
  renormalise: bool = True    # the chosen scores divided by their sum
  scale: float = 1.0          # then multiplied (a routed scaling factor)
  selection_bias: bool = False  # experts chosen on score + bias, weighted
                                # by the score alone

  def __post_init__(self):
    if self.score not in ("softmax", "sigmoid"):
      raise ValueError(f"score={self.score!r}: softmax or sigmoid")


@dataclasses.dataclass(frozen=True)
class MoEShare:
  """Which part of a layer of ``num_experts`` experts lives here, and the
  layer's router."""
  num_experts: int
  top_k: int
  held: Tuple[int, int]       # (first expert held, how many)
  router: Router = Router()

  def __post_init__(self):
    first, count = self.held
    if not (0 <= first and count >= 1
            and first + count <= self.num_experts):
      raise ValueError(f"held={self.held} is no range of "
                       f"{self.num_experts} experts")
    if not 1 <= self.top_k <= self.num_experts:
      raise ValueError(f"top_k={self.top_k} of {self.num_experts} experts")

  def head_rows(self, assignments: int) -> int:
    """Rows of the sorted stream computed in one piece: ``HEAD_LOADS`` times
    the expected number of ``assignments`` on the held experts, in whole
    sublanes of 8."""
    expected = -(-assignments * self.held[1] // self.num_experts)
    return min(assignments, 8 * -(-HEAD_LOADS * expected // 8))


def route(h: jax.Array, w_router: jax.Array, top_k: int,
          router: Router = Router(), bias=None):
  """-> (p ``[T, top_k]``, the chosen experts' weights; experts
  ``[T, top_k]``). By default p is renormalised to sum to 1. With a
  ``bias [num_experts]`` the experts are the ``top_k`` of ``scores + bias``
  and p their unbiased scores: the bias moves the choice and never a
  weight, so it has no gradient.

  Router logits, the score of every expert and the renormalisation in
  float32; the logits' matmul at ``highest`` precision, because a choice of
  experts decided by a bfloat16 product is a different model."""
  logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
  scores = jax.nn.softmax(logits, axis=-1) if router.score == "softmax" \
      else jax.nn.sigmoid(logits)
  if bias is None:
    top_p, top_e = lax.top_k(scores, top_k)
  else:
    _, top_e = lax.top_k(scores + bias.astype(scores.dtype), top_k)
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
  if router.renormalise:
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
  if router.scale != 1.0:
    top_p = top_p * router.scale
  return top_p, top_e


def shared_expert(h: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                  w_down: jax.Array):
  """``h [T, d]`` -> ``(silu(h w_gate) * (h w_up)) w_down``: the expert
  every token passes and every chip of an expert-parallel group computes for
  its own tokens. A plain dense product, outside the sort: on a TPU handed
  bfloat16 operands, float32 out of both passes (``layers/dense.py``)."""
  with jax.named_scope(scopes.MOE), jax.named_scope(scopes.MOE_SHARED):
    return mxu_dot(jax.nn.silu(mxu_dot(h, w_gate)) * mxu_dot(h, w_up),
                   w_down)


def sorted_positions(key: jax.Array, classes: int) -> jax.Array:
  """Where a stable sort of ``key`` (int32 ``[n]``, values in ``[0,
  classes)``) puts each element: the inverse of ``argsort(key,
  stable=True)`` as int32 ``[n]``, made without a second sort and without a
  scatter: an element lies at its class's start plus the number of its class
  that come before it."""
  mine = key[:, None] == jnp.arange(classes, dtype=key.dtype)
  upto = jnp.cumsum(mine.astype(jnp.int32), axis=0)
  sizes = upto[-1]
  starts = jnp.cumsum(sizes) - sizes
  return jnp.sum(jnp.where(mine, upto - 1 + starts, 0), axis=1)


def combine_kernel(rows: int, h: jax.Array, top_k: int):
  """What adds the head's ``rows`` rows up by token here: ``None`` for XLA's
  scatter-add (any backend but a TPU, and shapes the kernel does not take),
  else ``ops/pallas_moe_combine.py`` with this for its ``interpret``
  (``False``: on the chip). It reads what it can observe; no option names a
  path. A test that wants the kernel in Pallas's interpreter replaces this
  function."""
  if (jax.default_backend() == "tpu" and h.dtype == jnp.float32
      and pallas_moe_combine.fits(rows, h.shape[0], top_k, h.shape[1])):
    return False
  return None


# The head's way to the experts and back where the kernel runs: ``dispatch``
# gathers the sorted stream's rows of ``h``, ``combine`` adds the experts'
# rows up by token. They are each other's transposes, and what XLA's own
# transpose makes a scatter-add is written here as the token-major kernel:
# the backward of ``dispatch`` IS ``combine`` with a scale of one. ``pos [T,
# k]`` is the inverse of the sorted order; a row at or past ``n_live``
# belongs to no held expert (zeros going in, selected away coming out).
#
# The kernel is entered through two jitted functions, one a part of the route.
# A step calls each once a layer and a pass, and a ``jax.jit`` traces its
# Python once a process and shape, where the kernel's body alone was traced
# sixteen times a step (seconds of set-up on every warm start, before the
# compile cache can be asked: PERF.md, PR 44). Two, and each with its part's
# scopes INSIDE: a jitted function is lowered once a module, and its ops carry
# the names opened inside it whichever call it was lowered for (what lies
# outside, the layer's and the pass's, XLA joins on from each call), so one
# shared function could not name its kernel after two parts.
@functools.partial(jax.jit, static_argnums=(0,))
def _dispatch_sum(interpret, dx, pos, n_live):
  """The cotangent of ``h`` through the gather of the head's rows."""
  with jax.named_scope(scopes.MOE_ROUTE), \
      jax.named_scope(scopes.MOE_DISPATCH):
    return pallas_moe_combine.combine(
        dx, pos, (pos < n_live).astype(dx.dtype), interpret=interpret)


@functools.partial(jax.jit, static_argnums=(0,))
def _return_sum(interpret, y, pos, p_tok, n_live):
  """The layer's output: the head's rows by token, each under its weight."""
  with jax.named_scope(scopes.MOE_ROUTE), jax.named_scope(scopes.MOE_RETURN):
    return pallas_moe_combine.combine(
        y, pos, jnp.where(pos < n_live, p_tok.astype(y.dtype), 0),
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(interpret, h, tok_c, pos, n_live):
  return _dispatch_fwd(interpret, h, tok_c, pos, n_live)[0]


def _dispatch_fwd(interpret, h, tok_c, pos, n_live):
  with jax.named_scope(scopes.MOE_ROUTE), \
      jax.named_scope(scopes.MOE_DISPATCH):
    live = jnp.arange(tok_c.shape[0], dtype=jnp.int32) < n_live
    x = jnp.where(live[:, None], jnp.take(h, tok_c, axis=0), 0)
  return x, (pos, n_live)


def _dispatch_bwd(interpret, kept, dx):
  pos, n_live = kept
  return _dispatch_sum(interpret, dx, pos, n_live), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(interpret, y, p_c, tok_c, pos, p_tok, n_live):
  """``zeros([T, d]).at[tok_c].add(where(live, y * p_c, 0))``. ``p_tok [T,
  k]`` holds the values of ``p_c`` token-major (the router's own ``top_p``),
  for the kernel's read alone: the weights' gradient leaves through
  ``p_c``, in sorted order, as it does without the kernel."""
  return _combine_fwd(interpret, y, p_c, tok_c, pos, p_tok, n_live)[0]


def _combine_fwd(interpret, y, p_c, tok_c, pos, p_tok, n_live):
  out = _return_sum(interpret, y, pos, p_tok, n_live)
  return out, (y, p_c, tok_c, n_live)


def _combine_bwd(interpret, kept, dout):
  y, p_c, tok_c, n_live = kept
  with jax.named_scope(scopes.MOE_ROUTE), jax.named_scope(scopes.MOE_RETURN):
    live = jnp.arange(tok_c.shape[0], dtype=jnp.int32) < n_live
    g = jnp.where(live[:, None], jnp.take(dout, tok_c, axis=0), 0)
    dy = g * p_c[:, None].astype(g.dtype)
    dp = jnp.sum(g * y, axis=1).astype(p_c.dtype)
  return dy, dp, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_share(h: jax.Array, w_router: jax.Array, w_gate: jax.Array,
              w_up: jax.Array, w_down: jax.Array, share: MoEShare,
              bias=None):
  """``h [T, d]`` -> (``[T, d]`` this share's part of the layer's output,
  counters).

  ``w_router [d, num_experts]``; ``w_gate``, ``w_up`` ``[held, d, f]`` and
  ``w_down [held, f, d]`` hold the held experts only. Expert ``e``:
  ``(silu(h w_gate[e]) * (h w_up[e])) w_down[e]``. The counters (int32
  scalars and one ``[held]`` vector, no gradient): ``assignments`` that fell
  on held experts, ``loads`` per held expert, ``computed``: the live rows in
  the group sizes that were handed to the grouped matmuls, summed over the
  pieces that really ran (``assignments - computed`` is what a capacity, or
  a tail not walked, would have dropped: 0). ``bias [num_experts]`` where and
  only where the share's router chooses under one; the counters then gain
  ``moved``, the (token, slot) choices that the unbiased scores would not
  have made."""
  if (bias is None) == share.router.selection_bias:
    raise ValueError(f"selection_bias={share.router.selection_bias} and "
                     f"{'no' if bias is None else 'a'} bias")
  first, count = share.held
  t, k = h.shape[0], share.top_k
  n = t * k
  with jax.named_scope(scopes.MOE):
    # de_moe_route's four parts partition it: every statement under it lies
    # under exactly one of them (telemetry/scopes.py)
    with jax.named_scope(scopes.MOE_ROUTE):
      with jax.named_scope(scopes.MOE_ROUTER):
        top_p, top_e = route(h, w_router, k, share.router, bias)
      with jax.named_scope(scopes.MOE_SORT):
        local = top_e.astype(jnp.int32) - first
        here = (local >= 0) & (local < count)
        # sort key: the held expert's local number; `count` for the rest, so
        # the assignments of held experts are the sorted stream's head
        key = jnp.where(here, local, count).reshape(n)
        # named: a caller that rematerialises the layer keeps these two and
        # neither sorts nor counts the keys again (layers/remat.py)
        order = checkpoint_name(
            jnp.argsort(key, stable=True).astype(jnp.int32), MOE_ROUTE)
        loads = checkpoint_name(
            jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32),
            MOE_ROUTE)
        ends = jnp.cumsum(loads)
        starts = ends - loads
        n_live = ends[-1]
        head = share.head_rows(n)
        tail = n - head
        chunk = min(head, tail)
        n_chunks = -(-tail // chunk) if tail else 0
        pad = n_chunks * chunk - tail
        order = jnp.concatenate([order, jnp.zeros((pad,), jnp.int32)])
        tok = order // k
        p_sorted = jnp.take(top_p.reshape(n), order)
        kernel = combine_kernel(head, h, k)
        if kernel is not None:
          # kept with `order`: the backward's combine reads it too
          pos = checkpoint_name(
              sorted_positions(key, count + 1).reshape(t, k), MOE_ROUTE)

    def groups_of(begin, size, whole):
      """The sorted stream's rows ``begin .. begin + size`` -> (which of them
      are live, the held experts' group sizes among them, how many were live
      rows of a group). ``whole``: the rows past the live count (zeros) are
      given to the last expert's group, so that every one of the ``size``
      rows is multiplied and the time does not follow the live count."""
      live = begin + jnp.arange(size, dtype=jnp.int32) < n_live
      sizes = jnp.clip(ends, begin, begin + size) \
          - jnp.clip(starts, begin, begin + size)
      done = jnp.sum(sizes)
      if whole:
        sizes = sizes.at[-1].add(size - done)
      return live, sizes, done

    def experts_of(x, sizes, w_gate, w_up, w_down):
      with jax.named_scope(scopes.MOE_EXPERTS):
        gate, up = grouped_mxu_dots(x, (w_gate, w_up), sizes)
        return grouped_mxu_dots(jax.nn.silu(gate) * up, (w_down,), sizes)[0]

    def rows_of(begin, size, whole, h, tok_c, p_c, w_gate, w_up, w_down):
      """-> (the weighted expert outputs ``[size, d]`` of the sorted stream's
      rows ``begin .. begin + size``, how many of them were live rows of a
      group)."""
      live, sizes, done = groups_of(begin, size, whole)
      with jax.named_scope(scopes.MOE_ROUTE), \
          jax.named_scope(scopes.MOE_DISPATCH):
        # rows past the live count are zeros going in and selected away
        # coming out (left to no group, the grouped matmul would not even
        # write them)
        x = jnp.where(live[:, None], jnp.take(h, tok_c, axis=0), 0)
      y = experts_of(x, sizes, w_gate, w_up, w_down)
      with jax.named_scope(scopes.MOE_ROUTE), \
          jax.named_scope(scopes.MOE_RETURN):
        return jnp.where(live[:, None], y * p_c[:, None].astype(y.dtype),
                         0), done

    # no checkpoint of its own: whether the head's residuals are kept or
    # rebuilt is its caller's plan (layers/remat.py)
    if kernel is None:
      y, computed = rows_of(0, head, True, h, tok[:head], p_sorted[:head],
                            w_gate, w_up, w_down)
      with jax.named_scope(scopes.MOE_ROUTE), \
          jax.named_scope(scopes.MOE_RETURN):
        out = jnp.zeros_like(h).at[tok[:head]].add(y)
    else:
      # the same sums read from the token's side (ops/pallas_moe_combine.py):
      # the weighting and the select ride the kernel's read of a row
      _, sizes, computed = groups_of(0, head, True)
      y = experts_of(_dispatch(kernel, h, tok[:head], pos, n_live), sizes,
                     w_gate, w_up, w_down)
      out = _combine(kernel, y, p_sorted[:head], tok[:head], pos,
                     lax.stop_gradient(top_p), n_live)

    if tail:
      @jax.checkpoint
      def tail_rows(h, tok_t, p_t, w_gate, w_up, w_down):
        """What the rows past the head add to ``out`` and how many of them
        were computed: zeros, at no cost, unless the router sent more than
        the head holds. (The ``cond`` lies INSIDE the checkpoint: around it,
        its branch's residuals, the weights and ``h`` among them, would
        leave it as outputs.)"""

        def walk():
          def one_chunk(carry, xs):
            acc, done = carry
            c, tok_c, p_c = xs
            begin = head + c * chunk
            y, live = jax.checkpoint(functools.partial(
                rows_of, begin, chunk, False))(
                    h, tok_c, p_c, w_gate, w_up, w_down)
            with jax.named_scope(scopes.MOE_ROUTE), \
                jax.named_scope(scopes.MOE_RETURN):
              return (acc.at[tok_c].add(y), done + live), None
          carry, _ = lax.scan(
              one_chunk, (jnp.zeros_like(h), jnp.int32(0)),
              (jnp.arange(n_chunks, dtype=jnp.int32),
               tok_t.reshape(n_chunks, chunk),
               p_t.reshape(n_chunks, chunk)))
          return carry

        return lax.cond(n_live > head, walk,
                        lambda: (jnp.zeros_like(h), jnp.int32(0)))

      more, walked = tail_rows(h, tok[head:], p_sorted[head:], w_gate, w_up,
                               w_down)
      out, computed = out + more, computed + walked
  counters: Dict[str, jax.Array] = {
      "assignments": jnp.sum(here, dtype=jnp.int32), "loads": loads,
      "computed": computed}
  if bias is not None:
    # read by counting tools alone: a step that returns no counters runs no
    # second choice
    with jax.named_scope(scopes.MOE), jax.named_scope(scopes.MOE_ROUTE), \
        jax.named_scope(scopes.MOE_ROUTER):
      _, unbiased = route(h, w_router, k, share.router)
      counters["moved"] = jnp.sum(
          jnp.all(top_e[:, :, None] != unbiased[:, None, :], axis=-1),
          dtype=jnp.int32)
  return out, counters
