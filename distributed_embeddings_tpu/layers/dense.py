"""The language models' plain dense product, ``x @ w``, as the MXU takes it.

At JAX's default matmul precision a TPU multiplies float32 operands as ONE
bfloat16 pass with float32 accumulation: the operands are rounded (to nearest
even) on their way into the MXU. :func:`mxu_dot` forms the same products and
says so to the compiler (``ops.packed_table.mxu_operand_dtype`` says when: on
a TPU at default precision; on any other backend, or where
``jax_default_matmul_precision`` asks for more, it is ``jnp.dot``), and keeps
float32 everywhere else:

  forward   ``dot(x_cd, w_cd)``, accumulated and returned in float32
  backward  ``dx = dot(dy_cd, w_cd^T)``, ``dw = dot(x_cd^T, dy_cd)``, both
            accumulated AND returned in float32

The backward is written out because JAX's own transpose of a
bfloat16-operand product hands ``dx`` and ``dw`` back in bfloat16, a lower
precision than the models' configurations state. The residuals are the rounded
operands. Parameters, activations between products, gradients and the
optimizer's moments stay float32.

The ACTIVATIONS (``x``, ``dy``) are rounded once and written, behind an
``optimization_barrier``: each is read by two to five products, and whatever
made it writes the bfloat16 copy in the same pass (half the bytes to write,
half for every product to read). The WEIGHT's cast is left to the compiler,
which fuses it into the product's operand read: a weight is used by one
product a pass, a written copy costs a pass over it and 85 MB a matrix, and
a barrier there was measured slower (PERF.md, PR 39: Olmo's step 483 ms
against 501 with the weights written too, 501 with no barrier, 656 before).

The expert layer's GROUPED products (``layers/moe.py``, ``lax.ragged_dot``)
are :func:`grouped_mxu_dots`, the same policy and the same written-out
backward a group at a time: one rounded copy of the rows serves every weight
they are multiplied with (``w_gate`` and ``w_up`` share one), each cotangent's
serves its ``dx`` and its ``dw``. On a TPU, at the widths they fit, the
products themselves run in the grouped-matmul kernels of
``ops/pallas_grouped_matmul.py``, tiled to the call's shape
(:func:`grouped_kernel` decides from what it can observe); ``lax.ragged_dot``
everywhere else.

Not for a product whose result decides something (the router's logits,
``layers/moe.py::route``) or feeds a recurrence (``layers/gated_delta.py``):
those stay at ``highest``.
"""

import functools
import operator

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import pallas_grouped_matmul as grouped_matmul
from ..ops.packed_table import mxu_operand_dtype
from ..telemetry import registry


def _dot(a, b, contract, out_dtype):
  return lax.dot_general(a, b, (contract, ((), ())),
                         preferred_element_type=out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def dot_rounded(cd, x, w):
  """``x [..., k] @ w [k, n]`` with both operands rounded to ``cd``; the
  result and both gradients in the dtype ``x`` and ``w`` share."""
  return _dot_rounded_fwd(cd, x, w)[0]


def _written(a, cd):
  """An activation rounded to ``cd`` and written out (module docstring)."""
  return lax.optimization_barrier(a.astype(cd))


def _dot_rounded_fwd(cd, x, w):
  x_cd, w_cd = _written(x, cd), w.astype(cd)
  return _dot(x_cd, w_cd, ((x.ndim - 1,), (0,)), x.dtype), (x_cd, w_cd)


def _dot_rounded_bwd(cd, residuals, dy):
  x_cd, w_cd = residuals
  dy_cd = _written(dy, cd)
  lead = tuple(range(dy.ndim - 1))
  dx = _dot(dy_cd, w_cd, ((dy.ndim - 1,), (1,)), dy.dtype)
  dw = _dot(x_cd, dy_cd, (lead, lead), dy.dtype)
  return dx, dw


dot_rounded.defvjp(_dot_rounded_fwd, _dot_rounded_bwd)


def mxu_dot(x, w):
  """``x [..., k] @ w [k, n]`` -> ``[..., n]`` in ``x.dtype``: operands
  rounded once to what the MXU multiplies (module docstring); ``jnp.dot``,
  with the gradients ``jnp.dot`` has, wherever the policy keeps the dtype."""
  cd = mxu_operand_dtype(x.dtype)
  if cd == x.dtype or w.dtype != x.dtype:
    return jnp.dot(x, w)
  return dot_rounded(cd, x, w)


# a grouped product's weight gradient: rows ``x [m, k]`` against ``dy [m, n]``,
# a group of rows at a time -> ``[groups, k, n]`` (what JAX's own transpose of
# ``lax.ragged_dot`` asks for)
_GROUPED_DW = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


# grouped products met while a program is traced, by what forms them (a
# forward call of two weights is two; its backward four more):
# `tools/bench_ragged_dot.py` prints them, `tests/test_pallas_grouped_matmul.py`
# holds that a model without experts moves neither
KERNEL_PRODUCTS = "grouped_products/kernel"
XLA_PRODUCTS = "grouped_products/ragged_dot"


def grouped_kernel(cd, x, ws):
  """What forms the grouped products of the rows ``x [m, k]`` with the weights
  ``ws [groups, k, n]``, both rounded to ``cd``: ``None`` for
  ``lax.ragged_dot``, else the kernels of ``ops/pallas_grouped_matmul.py`` with
  this for their ``interpret`` (``False``: on the chip). The kernels take
  float32 rows rounded to bfloat16 on a TPU wherever all three products of
  every weight have a tiling (:func:`ops.pallas_grouped_matmul.tiles`: whole
  lane tiles, blocks that fit VMEM). It reads what it can observe; no option
  names a path. A test that wants the kernels in Pallas's interpreter replaces
  this function."""
  if jax.default_backend() != "tpu" or cd != jnp.bfloat16 \
      or x.dtype != jnp.float32:
    return None
  m, size = x.shape[0], jnp.dtype(cd).itemsize
  for w in ws:
    groups, k, n = w.shape
    if not (grouped_matmul.tiles(m, k, n, groups, False, size)
            and grouped_matmul.tiles(m, n, k, groups, False, size)
            and grouped_matmul.tiles(m, k, n, groups, True, size)):
      return None
  return False


def _products(cd, kernel, x, ws, sizes):
  """The forward pass: ``x``'s rows by every weight of ``ws`` -> (the
  products, the residuals)."""
  x_cd = _written(x, cd)
  ws_cd = tuple(_written(w, cd) for w in ws)
  if kernel is None:
    dot = functools.partial(lax.ragged_dot, preferred_element_type=x.dtype)
  else:
    dot = functools.partial(grouped_matmul.grouped_dot, interpret=kernel)
  return tuple(dot(x_cd, w_cd, sizes) for w_cd in ws_cd), (x_cd, ws_cd, sizes)


def _cotangents(cd, kernel, residuals, dys):
  """The backward pass -> (``dx``, summed over the weights; every ``dw``)."""
  x_cd, ws_cd, sizes = residuals
  dys_cd = [_written(dy, cd) for dy in dys]
  out = dys[0].dtype
  if kernel is None:
    dx_dot = lambda dy_cd, w_cd: lax.ragged_dot(
        dy_cd, jnp.swapaxes(w_cd, 1, 2), sizes, preferred_element_type=out)
    dw_dot = lambda dy_cd: lax.ragged_dot_general(
        x_cd, dy_cd, sizes, _GROUPED_DW, preferred_element_type=out)
  else:
    dx_dot = lambda dy_cd, w_cd: grouped_matmul.grouped_dot(
        dy_cd, w_cd, sizes, transposed=True, interpret=kernel)
    dw_dot = lambda dy_cd: grouped_matmul.grouped_dw(
        x_cd, dy_cd, sizes, interpret=kernel)
  dx = functools.reduce(operator.add, map(dx_dot, dys_cd, ws_cd))
  return dx, tuple(map(dw_dot, dys_cd))


def _never_differentiated(*_):
  raise NotImplementedError("grouped_dots_rounded's backward is written out")


# Where the kernels run, each pass is entered through ONE jitted function: a
# step meets it a dozen times an expert layer (forward, rebuilt, backward),
# and a `jax.jit` traces its Python (the kernels' bodies, the walk's arrays)
# once a process and shape and is lowered once a module, whatever the call.
# The forward's besides is a `custom_vjp` that nothing differentiates: it runs
# inside the layer's `jax.checkpoint` (layers/remat.py), whose partial
# evaluation takes every `jit` it meets apart into a known and a staged copy,
# new jaxprs at every call, each lowered again; a `custom_vjp` call has no
# such rule and passes whole. What PR 44 learned of the combine kernel
# (layers/moe.py, `_return_sum`) and PR 52 of these products (PERF.md).
_kernel_products = jax.custom_vjp(
    jax.jit(_products, static_argnums=(0, 1)), nondiff_argnums=(0, 1))
_kernel_products.defvjp(_never_differentiated, _never_differentiated)
_kernel_cotangents = jax.jit(_cotangents, static_argnums=(0, 1))


def _one_trace():
  """A context in which the jitted passes trace to ONE jaxpr a shape: a
  layer's checkpoint traces its body with no abstract mesh set and its
  backward with an empty one, two keys of `jit`'s cache for one function."""
  return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


def _pass(plain, jitted, products, cd, rows, ws, *args):
  """One pass of ``products`` grouped products over ``rows`` and ``ws``,
  counted: ``plain(cd, None, *args)`` around ``lax.ragged_dot``, or where
  :func:`grouped_kernel` says so ``jitted(cd, interpret, *args)``."""
  kernel = grouped_kernel(cd, rows, ws)
  registry.counter(
      XLA_PRODUCTS if kernel is None else KERNEL_PRODUCTS).inc(products)
  if kernel is None:
    return plain(cd, kernel, *args)
  with _one_trace():
    return jitted(cd, kernel, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def grouped_dots_rounded(cd, x, ws, sizes):
  """``lax.ragged_dot(x, w, sizes)`` for every ``w [groups, k, n]`` of the
  tuple ``ws``, the rows ``x [m, k]`` and the weights rounded to ``cd``; the
  results and every gradient in the dtype ``x`` and the weights share. ``x``
  is rounded ONCE for all of ``ws``, and ``dx`` is the sum over them."""
  return _grouped_dots_rounded_fwd(cd, x, ws, sizes)[0]


def _grouped_dots_rounded_fwd(cd, x, ws, sizes):
  return _pass(_products, _kernel_products, len(ws), cd, x, ws, x, ws, sizes)


def _grouped_dots_rounded_bwd(cd, residuals, dys):
  # the forward's answer again: a cotangent has `x`'s rows and dtype, the
  # rounded weights the weights' shapes
  return (*_pass(_cotangents, _kernel_cotangents, 2 * len(dys), cd, dys[0],
                 residuals[1], residuals, dys), None)


grouped_dots_rounded.defvjp(_grouped_dots_rounded_fwd,
                            _grouped_dots_rounded_bwd)


def grouped_mxu_dots(x, ws, sizes):
  """``lax.ragged_dot(x, w, sizes)`` for every ``w`` of the tuple ``ws``, as
  :func:`mxu_dot` forms a plain product: operands rounded once to what the
  MXU multiplies, float32 out of both passes; the plain ``lax.ragged_dot``s
  wherever the policy keeps the dtype."""
  cd = mxu_operand_dtype(x.dtype)
  if cd == x.dtype or any(w.dtype != x.dtype for w in ws):
    registry.counter(XLA_PRODUCTS).inc(len(ws))
    return tuple(lax.ragged_dot(x, w, sizes) for w in ws)
  return grouped_dots_rounded(cd, x, ws, sizes)
