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
serves its ``dx`` and its ``dw``.

Not for a product whose result decides something (the router's logits,
``layers/moe.py::route``) or feeds a recurrence (``layers/gated_delta.py``):
those stay at ``highest``.
"""

import functools
import operator

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.packed_table import mxu_operand_dtype


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def grouped_dots_rounded(cd, x, ws, sizes):
  """``lax.ragged_dot(x, w, sizes)`` for every ``w [groups, k, n]`` of the
  tuple ``ws``, the rows ``x [m, k]`` and the weights rounded to ``cd``; the
  results and every gradient in the dtype ``x`` and the weights share. ``x``
  is rounded ONCE for all of ``ws``, and ``dx`` is the sum over them."""
  return _grouped_dots_rounded_fwd(cd, x, ws, sizes)[0]


def _grouped_dots_rounded_fwd(cd, x, ws, sizes):
  x_cd = _written(x, cd)
  ws_cd = tuple(_written(w, cd) for w in ws)
  ys = tuple(lax.ragged_dot(x_cd, w_cd, sizes, preferred_element_type=x.dtype)
             for w_cd in ws_cd)
  return ys, (x_cd, ws_cd, sizes)


def _grouped_dots_rounded_bwd(cd, residuals, dys):
  x_cd, ws_cd, sizes = residuals
  dys_cd = [_written(dy, cd) for dy in dys]
  out = dys[0].dtype
  dx = functools.reduce(operator.add, (
      lax.ragged_dot(dy_cd, jnp.swapaxes(w_cd, 1, 2), sizes,
                     preferred_element_type=out)
      for dy_cd, w_cd in zip(dys_cd, ws_cd)))
  dws = tuple(lax.ragged_dot_general(x_cd, dy_cd, sizes, _GROUPED_DW,
                                     preferred_element_type=out)
              for dy_cd in dys_cd)
  return dx, dws, None


grouped_dots_rounded.defvjp(_grouped_dots_rounded_fwd,
                            _grouped_dots_rounded_bwd)


def grouped_mxu_dots(x, ws, sizes):
  """``lax.ragged_dot(x, w, sizes)`` for every ``w`` of the tuple ``ws``, as
  :func:`mxu_dot` forms a plain product: operands rounded once to what the
  MXU multiplies, float32 out of both passes; the plain ``lax.ragged_dot``s
  wherever the policy keeps the dtype."""
  cd = mxu_operand_dtype(x.dtype)
  if cd == x.dtype or any(w.dtype != x.dtype for w in ws):
    return tuple(lax.ragged_dot(x, w, sizes) for w in ws)
  return grouped_dots_rounded(cd, x, ws, sizes)
