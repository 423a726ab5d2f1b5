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

Not for a product whose result decides something (the router's logits,
``layers/moe.py::route``) or feeds a recurrence (``layers/gated_delta.py``):
those stay at ``highest``.
"""

import functools

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
