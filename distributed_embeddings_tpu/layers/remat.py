"""A decoder layer's rematerialisation plan, decided in one place.

A language model of this package keeps one layer's activations at a time:
each decoder layer runs under :func:`checkpoint_layer`, so its forward is run
again when its backward comes. What is expensive to run twice and nearly free
to keep is NAMED where it is made (``jax.ad_checkpoint.checkpoint_name``) and
outlives its layer; everything else is recomputed. The names:

``SPLASH_RESIDUALS``: the splash-attention kernel's ``out`` (bfloat16
``[S, heads x head_dim]``) and ``logsumexp`` (float32 ``[heads, S]``), named
inside JAX's kernel (its factory's ``residual_checkpoint_name``). With them
kept, the rematerialised layer needs no forward kernel: the projections and
the rotary pass rebuild ``q``, ``k``, ``v`` and the backward kernels take the
rest from here.

``MOE_ROUTE``: the expert layer's sorted order of assignments and the held
experts' loads (:func:`..layers.moe.moe_share`; int32, under 0.6 MB a layer),
which every later line of that layer hangs on: the stable argsort and the
bincount run once. Nothing differentiable of the expert layer is named: its
head's grouped matmuls are rematerialised with the layer, once.

A layer that makes none of the named values (``attention="xla"``, a dense MLP)
is rematerialised whole. No other ``jax.checkpoint`` stands on a decoder
layer's path but the two round the expert layer's tail, which no step walks
unless a router overflows the head (``layers/moe.py``).
``tools/step_recompute.py <cell>`` counts, in a cell's compiled step, the calls
this plan is meant to leave and the bytes it spends.
"""

import jax

SPLASH_RESIDUALS = "splash_residuals"
MOE_ROUTE = "moe_route"
KEPT = (SPLASH_RESIDUALS, MOE_ROUTE)


def checkpoint_layer(layer):
  """``layer`` rematerialised in the backward pass but for the values named
  in ``KEPT``."""
  return jax.checkpoint(
      layer, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
