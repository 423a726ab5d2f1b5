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

``SPARSE_SELECTION``: the keys a learned indexer chose for every query
(:func:`..layers.sparse_index.sparse_attention`), bit-packed: uint8
``[tiles, tile, key extent / 8]`` a run of tiles, 21 MB a layer at 16,384
positions and 5 MB at 8,192. The top-k over a tile's scores, the dearest
thing the indexer does that is not a product, runs once a layer a step, and
the backward attends under the very mask the forward did.

``SPARSE_ATTN_RESIDUALS``: that attention's output (float32
``[T, heads x head_dim]``, 268 MB a layer at 16,384 positions) and its
log-sum-exp (float32 ``[heads, T]``), what ``SPLASH_RESIDUALS`` is to the
splash kernel. On a TPU the attention is the kernels of
``ops/pallas_sparse_attn.py``, elsewhere XLA's, a tile of queries at a time;
on both the written-out backward rebuilds a block's probabilities from the
log-sum-exp, so with the two kept the rematerialised layer computes no score
at all. The heads' mean probabilities the indexer's loss is held to are NOT
kept (268 MB a layer at 8,192 positions): the backward runs their kernel
again.

``SHORT_CONV_IN``: a short-convolution mixer's first product
(:func:`..layers.short_conv.short_conv_mixer`): ``h W_in``, float32
``[T, 3 x hidden]`` (403 MB a layer at 16,384 positions of hidden 2,048),
the gates ``B``, ``C`` and the convolution's input ``u`` side by side: three
quarters of the mixer's forward arithmetic. With it kept the rematerialised
mixer rebuilds the gate chain from it (two multiplies and a three-tap
convolution an element, bound by memory) and runs ``W_out``'s product alone,
whose output the layer's feed-forward reads.

``MLA_LATENTS``: a latent-attention mixer's two down products
(:func:`..layers.latent_attention.latent_attention`): ``h W_dq`` and
``h W_dkv`` before their norms, float32 ``[T, q_lora_rank]`` and
``[T, kv_lora_rank + rope]`` (25 MB and 19 MB a layer at 8,192 positions of
768 and 576). What a product made is kept and the elementwise chain after it
(the two latent norms, the split) is rebuilt: the rematerialised mixer runs
the up products, the rotary pass and ``W_o`` again and neither down product.

``KDA_LATENTS``: the first halves of a per-channel delta-rule mixer's two
low-rank chains (``models/solar_open2.py::kda_mixer``): ``u W_fa`` and
``u W_ga``, float32 ``[T, 128]`` each (4 MB a layer each at 8,192
positions). Each is a pass over ``u [T, hidden]`` that makes 128 columns,
bound by memory: kept, the rebuilt mixer reads ``u`` for its three wide
projections alone. Those (``u W_q``, ``u W_k``, ``u W_v``: 101 MB a layer
together) are NOT kept: the step compiled for the chip stands at 16.3 of
17.18 GB without them (PERF.md, PR 51). The convolutions, the chains' second
halves and the rule itself, whose backward wants its own chunk matrices
anyway, are rebuilt with the layer. The rule's scan keeps its per-chunk
states as its forward's own output
(``layers/gated_delta.py::linear_state_scan``), inside the layer's backward;
its decayed pair products are one more ``jax.checkpoint``
(``_decayed_products``: the per-channel factors are rebuilt, never kept).

A layer that makes none of the named values is rematerialised whole: the
other models' ``attention="xla"`` (a tile loop under JAX's own transpose,
for tests and counting tools: nothing of it is kept, scores and
probabilities are rebuilt with the layer) and a dense MLP. No other ``jax.checkpoint`` stands on a decoder
layer's path but the two round the expert layer's tail, which no step walks
unless a router overflows the head (``layers/moe.py``), and the one round
the per-channel rule's pair products.
``tools/step_recompute.py <cell>`` counts, in a cell's compiled step, the calls
this plan is meant to leave and the bytes it spends.
"""

import jax

SPLASH_RESIDUALS = "splash_residuals"
MOE_ROUTE = "moe_route"
SPARSE_SELECTION = "sparse_selection"
SPARSE_ATTN_RESIDUALS = "sparse_attn_residuals"
SHORT_CONV_IN = "short_conv_in"
MLA_LATENTS = "mla_latents"
KDA_LATENTS = "kda_latents"
KEPT = (SPLASH_RESIDUALS, MOE_ROUTE, SPARSE_SELECTION, SPARSE_ATTN_RESIDUALS,
        SHORT_CONV_IN, MLA_LATENTS, KDA_LATENTS)


def checkpoint_layer(layer):
  """``layer`` rematerialised in the backward pass but for the values named
  in ``KEPT``."""
  return jax.checkpoint(
      layer, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
