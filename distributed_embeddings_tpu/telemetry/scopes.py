"""The names of the step's device scopes: one vocabulary, no logic.

Call sites wrap a stage in ``jax.named_scope(<constant>)``. That is metadata
written while the step is traced (it lands in every HLO op's ``op_name``,
which the profiler shows per device op) and costs nothing at run time. A
name carries no ``/``: the name stack uses it as its separator. An op's
layer is the OUTERMOST of these names in its name stack; it is backward
where that component sits inside ``transpose(...)``.

The table, the call sites and how to read a profile by these names:
``docs/ARCHITECTURE.md`` section 18.6. ``benchmark/scope_reduce.py`` turns
them into per-layer device time.
"""

# Top-level scopes: every device op of a sparse train step lies in exactly one.
ROUTE = "de_route"  # id routing dp->mp: routing tensors, dedup/unique, the id all_to_all, mean counts
GATHER = "de_gather"  # the fused row gather per sparse class (ops/packed_table.py)
COMBINE = "de_combine"  # one-hot dense-class lookups, the activation exchange mp->dp (transposed: the cotangent exchange), output assembly
MODEL = "de_model"  # the user model, forward and backward; flax's module names stay beneath it
LOSS = "de_loss"  # the loss and the regularisers' penalties
DENSE_UPDATE = "de_dense_update"  # gradient reduction across chips, optax on dense leaves and dense-class tables, the guard's selects, the step counter
APPLY = "de_apply"  # delta streams, XLA's scatter-add, the Pallas apply kernel

# Child scopes: always inside a top-level one.
ONEHOT = "de_onehot"  # a dense class's windowed one-hot MXU lookup; inside de_combine
EXCHANGE = "de_exchange"  # the collectives of parallel/wire.py; inside de_route and de_combine
INTERACT = "de_interact"  # models/dlrm.py::dot_interact; inside de_model
ATTENTION = "de_attention"  # a softmax-attention mixer whole, whatever the model (models/sdar_moe.py, models/olmo_hybrid.py, models/laguna.py, models/lfm2_moe.py, models/solar_open2.py, models/glm_moe_lite.py: there layers/latent_attention.py, models/keye_sparse.py: there with the indexer that chooses its keys): the input's norm where the block has one, q/k/v/o projections, q/k norms, RoPE (layers/attention.py::rope), gate, attention under the model's mask description (layers/attention.py: attention_splash on a TPU, attention_xla elsewhere); inside de_model
WINDOW_ATTENTION = "de_window_attention"  # models/laguna.py: the mixer of a sliding_attention layer (causal, same document, i - j < sliding_window); inside de_attention
FULL_ATTENTION = "de_full_attention"  # models/laguna.py: the mixer of a full_attention layer (causal, same document); inside de_attention
MOE = "de_moe"  # layers/moe.py::moe_share and shared_expert, the whole expert layer; inside de_model
MOE_ROUTE = "de_moe_route"  # router, top-k, sort by expert, row gather, weighted scatter-combine; inside de_moe
MOE_EXPERTS = "de_moe_experts"  # the grouped matmuls over the held experts and the gate's silu; inside de_moe
MOE_SHARED = "de_moe_shared"  # layers/moe.py::shared_expert: the expert every token passes, a plain dense SwiGLU; inside de_moe
LM_HEAD = "de_lm_head"  # final norm and the vocabulary head; inside de_model
LINEAR_ATTENTION = "de_linear_attention"  # models/olmo_hybrid.py, models/solar_open2.py (there the rule's decay is per key channel, and the input's norm is inside): a gated-delta-rule mixer whole (projections, short convolutions, gates, the rule, gated output norm, W_o, the sublayer's norm); inside de_model
DELTA_RULE = "de_delta_rule"  # layers/gated_delta.py::chunk_gated_delta_rule and chunk_kda_rule, the chunked rule alone; inside de_linear_attention
SPARSE_INDEX = "de_sparse_index"  # models/keye_sparse.py, layers/sparse_index.py: the learned indexer whole (its projections, scores, top-k and its own KL loss); inside de_attention
MLP = "de_mlp"  # a dense SwiGLU MLP and the norm of its sublayer (models/olmo_hybrid.py: every layer's; models/laguna.py and models/lfm2_moe.py: the leading dense layers'); inside de_model
MTP = "de_mtp"  # models/glm_moe_lite.py::mtp_module, the multi-token-prediction module whole: the norms of its two inputs, W_eh, its decoder layer (with the scopes a layer has: its attention is ALSO under de_attention and its experts under de_moe), its final norm and the shared head (under de_lm_head); inside de_model
SHORT_CONV = "de_short_conv"  # models/lfm2_moe.py, layers/short_conv.py: a double-gated short-convolution mixer whole (the input's norm, W_in, the gate B * u, the causal convolution with its reset, the gate C * c, W_out); inside de_model

# Parts: always inside the child scope of their layer, one level finer: what an
# op-level account of a language-model step is read by (benchmark/scope_parts.py).
# The four de_moe_* parts partition de_moe_route, the three de_index_*
# parts de_sparse_index and the two de_conv_* parts de_short_conv but for the
# input's norm; the others leave their
# layer a remainder (norms, gates, the residual add) that is read as the
# layer less its parts and kernels. XLA fuses across a part's line, so a
# part is exact to a fusion.
ATTN_PROJ = "de_attn_proj"  # the matmuls with wq, wk, wv, wo (models/laguna.py: and wg), each model's own lines round layers/attention.py; inside de_attention (Laguna: inside de_window_attention / de_full_attention)
ATTN_QK = "de_attn_qk"  # q and k between projection and kernel: q/k norms, rope, the head_dim ** -0.5 scaling; inside de_attention (Laguna: as above)
ATTN_CORE = "de_attn_core"  # the call of attend(...), layers/attention.py::attention_splash (or attention_xla): transposes and casts to the kernel's layout, the kernel, the way back; inside de_attention (Laguna: as above). models/keye_sparse.py: the attention under the selection (layers/sparse_index.py): on a TPU the de_sparse_attn_fwd/dq/dkv kernels of ops/pallas_sparse_attn.py with the assembly of their int8 mask, its block counts and the casts to their layout; elsewhere XLA's scores, masked softmax and products with v a tile at a time, forward and the written-out backward
INDEX_SCORES = "de_index_scores"  # the indexer's projections, its key's LayerNorm, its rotary pass, the score product, the ReLU and the weighted sum over index heads (backward: the score again and the three products of its gradient); inside de_sparse_index
INDEX_SELECT = "de_index_select"  # layers/sparse_index.py::select_topk and the packing of the mask: forward only, the plan keeps the selection; inside de_sparse_index
INDEX_LOSS = "de_index_loss"  # the KL's target (the heads' mean of the main attention's probabilities: on a TPU the de_sparse_attn_mean kernel, once a direction), the indexer's softmax over the selection and the KL (backward: its gradient into the score); inside de_sparse_index
MLA_DOWN = "de_mla_down"  # layers/latent_attention.py: the products with W_dq and W_dkv, the two latent norms and the split of the shared rotary key off the key-value latent; inside de_attention
MLA_UP = "de_mla_up"  # layers/latent_attention.py: the products with W_uq and W_ukv, the split into a head's own and rotary parts, the broadcast of the shared rotary key to every head and the join; inside de_attention (W_o is de_attn_proj, the rotary pass and the scaling de_attn_qk, the kernel's call de_attn_core)
MOE_ROUTER = "de_moe_router"  # layers/moe.py::route whole: the router's matmul, the scores, top_k, renormalisation; inside de_moe_route
MOE_SORT = "de_moe_sort"  # the sort key, argsort, bincount, the cumulative sums, tok, p_sorted; inside de_moe_route
MOE_DISPATCH = "de_moe_dispatch"  # the gather of the sorted stream's rows of h with its select (transposed: the scatter-add of the cotangent into h; on a TPU the head's is the kernel de_moe_combine, ops/pallas_moe_combine.py); inside de_moe_route
MOE_RETURN = "de_moe_return"  # the weighting y * p with its select and the scatter-add into the output, head and tail (on a TPU the head's three are one call of the kernel de_moe_combine; transposed: a gather); inside de_moe_route
LINATTN_PROJ = "de_linattn_proj"  # the matmuls with wq, wk, wv, wg, wb, wa, wo (models/solar_open2.py: wq, wk, wv, wo; its gates' products are de_linattn_gate); inside de_linear_attention
LINATTN_CONV = "de_linattn_conv"  # short(...): causal_conv with its reset and the silu, three times; inside de_linear_attention
LINATTN_GATE = "de_linattn_gate"  # models/solar_open2.py::kda_mixer: what stands between the projections and the rule besides the convolutions, and behind it: the two low-rank chains (W_fa W_fb to the per-channel decay, W_ga W_gb to the output gate), softplus and the decay, beta with its product, the sigmoid-gated output norm; bound by memory; inside de_linear_attention (models/olmo_hybrid.py's mixer does not enter it)
CONV_PROJ = "de_conv_proj"  # the matmuls with w_in and w_out; inside de_short_conv
CONV_GATE = "de_conv_gate"  # the gate chain between them: B * u, causal_conv with its reset, C * c; bound by memory where the products are bound by the MXU; inside de_short_conv

TOP_LEVEL = (ROUTE, GATHER, COMBINE, MODEL, LOSS, DENSE_UPDATE, APPLY)
CHILDREN = (ONEHOT, EXCHANGE, INTERACT)
# a language model's, all inside de_model (benchmark/scope_children*.py read them)
LM_CHILDREN = (ATTENTION, MOE, MOE_ROUTE, MOE_EXPERTS, LM_HEAD,
               LINEAR_ATTENTION, DELTA_RULE, MLP, WINDOW_ATTENTION,
               FULL_ATTENTION, MOE_SHARED, SPARSE_INDEX, SHORT_CONV, MTP)
# a language model's parts, each inside one of LM_CHILDREN
PARTS = (ATTN_PROJ, ATTN_QK, ATTN_CORE, MOE_ROUTER, MOE_SORT, MOE_DISPATCH,
         MOE_RETURN, LINATTN_PROJ, LINATTN_CONV, INDEX_SCORES, INDEX_SELECT,
         INDEX_LOSS, CONV_PROJ, CONV_GATE, MLA_DOWN, MLA_UP, LINATTN_GATE)
