"""Every instruction of the sparse train step lies under a scope of
``telemetry/scopes.py``: coverage by count, on the compiled HLO of the toy
DLRM and the toy zoo step, at world 1 and on a mesh of four virtual devices.
And the parts of a language-model step (``scopes.PARTS``): where each lies,
in which passes, and what a rematerialised layer does not run again, on the
LOWERED text of the seven toy models' steps (the CPU's compiler merges a
rebuilt op with its forward twin; the TPU's barrier forbids that).
"""

import collections
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import test_glm_moe_lite
import test_keye_sparse
import test_laguna
import test_lfm2_moe
import test_olmo_hybrid
import test_sdar_moe
import test_solar_open2
from distributed_embeddings_tpu.layers import TableConfig, remat
from distributed_embeddings_tpu.layers.decoder import next_token_loss
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models import (
    DLRM,
    SyntheticModel,
    bce_loss,
    glm_moe_lite,
    keye_sparse,
    laguna,
    lfm2_moe,
    olmo_hybrid,
    sdar_moe,
    solar_open2,
)
from distributed_embeddings_tpu.models.dlrm import dlrm_embedding_plan
from distributed_embeddings_tpu.models.synthetic import (
    EmbeddingGroup,
    SyntheticModelConfig,
    expand_tables,
)
from distributed_embeddings_tpu.ops.packed_table import (
    adagrad_rule,
    adam_rule,
    sgd_rule,
)
from distributed_embeddings_tpu.parallel import create_mesh
from distributed_embeddings_tpu.telemetry import scopes
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

BATCH = 64
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")
NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element")


def _dlrm(world):
  rows = [40, 6000, 7, 9000, 12, 5000]  # small ones one-hot, big ones sparse
  model = DLRM(vocab_sizes=rows, embedding_dim=16, bottom_mlp=(32, 16),
               top_mlp=(32, 1), world_size=world, strategy="memory_balanced",
               batch_hint=BATCH, compute_dtype=jnp.float32)
  plan = dlrm_embedding_plan(rows, 16, world, "memory_balanced",
                             batch_hint=BATCH)
  cats = [jnp.zeros((BATCH,), jnp.int32) for _ in rows]
  acts = [jnp.zeros((2, 16), jnp.float32) for _ in rows]
  return model, plan, sgd_rule(0.1), optax.sgd(0.1), cats, acts, 13


def _zoo(world):
  cfg = SyntheticModelConfig(
      name="toy", embedding_groups=(
          EmbeddingGroup(2, (1, 4), 2000, 8, True),   # shared, multi-hot
          EmbeddingGroup(3, (1,), 3000, 16, False),
          EmbeddingGroup(2, (1,), 20, 8, False)),     # one-hot dense class
      mlp_sizes=(32, 16), num_numerical_features=4, interact_stride=None)
  tables, tmap, hotness = expand_tables(cfg)
  model = SyntheticModel(config=cfg, world_size=world,
                         strategy="memory_balanced", dense_row_threshold=64,
                         batch_hint=BATCH)
  plan = DistEmbeddingStrategy(tables, world, "memory_balanced",
                               input_table_map=tmap, dense_row_threshold=64,
                               input_hotness=hotness, batch_hint=BATCH)
  cats = [jnp.zeros((BATCH,) if h == 1 else (BATCH, h), jnp.int32)
          for h in hotness]
  acts = [jnp.zeros((2, tables[t].output_dim), jnp.float32) for t in tmap]
  return (model, plan, adagrad_rule(0.01), optax.adagrad(0.01), cats, acts, 4)


def _compiled_step(family, world) -> str:
  model, plan, rule, opt, cats, acts, n_num = family(world)
  numerical = jnp.zeros((BATCH, n_num), jnp.float32)
  dense = model.init(jax.random.PRNGKey(0), numerical[:2],
                     [c[:2] for c in cats], emb_acts=acts)["params"]
  mesh = create_mesh(world, devices=jax.devices()[:world]) \
      if world > 1 else None
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1), mesh=mesh)
  labels = jnp.zeros((BATCH,), jnp.float32)
  step = make_sparse_train_step(model, plan, bce_loss, opt, rule, mesh, state,
                                (numerical, cats, labels), donate=False)
  return step.lower(state, numerical, cats, labels).compile().as_text()


def _layer(op_name: str):
  """(top-level scope, backward) of an ``op_name``, (None, False) without."""
  for part in op_name.split(";")[0].split("/"):
    inner = _WRAPPED.match(part)
    if inner and inner.group(1) in scopes.TOP_LEVEL:
      return inner.group(1), "transpose(" in part
  return None, False


# What may lie outside every top-level scope, as (opcode, op_name) patterns.
# The compiler's own instructions: layout copies, fusion wrappers, rewritten
# dots and hoisted constant broadcasts come with no op_name at all, or with
# one that stops at the functions (``jit(..)/jit(local_step)[/shard_map]``)
# or names a compiler temporary (``broadcast.12``) or a parameter.
COMPILER_MADE = re.compile(
    r"^$|^jit\(\w+\)(/jit\(\w+\))*(/shard_map)?(/[a-z\-]+\.\d+)?$"
    r"|^[a-z\-]+\.\d+$|^state\[")
# The program's own, written out: the step counter's add.
UNSCOPED_BY_DESIGN = re.compile(r"^jit\(\w+\)(/jit\(\w+\))*(/shard_map)?/add$")


def _account(text, made_too=lambda name: False):
  """The compiled step's instructions that do work, by where they lie:
  -> (``(top-level scope, backward) -> count``, the strays' lines, how many
  the compiler made (or ``made_too`` says so of their ``op_name``), how many
  in all)."""
  seen = collections.Counter()
  stray, total, made = [], 0, 0
  for line in text.splitlines():
    m = _INSTRUCTION.match(line)
    if not m or m.group(1) in NO_WORK:
      continue
    total += 1
    name = _OP_NAME.search(line)
    name = name.group(1) if name else ""
    scope, backward = _layer(name)
    if scope is not None:
      seen[(scope, backward)] += 1
    elif COMPILER_MADE.match(name) or made_too(name):
      made += 1
    elif UNSCOPED_BY_DESIGN.match(name):
      if m.group(1) not in ("add", "fusion"):
        stray.append(line.strip()[:200])
    else:
      stray.append(line.strip()[:200])
  return seen, stray, made, total


@pytest.mark.parametrize("family,world", [
    (_dlrm, 1), (_zoo, 1), (_dlrm, 4), (_zoo, 4)],
    ids=["dlrm-world1", "zoo-world1", "dlrm-world4", "zoo-world4"])
def test_every_instruction_of_the_step_has_a_top_level_scope(family, world):
  text = _compiled_step(family, world)
  seen, stray, made, total = _account(text)
  assert not stray, "\n".join(stray[:20])
  assert made < 0.3 * total, (made, total)
  for scope in scopes.TOP_LEVEL:
    assert seen[(scope, False)], f"no forward op under {scope}"
  # the differentiated tail has both directions; routing, the fused gather,
  # the dense update and the sparse apply lie outside autodiff
  for scope in (scopes.COMBINE, scopes.MODEL, scopes.LOSS):
    assert seen[(scope, True)], f"no backward op under {scope}"
  for scope in (scopes.ROUTE, scopes.GATHER, scopes.DENSE_UPDATE,
                scopes.APPLY):
    assert not seen[(scope, True)], f"{scope} has a backward op"
  for child in (scopes.ONEHOT,) + ((scopes.EXCHANGE,) if world > 1 else ()) \
      + ((scopes.INTERACT,) if family is _dlrm else ()):
    assert f"{child}/" in text or f"{child})" in text, child


def test_the_vocabulary_is_one_flat_set_of_names():
  names = scopes.TOP_LEVEL + scopes.CHILDREN + scopes.LM_CHILDREN \
      + scopes.PARTS
  assert len(set(names)) == len(names) == 41
  for n in names:
    assert n.startswith("de_") and "/" not in n and "(" not in n
  declared = {v for k, v in vars(scopes).items()
              if k.isupper() and isinstance(v, str)}
  assert declared == set(names)


# ---- the parts of a language-model step ---------------------------------------
# the toy models of tests/test_sdar_moe.py, test_olmo_hybrid.py, test_laguna.py,
# test_keye_sparse.py, test_lfm2_moe.py, test_glm_moe_lite.py,
# test_solar_open2.py
LM_TOYS = {
    "glm_moe_lite": (glm_moe_lite.GlmMoeLite, glm_moe_lite.mtp_training_loss,
                     test_glm_moe_lite.TOY),
    "lfm2_moe": (lfm2_moe.Lfm2Moe, next_token_loss, test_lfm2_moe.TOY),
    "solar_open2": (solar_open2.SolarOpen2, next_token_loss,
                    test_solar_open2.TOY),
    "sdar_moe": (sdar_moe.SDARMoE, sdar_moe.block_diffusion_loss,
                 dataclasses.replace(test_sdar_moe.TOY, num_experts=8,
                                     num_experts_per_tok=2,
                                     experts_held=(0, 4))),
    "olmo_hybrid": (olmo_hybrid.OlmoHybrid, next_token_loss,
                    test_olmo_hybrid.TOY),
    "laguna": (laguna.Laguna, next_token_loss, test_laguna.TOY),
    "keye_sparse": (keye_sparse.KeyeSparse, keye_sparse.sparse_training_loss,
                    dataclasses.replace(test_keye_sparse.TOY,
                                        num_hidden_layers=1,
                                        experts_held=(0, 4))),
}
# part -> the scope the vocabulary's table puts it inside
INSIDE = {
    scopes.ATTN_PROJ: scopes.ATTENTION, scopes.ATTN_QK: scopes.ATTENTION,
    scopes.ATTN_CORE: scopes.ATTENTION, scopes.MOE_ROUTER: scopes.MOE_ROUTE,
    scopes.MOE_SORT: scopes.MOE_ROUTE, scopes.MOE_DISPATCH: scopes.MOE_ROUTE,
    scopes.MOE_RETURN: scopes.MOE_ROUTE,
    scopes.LINATTN_PROJ: scopes.LINEAR_ATTENTION,
    scopes.LINATTN_CONV: scopes.LINEAR_ATTENTION,
    scopes.LINATTN_GATE: scopes.LINEAR_ATTENTION,
    scopes.INDEX_SCORES: scopes.SPARSE_INDEX,
    scopes.INDEX_SELECT: scopes.SPARSE_INDEX,
    scopes.INDEX_LOSS: scopes.SPARSE_INDEX,
    scopes.CONV_PROJ: scopes.SHORT_CONV, scopes.CONV_GATE: scopes.SHORT_CONV,
    scopes.MLA_DOWN: scopes.ATTENTION, scopes.MLA_UP: scopes.ATTENTION}
INDEX_PARTS = (scopes.INDEX_SCORES, scopes.INDEX_SELECT, scopes.INDEX_LOSS)
ROUTE_PARTS = (scopes.MOE_ROUTER, scopes.MOE_SORT, scopes.MOE_DISPATCH,
               scopes.MOE_RETURN)
PARTS_OF = {
    "glm_moe_lite": (scopes.ATTN_PROJ, scopes.ATTN_QK, scopes.ATTN_CORE,
                     scopes.MLA_DOWN, scopes.MLA_UP) + ROUTE_PARTS,
    "sdar_moe": (scopes.ATTN_PROJ, scopes.ATTN_QK, scopes.ATTN_CORE)
    + ROUTE_PARTS,
    "olmo_hybrid": (scopes.ATTN_PROJ, scopes.ATTN_QK, scopes.ATTN_CORE,
                    scopes.LINATTN_PROJ, scopes.LINATTN_CONV),
    "laguna": (scopes.ATTN_PROJ, scopes.ATTN_QK, scopes.ATTN_CORE)
    + ROUTE_PARTS,
    "keye_sparse": (scopes.ATTN_PROJ, scopes.ATTN_QK, scopes.ATTN_CORE)
    + INDEX_PARTS + ROUTE_PARTS,
    "lfm2_moe": (scopes.ATTN_PROJ, scopes.ATTN_QK, scopes.ATTN_CORE,
                 scopes.CONV_PROJ, scopes.CONV_GATE) + ROUTE_PARTS,
    "solar_open2": (scopes.ATTN_PROJ, scopes.ATTN_QK, scopes.ATTN_CORE,
                    scopes.LINATTN_PROJ, scopes.LINATTN_CONV,
                    scopes.LINATTN_GATE) + ROUTE_PARTS}
# the passes a part has ops in where not all three: what `remat.KEPT` names is
# made in the forward and never rebuilt (the selection; the attention's output
# and log-sum-exp, so the rebuilt layer computes no score)
PASSES_OF = {
    ("keye_sparse", scopes.ATTN_CORE): {"forward", "backward"},
    ("keye_sparse", scopes.INDEX_SELECT): {"forward"},
    ("keye_sparse", scopes.INDEX_LOSS): {"forward", "backward"}}
REMAT = "rematted_computation"   # jax.checkpoint's rebuilt forward
_LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)


def _lowered(name):
  """The toy model's sparse train step (token table as a sequence input
  under summed Adam), lowered."""
  model_cls, loss, cfg = LM_TOYS[name]
  batch = 2
  cats = jnp.zeros((batch, cfg.seq_len), jnp.int32)
  numerical = jnp.full((batch, getattr(cfg, "n_numerical", cfg.seq_len)),
                       0.5, jnp.float32)
  labels = {"targets": cats}
  if name == "glm_moe_lite":     # its prediction module's, two ahead
    labels["targets_2"] = cats
  plan = DistEmbeddingStrategy(
      [TableConfig(cfg.vocab_size, cfg.hidden_size, combiner=None)], 1,
      "memory_balanced", input_table_map=[0], dense_row_threshold=0,
      input_hotness=[cfg.seq_len], batch_hint=batch)
  model = model_cls(cfg)
  dense = model.init(jax.random.PRNGKey(0), numerical, None, emb_acts=[
      jnp.zeros((batch, cfg.seq_len, cfg.hidden_size))])["params"]
  rule, opt = adam_rule(3e-3, summed=True), optax.adam(3e-3)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(1))
  step = make_sparse_train_step(model, plan, loss, opt, rule, None, state,
                                (numerical, [cats], labels), donate=False)
  return step.lower(state, numerical, [cats], labels)


def _lowered_step(name) -> str:
  """:func:`_lowered` as text, with every op's name stack."""
  return _lowered(name).as_text(debug_info=True)


def _logit_sum(out):
  """A scalar of a model's outputs that reaches every leaf: the logits' sum
  and, where there is a prediction module, its logits' too."""
  total = jnp.sum(out["logits"])
  return total + jnp.sum(out["mtp_logits"]) if "mtp_logits" in out else total


def _components(name_stack: str):
  """The names of a name stack, each out of its ``jvp(``/``transpose(``
  wrappers."""
  return [m.group(1) if (m := _WRAPPED.match(part)) else part
          for part in name_stack.split("/")]


def _pass_of(name_stack: str) -> str:
  if REMAT in name_stack.split("/"):
    return "rebuilt"
  return "backward" if "transpose(" in name_stack else "forward"


@pytest.fixture(scope="module", params=sorted(LM_TOYS))
def lm_stacks(request):
  """(toy, the name stacks of its lowered step's ops)."""
  lowered = _lowered(request.param)
  stacks = {s for _, s in _LOC.findall(lowered.as_text(debug_info=True))}
  if request.param == "keye_sparse":
    # a tile loop's body is lowered as a function of its own, whose ops'
    # name stacks start at the call; the compiled program's `op_name`s are
    # whole again (what a trace of the chip shows)
    stacks |= set(_OP_NAME.findall(lowered.compile().as_text()))
  return request.param, sorted(s for s in stacks if s.startswith("jit("))


def test_every_part_lies_only_inside_its_layers_scope(lm_stacks):
  name, stacks = lm_stacks
  seen = collections.Counter()
  for stack in stacks:
    names = _components(stack)
    parts = [n for n in names if n in scopes.PARTS]
    assert len(parts) <= 1, stack           # a part holds no other part
    for part in parts:
      seen[part] += 1
      before = names[:names.index(part)]
      assert INSIDE[part] in before and scopes.MODEL in before, stack
      if name == "laguna" and INSIDE[part] == scopes.ATTENTION:
        # a part of a Laguna mixer lies inside the layer's kind
        assert before[-1] in (scopes.WINDOW_ATTENTION,
                              scopes.FULL_ATTENTION), stack
      elif name == "keye_sparse" and INSIDE[part] != scopes.MOE_ROUTE:
        # the tile loop's own components (`while`, `body`) may lie between
        assert [n for n in before if n.startswith("de_")][-1] \
            == INSIDE[part], stack
      else:
        assert before[-1] == INSIDE[part], stack
  assert set(seen) == set(PARTS_OF[name])


def test_the_three_index_parts_partition_the_indexer(lm_stacks):
  name, stacks = lm_stacks
  under = [s for s in stacks if scopes.SPARSE_INDEX in _components(s)]
  assert bool(under) == (name == "keye_sparse")
  for stack in under:
    names = _components(stack)
    assert sum(p in names for p in INDEX_PARTS) == 1, stack
    assert scopes.ATTENTION in names[:names.index(scopes.SPARSE_INDEX)]
    assert scopes.ATTN_CORE not in names, stack


def test_the_four_route_parts_partition_the_expert_route(lm_stacks):
  name, stacks = lm_stacks
  under = [s for s in stacks if scopes.MOE_ROUTE in _components(s)]
  assert bool(under) == (name != "olmo_hybrid")
  for stack in under:
    assert sum(p in _components(stack) for p in ROUTE_PARTS) == 1, stack


def test_the_two_conv_parts_partition_the_mixer_but_for_its_norm(lm_stacks):
  """Every op under ``de_short_conv`` lies under one of its two parts or is
  the input's norm or the residual add, which lie straight under it."""
  name, stacks = lm_stacks
  under = [s for s in stacks if scopes.SHORT_CONV in _components(s)]
  assert bool(under) == (name == "lfm2_moe")
  for stack in under:
    names = _components(stack)
    parts = [p for p in (scopes.CONV_PROJ, scopes.CONV_GATE) if p in names]
    assert len(parts) <= 1, stack
    assert scopes.MODEL in names[:names.index(scopes.SHORT_CONV)], stack
    assert scopes.ATTENTION not in names and scopes.MLP not in names, stack
  if not under:
    return
  gate = {_components(s)[-1] for s in under
          if scopes.CONV_GATE in _components(s)}
  assert {"mul", "pad"} <= gate and "dot_general" not in gate
  assert {_components(s)[-1] for s in under
          if scopes.CONV_PROJ in _components(s)} >= {"dot_general"}


def test_every_instruction_of_the_kda_models_step_has_a_top_level_scope():
  """The Solar-Open2 toy's compiled sparse train step, as the DLRM and zoo
  steps above: every instruction that does work lies under exactly one
  top-level scope (an ``op_name`` holds one outermost), none strays, and
  the model's own lie under ``de_model`` in both directions. What XLA's CPU
  compiler makes of a sort and of a running sum are computations of its own
  whose instructions carry a bare name and no stack (``lt_to``,
  ``reduce_window_sum``; a label's parameter likewise): counted as the
  compiler's."""
  seen, stray, made, total = _account(
      _lowered("solar_open2").compile().as_text(),
      made_too=lambda name: "/" not in name)
  assert not stray, "\n".join(stray[:20])
  assert made < 0.3 * total, (made, total)
  # one sequence input on one device: what de_combine holds is a reshape of
  # the gathered rows, which the compiler folds away in both directions
  for scope in set(scopes.TOP_LEVEL) - {scopes.COMBINE}:
    assert seen[(scope, False)], f"no forward op under {scope}"
  for scope in (scopes.MODEL, scopes.LOSS):
    assert seen[(scope, True)], f"no backward op under {scope}"
  for scope in (scopes.ROUTE, scopes.GATHER, scopes.DENSE_UPDATE,
                scopes.APPLY):
    assert not seen[(scope, True)], f"{scope} has a backward op"
  assert set(scope for scope, _ in seen) <= set(scopes.TOP_LEVEL)
  # the model is most of the step
  assert seen[(scopes.MODEL, False)] + seen[(scopes.MODEL, True)] > total / 2


def test_the_kda_mixers_parts_lie_where_the_table_says(lm_stacks):
  """Under ``de_linear_attention`` an op lies under at most one of the three
  parts and the rule; ``de_linattn_gate`` holds the low-rank chains'
  products, softplus's and the sigmoids' ops and nothing of the rule or of
  the convolutions; Olmo-Hybrid's mixer does not enter it; the rule's pair
  products are rebuilt inside the backward (the one ``jax.checkpoint`` on
  the layer's path besides the layer's own)."""
  name, stacks = lm_stacks
  gate = [s for s in stacks if scopes.LINATTN_GATE in _components(s)]
  assert bool(gate) == (name == "solar_open2")
  if not gate:
    return
  inside = (scopes.LINATTN_PROJ, scopes.LINATTN_CONV, scopes.LINATTN_GATE,
            scopes.DELTA_RULE)
  for stack in stacks:
    names = _components(stack)
    if scopes.LINEAR_ATTENTION in names:
      assert sum(p in names for p in inside) <= 1, stack
      assert scopes.ATTENTION not in names and scopes.MOE not in names, stack
  last = {_components(s)[-1] for s in gate}
  assert {"dot_general", "logistic", "exp", "mul"} <= last, last
  assert "triangular_solve" not in last and "pad" not in last
  rule = [s for s in stacks if scopes.DELTA_RULE in _components(s)]
  assert {"triangular_solve", "while", "cumsum"} <= {
      _components(s)[-1] for s in rule}
  # the pair products' own checkpoint: rebuilt twice over inside the
  # layer's backward (the layer's rebuilt forward, then the rule's)
  assert any(_components(s).count(REMAT) == 1 and "transpose(" in s
             for s in rule)


def test_the_latent_parts_and_the_prediction_module_lie_where_the_table_says(
    lm_stacks):
  """Under ``de_attention`` of the latent mixer an op lies under at most one
  of the five parts, the two latent parts hold the four latent products
  between them, and the rebuilt forward runs no down product (the plan keeps
  the latents). Everything under ``de_mtp`` lies inside ``de_model`` (the
  module with a whole layer's scopes beneath it, its head under
  ``de_lm_head``) or inside ``de_loss`` (its cross-entropy)."""
  name, stacks = lm_stacks
  module = [s for s in stacks if scopes.MTP in _components(s)]
  assert bool(module) == (name == "glm_moe_lite")
  if not module:
    assert not any(p in _components(s) for s in stacks
                   for p in (scopes.MLA_DOWN, scopes.MLA_UP))
    return
  for stack in module:
    names = _components(stack)
    before = names[:names.index(scopes.MTP)]
    assert (scopes.MODEL in before) != (scopes.LOSS in before), stack
  beneath = {n for s in module for n in _components(s)
             if n.startswith("de_")}
  assert {scopes.ATTENTION, scopes.MLA_DOWN, scopes.MLA_UP, scopes.ATTN_CORE,
          scopes.MOE, scopes.MOE_ROUTER, scopes.MOE_SHARED,
          scopes.LM_HEAD, scopes.LOSS} <= beneath
  assert scopes.MLP not in beneath        # its layer is an expert layer
  # the trunk's head lies under de_lm_head and not under de_mtp
  assert any(scopes.LM_HEAD in _components(s)
             and scopes.MTP not in _components(s) for s in stacks)
  products = collections.Counter()
  for stack in stacks:
    names = _components(stack)
    if scopes.ATTENTION not in names:
      continue
    assert sum(p in names for p in PARTS_OF[name][:5]) <= 1, stack
    if names[-1] == "dot_general":
      part = [p for p in (scopes.MLA_DOWN, scopes.MLA_UP) if p in names]
      products[(part[0] if part else None, _pass_of(stack))] += 1
  assert products[(scopes.MLA_DOWN, "forward")] > 0
  assert products[(scopes.MLA_UP, "rebuilt")] > 0
  assert products[(scopes.MLA_DOWN, "rebuilt")] == 0
  assert any(_pass_of(s) == "rebuilt" and scopes.MLA_DOWN in _components(s)
             for s in stacks)               # the latent norms are rebuilt


def test_every_part_has_ops_in_all_three_passes(lm_stacks):
  """Every decoder layer runs under ``checkpoint_layer``, so what a part
  holds is traced forward, rebuilt and backward."""
  name, stacks = lm_stacks
  passes = collections.defaultdict(set)
  for stack in stacks:
    for part in set(_components(stack)) & set(scopes.PARTS):
      passes[part].add(_pass_of(stack))
  for part in PARTS_OF[name]:
    assert passes[part] == PASSES_OF.get(
        (name, part), {"forward", "rebuilt", "backward"}), part


def test_what_the_plan_keeps_is_not_rebuilt_under_its_part(lm_stacks):
  """``remat.KEPT`` by name (``tests/test_remat_plan.py`` holds it by
  count): the expert route's argsort and bincount are traced in the forward
  and under no ``rematted_computation/.../de_moe_sort``."""
  name, stacks = lm_stacks
  assert remat.MOE_ROUTE in remat.KEPT
  sorts = [s for s in stacks if scopes.MOE_SORT in _components(s)
           and re.search(r"jit\((argsort|bincount)\)", s)]
  assert bool(sorts) == (name != "olmo_hybrid")
  assert {_pass_of(s) for s in sorts} <= {"forward"}, sorts
  # the rest of the part is rebuilt: the keys, the sums, `tok`, `p_sorted`
  if sorts:
    assert any(_pass_of(s) == "rebuilt" for s in stacks
               if scopes.MOE_SORT in _components(s))


SPLASH_TOYS = {
    # the published head: 192 + 64 for the scores, 256 for the values
    "glm_moe_lite": dict(qk_nope_head_dim=192, qk_rope_head_dim=64,
                         v_head_dim=256, seq_len=128),
    "sdar_moe": dict(head_dim=128, seq_len=64),
    "olmo_hybrid": dict(head_dim=128, seq_len=128, chunk=64),
    # one multi-query call a key-value head: 2 query heads on each of 2
    "solar_open2": dict(head_dim=128, seq_len=128, chunk=64),
    "laguna": dict(head_dim=128, seq_len=128),
    # the published head: half a lane tile
    "lfm2_moe": dict(head_dim=64, seq_len=128)}


@pytest.mark.parametrize("name", sorted(SPLASH_TOYS))
def test_no_splash_forward_kernel_is_called_in_a_rebuilt_core(
    name, monkeypatch):
  """The toy at a head the kernel takes, ``attention="splash"``, lowered for
  the TPU with no chip: the forward kernel's program is called once an
  attention layer, under ``de_attn_core`` in the forward pass; the rebuilt
  forward (``rematted_computation/.../de_attn_core``) calls none, because
  its output and log-sum-exp are kept (``remat.SPLASH_RESIDUALS``)."""
  model_cls, _, cfg = LM_TOYS[name]
  cfg = dataclasses.replace(cfg, attention="splash", **SPLASH_TOYS[name])
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  model = model_cls(cfg)
  rows = jnp.zeros((1, cfg.seq_len, cfg.hidden_size))
  numerical = jnp.full((1, getattr(cfg, "n_numerical", cfg.seq_len)), 0.5,
                       jnp.float32)
  params = jax.eval_shape(lambda: model.init(
      jax.random.PRNGKey(0), numerical, None, emb_acts=[rows]))["params"]
  grad = jax.value_and_grad(lambda p, r: _logit_sum(model.apply(
      {"params": p}, numerical, None, emb_acts=[r])))
  text = jax.jit(grad).trace(params, rows).lower(
      lowering_platforms=("tpu",)).as_text(debug_info=True)
  locs = dict(_LOC.findall(text))
  # the private functions that hold a forward kernel, and who calls them
  forward = set()
  for body in text.split("func.func ")[1:]:
    kernels = [locs.get(ref, "") for ref in re.findall(
        r"tpu_custom_call.*loc\((#loc\d+)\)", body)]
    if any("fwd" in k for k in kernels):
      forward.add(re.match(r"(?:private |public )?@([\w.]+)", body).group(1))
  assert forward
  sites = [locs[ref] for callee, ref in re.findall(
      r"call @([\w.]+)\(.*loc\((#loc\d+)\)", text) if callee in forward]
  if name == "glm_moe_lite":      # the trunk's layers and the module's
    attention_layers = len(cfg.layers_here) + 1
  elif name == "lfm2_moe":
    attention_layers = sum(mixer == lfm2_moe.FULL for mixer, _ in cfg.kinds)
  elif name == "solar_open2":
    attention_layers = sum(kind == solar_open2.GQA for kind in cfg.kinds)
  else:
    layers = len(getattr(cfg, "layer_types", ())) or cfg.num_hidden_layers
    attention_layers = layers if name != "olmo_hybrid" else sum(
        kind == olmo_hybrid.FULL for kind in cfg.layer_types)
  assert len(sites) == attention_layers
  for stack in sites:
    assert scopes.ATTN_CORE in _components(stack), stack
    assert _pass_of(stack) == "forward", stack
  # the rebuilt core is there all the same (the layout's way in), without it
  assert any(_pass_of(s) == "rebuilt" and scopes.ATTN_CORE in _components(s)
             for s in locs.values())


@pytest.mark.parametrize("name", sorted(LM_TOYS))
def test_the_parts_add_no_equation(name, monkeypatch):
  """A part is a name: the jaxpr of the toy's value-and-gradient is, equation
  for equation, the one traced with the parts' scopes left out."""
  model_cls, _, cfg = LM_TOYS[name]
  model = model_cls(cfg)
  rows = jnp.zeros((2, cfg.seq_len, cfg.hidden_size))
  numerical = jnp.full((2, getattr(cfg, "n_numerical", cfg.seq_len)), 0.5,
                       jnp.float32)
  params = jax.eval_shape(lambda: model.init(
      jax.random.PRNGKey(0), numerical, None, emb_acts=[rows]))["params"]

  def jaxpr():
    # a function object each: `make_jaxpr` remembers what it traced
    grad = jax.value_and_grad(lambda p, r: _logit_sum(model.apply(
        {"params": p}, numerical, None, emb_acts=[r])))
    return re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(grad)(
        params, rows)))

  with_parts = jaxpr()
  named_scope = jax.named_scope
  monkeypatch.setattr(jax, "named_scope", lambda n: (
      contextlib.nullcontext() if n in scopes.PARTS else named_scope(n)))
  assert jaxpr() == with_parts


def test_main_py_profile_dir_traces_five_annotated_steps(tmp_path):
  """``examples/dlrm/main.py --profile_dir``: the operator's use of the
  scopes (docs/ARCHITECTURE.md, "Reading a profile")."""
  import glob
  import os
  import subprocess
  import sys
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  done = subprocess.run(
      [sys.executable, os.path.join(root, "examples", "dlrm", "main.py"),
       "--dataset", "dummy", "--sparse", "--batch_size", "256",
       "--vocab_scale", "0.0001", "--world_size", "1", "--steps", "9",
       "--profile_dir", str(tmp_path)],
      env=env, capture_output=True, text=True, timeout=600)
  assert done.returncode == 0, done.stderr[-2000:]
  assert "profile of steps 4..8" in done.stdout
  files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
  assert len(files) == 1
  data = jax.profiler.ProfileData.from_file(files[0])
  steps = [e.name for plane in data.planes for line in plane.lines
           for e in line.events if e.name == "train"]
  assert len(steps) == 5
