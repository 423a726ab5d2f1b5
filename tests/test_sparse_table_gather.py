"""Small SPARSE tables travel too: across chips a sparse-kind class whose
packed block is fewer bytes than the rows it would ship is all-gathered,
optimizer lanes and all, and read by a row gather on each chip's own
samples; the per-occurrence deltas, scatter-added locally into zeros of
the gathered shape, are reduce-scattered home and added to the owner's
block (``DistributedLookup.tables_travel`` with the packed layouts,
``LocalIds``, ``wire.scatter_tables``). The planner gives such tables a
class of their own where that saves every rank a padded slot
(``DistEmbeddingStrategy._split_small_sparse_tables``).

Held here: the travelled step against the row exchange's step of the SAME
plan (the forward to the bit, the state within float32 summation order)
and against a world-1 run, under SGD and per-occurrence Adagrad with its
accumulators, at hotness 1, a summed and a mean multi-hot input with PAD
ids and a sequence input, with a rank that owns none of the class's tables
and ids drawn from so few rows that every chip holds duplicates of the
others'; the guarded and the micro-batched step; what keeps its rows
(``exact=True``, a summed rule, model-parallel inputs, the simple
forward); the collectives of the traced step; the planner's rule on the
benchmark cell's plan and where it must not engage; ``exchange_report()``
against a hand count; and, compiled for a described v5e at the cell's
real shapes, that no index into the gathered block is static."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_embeddings_tpu.analysis.jaxpr_audit import summarize
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    get_weights,
    set_weights,
)
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.ops.packed_table import (
    PackedLayout,
    adagrad_rule,
    adam_rule,
    scatter_add_fused,
    sgd_rule,
)
from distributed_embeddings_tpu.parallel import (
    DistributedLookup,
    class_param_name,
    create_mesh,
    wire,
)
from distributed_embeddings_tpu.parallel.lookup_engine import (
    LocalIds,
    class_buckets,
    padded_rows,
    sparse_class_traffic,
)
from distributed_embeddings_tpu.training import (
    init_sparse_state,
    make_sparse_train_step,
    shard_batch,
    shard_params,
    unpack_sparse_state,
)
from test_dense_table_gather import _cell_plan, one_chip  # noqa: F401

W, B = 8, 512            # width; GLOBAL batch
# table t goes to rank t % world ('basic'): at world 4 ranks 0-2 hold a
# small and a large table each and rank 3 one large table alone, the
# benchmark cell's shape; at world 2 each rank holds both kinds
SMALL = (24, 40, 48)     # 6 physical rows at most under Adagrad: 9 KB against 12
BIG = (3000, 5000, 4000, 6000)
ROWS = SMALL + BIG
LR = 0.5
KINDS = {"hot1": ("sum", 1), "sum": ("sum", 3), "mean": ("mean", 3),
         "sequence": (None, 4)}
RULES = {"sgd": lambda: sgd_rule(LR),
         "adagrad": lambda: adagrad_rule(LR, 0.1)}


def _plan(kind, world, **kw):
  combiner, h = KINDS[kind]
  return DistEmbeddingStrategy(
      [TableConfig(r, W, combiner=combiner) for r in ROWS], world, "basic",
      input_hotness=[h] * len(ROWS), batch_hint=B, **kw)


def _inputs(kind, seed=0):
  """Ids of the small tables from their first 16 rows only: 128 samples a
  chip over 16 rows, so every chip scatters into rows the others do."""
  combiner, h = KINDS[kind]
  rng = np.random.default_rng(seed)
  cats = []
  for r in ROWS:
    ids = rng.integers(0, min(r, 16) if r in SMALL else r,
                       (B, h)).astype(np.int32)
    if h > 1 and combiner is not None:
      pad = rng.random((B, h)) < 0.25
      pad[:, 0] = False
      ids[pad] = -1
    cats.append(ids[:, 0] if h == 1 else ids)
  return cats


def _weights(seed=1):
  rng = np.random.default_rng(seed)
  return [rng.standard_normal((r, W)).astype(np.float32) for r in ROWS]


def _travelling(plan, rule, kind, dp_input=True):
  """The sparse classes whose tables travel in the fused step."""
  engine = DistributedLookup(plan, dp_input=dp_input)
  hot = lambda i: KINDS[kind][1]  # noqa: E731
  layouts = engine.fused_layouts(rule)
  return [k for k in plan.class_keys if plan.classes[k].kind == "sparse"
          and engine.tables_travel(k, hot, B // plan.world_size, layouts)]


class Tail(nn.Module):
  """Every input's rows against a head of its own: ``[B, 3]``."""

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    out = 0.0
    for i, a in enumerate(emb_acts):
      a = a.reshape(a.shape[0], -1)
      head = self.param(f"head_{i}", nn.initializers.normal(0.3),
                        (a.shape[1], 3))
      out = out + jnp.tanh(a) @ head
    return out


def _loss(out, y):
  return jnp.mean((out - y) ** 2)


def _labels():
  return jnp.asarray(np.random.default_rng(3).normal(size=(B, 3)),
                     jnp.float32)


def _one_step(kind, world, rule, steps=1, cats=None, **step_kw):
  """``steps`` steps of ``make_sparse_train_step`` from the same weights
  on the same batch -> ``(plan, loss, tables, accumulators, dense,
  jaxpr)``; accumulators per table like the tables (empty for SGD)."""
  plan = _plan(kind, world)
  mesh = create_mesh(world) if world > 1 else None
  combiner, h = KINDS[kind]
  cats, weights, y = cats or _inputs(kind), _weights(), _labels()
  shape = (2, h, W) if combiner is None and h > 1 else (2, W)
  model = Tail()
  dense = model.init(jax.random.PRNGKey(2), jnp.zeros((2, 0)), None,
                     emb_acts=[jnp.zeros(shape)] * len(cats))["params"]
  opt = optax.sgd(LR)
  class_params = {n: jnp.asarray(v)
                  for n, v in set_weights(plan, weights).items()}
  state = shard_params(init_sparse_state(
      plan, {**dense, "embeddings": class_params}, rule, opt), mesh)
  batch = (jnp.zeros((B, 0)), [jnp.asarray(c) for c in cats], y)
  step = make_sparse_train_step(model, plan, _loss, opt, rule, mesh, state,
                                batch, donate=False, **step_kw)
  sharded = shard_batch(batch, mesh)
  jaxpr = jax.make_jaxpr(step)(state, *sharded)
  for _ in range(steps):
    state, loss = step(state, *sharded)[:2]
  params, aux = unpack_sparse_state(plan, rule, jax.device_get(state),
                                    include_aux=True)
  accs = (get_weights(plan, {n: np.asarray(a[0]) for n, a in aux.items()})
          if rule.n_aux else [])
  return (plan, float(loss), get_weights(plan, params["embeddings"]), accs,
          {n: np.asarray(params[n]) for n in dense}, jaxpr)


@pytest.fixture
def rows_stay(monkeypatch):
  """The row exchange's step of the same plan: the sparse-kind classes are
  never asked about with layouts."""
  real = DistributedLookup.tables_travel
  monkeypatch.setattr(
      DistributedLookup, "tables_travel",
      lambda self, key, hot, b, layouts=None: real(self, key, hot, b))


def _collectives(jaxpr):
  counts = summarize(jaxpr.jaxpr).counts
  return {k: counts.get(k, 0) for k in (
      "all_gather", "reduce_scatter", "all_to_all", "ppermute")}


# ---- the toy plan is the cell's shape ---------------------------------------

@pytest.mark.parametrize("world", [2, 4])
def test_the_toy_plan_has_a_travelling_class_and_a_rank_without_tables(world):
  plan = _plan("hot1", world)
  for rule in (sgd_rule(LR), adagrad_rule(LR, 0.1)):
    (key,) = _travelling(plan, rule, "hot1")
    cp = plan.classes[key]
    assert sorted(s.input_dim for sh in cp.shards_per_rank for s in sh) \
        == sorted(SMALL)
    assert not _travelling(plan, rule, "hot1", dp_input=False)
  if world == 4:
    assert cp.shards_per_rank[3] == [] and cp.rows_per_rank[3] == 0
  # every large table stayed where the planner had it, one slot a rank
  # fewer than with the small ones beside it
  stay = [k for k in plan.class_keys if k != key]
  assert sum(plan.classes[k].num_slots for k in stay) == (
      1 if world == 4 else 2)


# ---- the step, against the row exchange and against one chip ---------------

@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("rule_name", list(RULES))
@pytest.mark.parametrize("kind", list(KINDS))
def test_one_train_step_equals_the_row_exchange_and_world_1(
    kind, rule_name, world, request):
  rule = RULES[rule_name]()
  plan, loss, tables, accs, dense, jaxpr = _one_step(kind, world, rule)
  travelling = _travelling(plan, rule, kind)
  assert travelling
  *_, loss_1, tables_1, accs_1, dense_1, _ = _one_step(kind, 1, rule)
  request.getfixturevalue("rows_stay")
  _, loss_r, tables_r, accs_r, dense_r, jaxpr_r = _one_step(kind, world, rule)
  # the forward reads the same rows and sums them in the same order
  assert loss == loss_r
  assert abs(loss - loss_1) <= 1e-6 * abs(loss_1)
  weights = _weights()
  for t, (a, r, o) in enumerate(zip(tables, tables_r, tables_1)):
    np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5, err_msg=f"table {t}")
    np.testing.assert_allclose(a, o, rtol=1e-5, atol=1e-5, err_msg=f"table {t}")
    assert np.abs(a - weights[t]).max() > 1e-4, f"table {t} did not train"
  for t, (a, r, o) in enumerate(zip(accs, accs_r, accs_1)):
    np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5, err_msg=f"acc {t}")
    np.testing.assert_allclose(a, o, rtol=1e-5, atol=1e-5, err_msg=f"acc {t}")
    assert a.max() > 0.1 + 1e-6, f"accumulator {t} did not move"
  for name in dense:
    np.testing.assert_allclose(dense[name], dense_r[name], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dense[name], dense_1[name], rtol=1e-5, atol=1e-5)
  # one all_gather and one reduce_scatter for the class, and the
  # all_to_alls (ids, rows, cotangents) of the buckets that stay alone
  engine = DistributedLookup(plan)
  hot = lambda i: KINDS[kind][1]  # noqa: E731
  n_all = sum(len(engine._buckets(k, hot)) for k in plan.class_keys)
  n_stay = sum(len(engine._buckets(k, hot)) for k in plan.class_keys
               if k not in travelling)
  assert 0 < n_stay < n_all
  assert _collectives(jaxpr) == {
      "all_gather": 1, "reduce_scatter": 1, "all_to_all": 3 * n_stay,
      "ppermute": 0}
  assert _collectives(jaxpr_r) == {
      "all_gather": 0, "reduce_scatter": 0, "all_to_all": 3 * n_all,
      "ppermute": 0}


def test_duplicates_of_one_row_on_every_chip_add_up():
  """Every sample of every chip reads row 5 of table 0 (and its own rows of
  the others): under SGD the row moves by the sum of 512 cotangents, from
  four chips' scatters, as it does on one chip."""
  rule = sgd_rule(LR)
  cats = _inputs("hot1")
  cats[0] = np.full((B,), 5, np.int32)
  _, _, tables, *_ = _one_step("hot1", 4, rule, cats=cats)
  _, _, tables_1, *_ = _one_step("hot1", 1, rule, cats=cats)
  moved = np.abs(tables[0] - _weights()[0]).max(axis=1)
  assert moved[5] > 1e-3 and np.all(np.delete(moved, 5) == 0)
  np.testing.assert_allclose(tables[0], tables_1[0], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rule_name", list(RULES))
def test_three_steps_stay_with_one_chip(rule_name):
  """The second step reads what the first one's reduce-scatter wrote, rows
  and accumulators."""
  rule = RULES[rule_name]()
  _, loss, tables, accs, _, _ = _one_step("sum", 4, rule, steps=3)
  _, loss_1, tables_1, accs_1, _, _ = _one_step("sum", 1, rule, steps=3)
  assert abs(loss - loss_1) <= 1e-5 * abs(loss_1)
  for a, o in zip(tables + accs, tables_1 + accs_1):
    np.testing.assert_allclose(a, o, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("how", ["guard", "micro_batches"])
def test_the_guarded_and_the_micro_batched_step_travel_too(how):
  """Both apply prebuilt delta streams: the travelled class's is its
  owner's summed block delta, one entry a physical row."""
  # a micro-batch of 64 samples a chip ships 6 KB a slot: SGD's block (4.6
  # KB) travels, Adagrad's (9.2 KB) would not
  rule = adagrad_rule(LR, 0.1) if how == "guard" else sgd_rule(LR)
  kw = {"guard": True} if how == "guard" else {"micro_batches": 2}
  plan, loss, tables, accs, _, jaxpr = _one_step("sum", 4, rule, **kw)
  _, loss_1, tables_1, accs_1, _, _ = _one_step("sum", 1, rule, **kw)
  assert abs(loss - loss_1) <= 1e-6 * abs(loss_1)
  for a, o in zip(tables + accs, tables_1 + accs_1):
    np.testing.assert_allclose(a, o, rtol=1e-5, atol=1e-5)
  got = _collectives(jaxpr)
  assert got["all_gather"] == 1 and got["reduce_scatter"] == 1


@pytest.mark.parametrize("how", ["exact", "summed"])
def test_exact_and_summed_updates_keep_their_rows(how):
  """Applied once a distinct row of the GLOBAL batch: not a sum of ranks'
  parts. The plan is the same, nothing gathers, the result is one chip's."""
  rule = adam_rule(LR, summed=True) if how == "summed" else sgd_rule(LR)
  kw = {"exact": True} if how == "exact" else {}
  plan, loss, tables, _, _, jaxpr = _one_step("sum", 4, rule, **kw)
  if how == "exact":
    assert _travelling(plan, rule, "sum")   # by bytes alone it would
  assert _collectives(jaxpr)["all_gather"] == 0
  assert _collectives(jaxpr)["reduce_scatter"] == 0
  _, loss_1, tables_1, *_ = _one_step("sum", 1, rule, **kw)
  assert abs(loss - loss_1) <= 1e-6 * abs(loss_1)
  for a, o in zip(tables, tables_1):
    if how == "exact":
      np.testing.assert_allclose(a, o, rtol=1e-5, atol=1e-5)
    else:
      # Adam's first step is the rate times g / (|g| + eps): where a summed
      # gradient is near zero, four chips' order of addition decides it
      assert np.mean(np.abs(a - o) > 1e-4) < 1e-3


def test_the_apply_refuses_an_exact_update_of_travelled_rows():
  plan = _plan("hot1", 4)
  rule = sgd_rule(LR)
  engine = DistributedLookup(plan)
  layouts = engine.fused_layouts(rule)
  (key,) = _travelling(plan, rule, "hot1")
  name = class_param_name(*key)
  mesh = create_mesh(4)
  cats = [jnp.asarray(c) for c in _inputs("hot1")]
  from jax.sharding import PartitionSpec as P
  from distributed_embeddings_tpu.compat import shard_map

  def local(buf, *xs):
    ids_all = engine.route_ids(list(xs), None, layouts)
    ids_all = {bk: v for bk, v in ids_all.items() if bk.class_key == key}
    assert all(isinstance(v, LocalIds) for v in ids_all.values())
    z, res = engine.lookup_sparse_fused({name: buf}, layouts, ids_all)
    return engine.apply_sparse({name: buf}, layouts, z, res, rule,
                               jnp.zeros((), jnp.int32), exact=True)[name]

  buf = jnp.zeros((4 * layouts[name].phys_rows, layouts[name].phys_width))
  with pytest.raises(ValueError, match="travelled tables"):
    jax.make_jaxpr(shard_map(
        local, mesh=mesh, in_specs=(P("mp"),) + tuple(P("mp") for _ in cats),
        out_specs=P("mp")))(buf, *cats)


def test_the_simple_forward_keeps_a_sparse_class_rows():
  """``engine.forward`` (the flax module's, a server's) has no packed
  layouts to hand over: ids and rows cross as before."""
  plan = _plan("hot1", 4)
  engine = DistributedLookup(plan)
  ids_all = None

  def route(*xs):
    nonlocal ids_all
    ids_all = engine.route_ids(list(xs))
    return 0

  from jax.sharding import PartitionSpec as P
  from distributed_embeddings_tpu.compat import shard_map
  cats = [jnp.asarray(c) for c in _inputs("hot1")]
  jax.make_jaxpr(shard_map(route, mesh=create_mesh(4),
                           in_specs=tuple(P("mp") for _ in cats),
                           out_specs=P()))(*cats)
  assert ids_all and not any(isinstance(v, LocalIds)
                             for v in ids_all.values())


# ---- the planner's rule ------------------------------------------------------

def _gens(plan):
  return {k[3]: [[s.input_dim for s in sh]
                 for sh in plan.classes[k].shards_per_rank]
          for k in plan.class_keys if plan.classes[k].kind == "sparse"}


def test_the_rule_engages_on_the_benchmark_cells_plan():
  """``dlrm_train_4chip``: generation 1 held a table of under 10,000 rows
  beside a large one on three ranks and padded to two slots a rank; the
  three small tables get generation 2, and every rank runs one slot of
  generation 1."""
  plan = _cell_plan(4)
  gens = _gens(plan)
  assert gens[2] == [[9760], [5065], [4322], []]
  assert gens[1] == [[100836], [146483], [738386], [6410323]]
  assert [len(r) for r in gens[0]] == [1, 1, 1, 1]
  assert [plan.classes[k].num_slots for k in plan.class_keys
          if plan.classes[k].kind == "sparse"] == [1, 1, 1]
  key = (128, None, "sparse", 2)
  engine = DistributedLookup(plan)
  hot = lambda i: 1  # noqa: E731
  layouts = engine.fused_layouts(sgd_rule(0.1))
  assert [k for k in plan.class_keys
          if engine.tables_travel(k, hot, 16384, layouts)] == [
              (128, None, "dense", 0), key]
  # model-parallel inputs keep the rows; so does a rule whose lanes double
  # the block past the rows' bytes (Adagrad at width 128: 30 MB against 25)
  assert not DistributedLookup(plan, dp_input=False).tables_travel(
      key, hot, 16384, layouts)
  assert not engine.tables_travel(key, hot, 16384, {
      class_param_name(*key): PackedLayout(rows=9760, width=128, n_aux=1)})


def test_the_rule_leaves_every_other_plan_as_it_was(monkeypatch):
  def plan_of(rows, world=4, hot=None, **kw):
    return DistEmbeddingStrategy(
        [TableConfig(r, W, combiner="sum") for r in rows], world, "basic",
        input_hotness=hot, batch_hint=B, **kw)

  def moved(make):
    """The sparse generations with the rule, and whether they are what
    the planner assigns without it."""
    with_rule = _gens(make())
    with monkeypatch.context() as m:
      m.setattr(DistEmbeddingStrategy, "_split_small_sparse_tables",
                lambda self: None)
      return with_rule, with_rule != _gens(make())

  # where it engages: the toy plan, the cell's
  assert moved(lambda: _plan("hot1", 4))[1]
  assert moved(lambda: _cell_plan(4))[1]
  # one rank; no batch_hint; the deduplicated exchange
  assert not moved(lambda: _cell_plan(1))[1]
  assert not moved(lambda: _cell_plan(4, batch_hint=None))[1]
  assert not moved(lambda: _plan("hot1", 4, dedup_exchange=True))[1]
  # no slot would fall: ranks 0-2 hold a small and a large table and rank
  # 3 two large ones, so what stays still pads to two slots a rank
  assert not moved(lambda: plan_of(SMALL + BIG + (7000,)))[1]
  # tables over the byte rule at this batch (200 rows are 13 physical rows:
  # 4 x 13 x 128 values against 8 x 512 a slot)
  assert not moved(lambda: plan_of((200, 210, 220) + BIG))[1]
  # a ragged-fed table stays where it was (a value stream has no travelled
  # form) while the other two go; all three ragged, nothing is left to move
  gens, did = moved(lambda: plan_of(ROWS, hot=[-1, 1, 1, 1, 1, 1, 1]))
  assert did and gens[max(gens)] == [[], [SMALL[1]], [SMALL[2]], []]
  assert not moved(lambda: plan_of(ROWS, hot=[-1, -1, -1, 1, 1, 1, 1]))[1]
  # row-sliced shards stay: their slots are partial sums of a window
  def sliced():
    return plan_of((6000,) + SMALL, world=2, row_slice_threshold=8 * 2000)

  gens, did = moved(sliced)
  plan = sliced()
  rs = [s for sh in plan.rank_shards for s in sh if s.row_sliced]
  assert rs and (not did or all(s.gen < max(gens) for s in rs))


def test_exchange_report_counts_the_sparse_classes_by_hand():
  plan = _cell_plan(4)
  rep = plan.exchange_report()["classes"]
  # a slot ships 65,536 samples x 128 x 4 bytes, three quarters of them
  # off the chip; generation 2's block is 9,760 rows x 512 bytes, to three
  # other chips
  slot = 65536 * 128 * 4 * 3 // 4
  assert rep["mp_table_w128_cat_g2"] == {
      "kind": "sparse", "width": 128, "dedup": False, "padded_slots": 1,
      "moves": "tables", "rows_bytes": slot,
      "tables_bytes": 3 * 9760 * 128 * 4}
  assert rep["mp_table_w128_cat_g1"] == {
      "kind": "sparse", "width": 128, "dedup": False, "padded_slots": 1,
      "moves": "rows", "rows_bytes": slot,
      "tables_bytes": 3 * 6410323 * 128 * 4}
  assert rep["mp_table_w128_cat"]["moves"] == "rows"
  assert rep["mp_table_w128_cat_dense"]["padded_slots"] == 11
  # the slots a rank still runs as rows: three before this rule, two now
  assert sum(c["padded_slots"] for c in rep.values()
             if c["moves"] == "rows") == 2
  # under Adagrad's lanes the block is twice the bytes: it stays
  acc = plan.exchange_report(n_aux=1)["classes"]["mp_table_w128_cat_g2"]
  assert acc["tables_bytes"] == 3 * 9760 * 256 * 4 and acc["moves"] == "rows"
  # model-parallel inputs; a batch of 8; one chip; no batch at all
  g2 = lambda **kw: plan.exchange_report(**kw)["classes"][  # noqa: E731
      "mp_table_w128_cat_g2"]["moves"]
  assert g2(dp_input=False) == "rows" and g2(global_batch=8) == "rows"
  one = _cell_plan(1).exchange_report()["classes"]
  assert all(c["moves"] == "rows" for c in one.values())
  assert sum(c["padded_slots"] for c in one.values()
             if c["kind"] == "sparse") == 11   # one chip runs every table
  blind = _cell_plan(4, batch_hint=None).exchange_report()["classes"]
  assert all(c["moves"] is None for c in blind.values())
  # the engine's count is the report's
  key = (128, None, "sparse", 2)
  assert sparse_class_traffic(
      plan, key, class_buckets(plan, key, lambda i: 1), 16384, True,
      PackedLayout(rows=padded_rows(plan, key), width=128)) == (
          "tables", slot, 3 * 9760 * 128 * 4)


def test_the_choice_counts_the_packed_block():
  side = wire.dense_class_side
  # width 8 under Adagrad: 16 lanes a row, 8 rows a physical row of 128
  lay = PackedLayout(rows=100, width=8, n_aux=1)
  assert (lay.phys_rows, lay.phys_width) == (13, 128)
  assert side(4, True, 1, 512, 13, 8, table_width=128) == (
      "rows", 512 * 8 * 4 * 3 // 4, 3 * 13 * 128 * 4)
  assert side(4, True, 2, 512, 13, 8, table_width=128)[0] == "tables"
  assert side(1, True, 2, 512, 13, 8, table_width=128)[0] == "rows"
  assert side(4, False, 2, 512, 13, 8, table_width=128)[0] == "rows"


# ---- the chip's compiler -----------------------------------------------------

def test_no_index_into_the_gathered_block_is_static(one_chip):  # noqa: F811
  """Compiled for a v5e (nothing runs) at ``dlrm_train_4chip``'s shapes:
  the local samples' gather reads the whole ``[4 x 9,760, 128]`` block by
  computed indices, and the deltas' scatter writes the whole of it. PR 30's
  fault was a static slice of a gathered block that this installation's TPU
  compiler merged with its neighbour; here a slot's owner is an added
  constant, and the compiled program must not have turned it back into
  slices of the block."""
  plan = _cell_plan(4)
  key = (128, None, "sparse", 2)
  engine = DistributedLookup(plan)
  rule = sgd_rule(0.1)
  layout = engine.fused_layouts(rule)[class_param_name(*key)]
  glayout = engine._gathered_layout(layout)
  assert glayout.shape == (4 * 9760, 128)
  (bucket,) = engine._buckets(key, lambda i: 1)
  slots = tuple((r, p) for r, p, _ in engine._real_slots(bucket))
  assert [r for r, _ in slots] == [0, 1, 2]
  block = jax.ShapeDtypeStruct(glayout.shape, jnp.float32, sharding=one_chip)
  ids = jax.ShapeDtypeStruct((3, 16384), jnp.int32, sharding=one_chip)
  rows = jax.ShapeDtypeStruct((3, 16384, 128), jnp.float32,
                              sharding=one_chip)

  def read(buf, i):
    return engine._z_sparse_fused(
        key, glayout, buf, i,
        rows_at=engine._gathered_ids(i, slots, key, layout))[0]

  def write(i, d):
    at = engine._gathered_ids(i, slots, key, layout)
    return scatter_add_fused(glayout, jnp.zeros(glayout.shape, d.dtype),
                             at.reshape(-1), d.reshape(-1, 128))

  # ONE gather yields all three slots' rows, ONE scatter takes all three
  # slots' deltas into the whole block, and no window of float rows is cut
  # anywhere (the only slices are of index vectors)
  for fn, avals, op, result in (
      (read, (block, ids), "gather", r"f32\[3,16384,128\]"),
      (write, (ids, rows), "scatter", r"f32\[39040,128\]")):
    lines = jax.jit(fn).lower(*avals).compile().as_text().splitlines()
    ops = [ln for ln in lines if re.search(rf"= {result}\S* {op}\(", ln)]
    assert len(ops) == 1, ops
    cuts = [ln for ln in lines
            if re.search(r"= f32\[\S+ (dynamic-)?slice\(", ln)]
    assert not cuts, cuts
  # the owners' bases are the blocks' first rows
  got = np.asarray(engine._gathered_ids(
      jnp.asarray([[0, 9759, 9760], [1, 5064, 9760], [2, 4321, 9760]]),
      slots, key, layout))
  assert got.tolist() == [[0, 9759, 39040], [9761, 9760 + 5064, 39040],
                          [2 * 9760 + 2, 2 * 9760 + 4321, 39040]]
