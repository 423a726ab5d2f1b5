"""Small tables travel to the samples: across chips a dense-kind (MXU
one-hot) class whose block is fewer bytes than the rows it would ship is
all-gathered and looked up on each chip's own samples
(``wire.dense_class_side``, ``DistributedLookup.tables_travel``).

Held here: the forward equals the row exchange's bit for bit (every output
row is one table row, or the same ordered sum of them), the dense tables'
gradients and the state after one ``make_sparse_train_step`` equal it within
float32 summation order (four partial sums of a table's gradient are added
in another order), both equal a world-1 run of the same batch, the traced
step holds one ``all_gather`` and one ``reduce_scatter`` a class and no
``all_to_all`` for it, and the choice is a pure function of static counts.

The row exchange is reached through the choice's own inputs: an engine with
``dp_input=False`` (which never gathers), a batch smaller than the tables,
one rank."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from distributed_embeddings_tpu.analysis.jaxpr_audit import summarize
from distributed_embeddings_tpu.compat import shard_map
from distributed_embeddings_tpu.layers.dist_model_parallel import (
    get_weights,
    set_weights,
)
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models.dlrm import dlrm_embedding_plan
from distributed_embeddings_tpu.ops.packed_table import sgd_rule
from distributed_embeddings_tpu.parallel import (
    DistributedLookup,
    class_param_name,
    create_mesh,
    wire,
)
from distributed_embeddings_tpu.parallel.lookup_engine import (
    class_buckets,
    dense_class_traffic,
    padded_rows,
)
from distributed_embeddings_tpu.training import (
    init_sparse_state,
    make_sparse_train_step,
    shard_batch,
    shard_params,
    unpack_sparse_state,
)

W, B = 8, 128            # width; GLOBAL batch
SMALL = (5, 12, 40, 100, 30, 7, 64)   # dense-kind under the threshold
BIG = (500, 900)         # sparse-kind: the row exchange stays for these
THRESHOLD = 128
LR = 0.5
# (combiner, hotness); hotness > 1 with a combiner carries PAD ids
KINDS = {"hot1": ("sum", 1), "sum": ("sum", 3), "mean": ("mean", 3),
         "sequence": (None, 4)}


def _plan(kind, world, rows=SMALL + BIG, batch=B):
  combiner, h = KINDS[kind]
  return DistEmbeddingStrategy(
      [TableConfig(r, W, combiner=combiner) for r in rows], world,
      "memory_balanced", dense_row_threshold=THRESHOLD,
      input_hotness=[h] * len(rows), batch_hint=batch)


def _inputs(kind, rows=SMALL + BIG, batch=B, seed=0):
  combiner, h = KINDS[kind]
  rng = np.random.default_rng(seed)
  cats = []
  for r in rows:
    ids = rng.integers(0, r, (batch, h)).astype(np.int32)
    if h > 1 and combiner is not None:
      pad = rng.random((batch, h)) < 0.25
      pad[:, 0] = False
      ids[pad] = -1
    cats.append(ids[:, 0] if h == 1 else ids)
  return cats


def _weights(rows=SMALL + BIG, seed=1):
  rng = np.random.default_rng(seed)
  return [rng.standard_normal((r, W)).astype(np.float32) for r in rows]


def _hotness_of(kind):
  return lambda i: KINDS[kind][1]


def _forward(plan, class_params, cats, dp_input=True, cots=None):
  """The engine's simple forward under ``shard_map`` (plain at world 1):
  per input ``[B, ...]`` outputs; with ``cots`` (one cotangent an output)
  instead the class params' gradients of ``sum(out * cot)``, each rank's
  own samples only, as a step's local loss is."""
  engine = DistributedLookup(plan, dp_input=dp_input)

  def fwd(params, *xs):
    return tuple(engine.forward(params, list(xs)))

  def grads(params, xs, cs):
    def local_loss(p):
      return sum(jnp.sum(o * c) for o, c in zip(fwd(p, *xs), cs))
    return jax.grad(local_loss)(params)

  if plan.world_size == 1:
    if cots is None:
      return [np.asarray(o) for o in jax.jit(fwd)(class_params, *cats)]
    return jax.jit(grads)(class_params, tuple(cats), tuple(cots))
  mesh = create_mesh(plan.world_size)
  pspec = {n: P("mp", None) for n in class_params}
  bspec = tuple(P("mp") for _ in cats)
  if cots is None:
    f = shard_map(fwd, mesh=mesh, in_specs=(pspec,) + bspec, out_specs=bspec)
    return [np.asarray(o) for o in jax.jit(f)(class_params, *cats)]
  f = shard_map(grads, mesh=mesh, in_specs=(pspec, bspec, bspec),
                out_specs=pspec)
  return jax.jit(f)(class_params, tuple(cats), tuple(cots))


def _class_params(plan, weights):
  return {n: jnp.asarray(v) for n, v in set_weights(plan, weights).items()}


def _dense_name(plan):
  (key,) = [k for k in plan.class_keys if plan.classes[k].kind == "dense"]
  return key, class_param_name(*key)


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("kind", list(KINDS))
def test_forward_equals_the_row_exchange_bit_for_bit(kind, world):
  plan, cats, weights = _plan(kind, world), _inputs(kind), _weights()
  key, _ = _dense_name(plan)
  hot = _hotness_of(kind)
  assert DistributedLookup(plan).tables_travel(key, hot, B // world)
  assert not DistributedLookup(plan, dp_input=False).tables_travel(
      key, hot, B // world)
  params = _class_params(plan, weights)
  tables = _forward(plan, params, cats)
  rows = _forward(plan, params, cats, dp_input=False)
  plan1 = _plan(kind, 1)
  one = _forward(plan1, _class_params(plan1, weights), cats)
  for i, (t, r, o) in enumerate(zip(tables, rows, one)):
    assert t.shape == r.shape == o.shape
    assert np.array_equal(t, r), f"input {i}: tables side != rows side"
    assert np.array_equal(t, o), f"input {i}: world {world} != world 1"
  # and they are the rows: input 3 reads table 3 (100 rows)
  ids = cats[3] if cats[3].ndim == 2 else cats[3][:, None]
  got = weights[3][np.clip(ids, 0, None)] * (ids >= 0)[..., None]
  combiner = KINDS[kind][0]
  if combiner is None:
    want = got if ids.shape[1] > 1 else got[:, 0]
  else:
    want = got.sum(1)
    if combiner == "mean":
      want = want / np.maximum((ids >= 0).sum(1), 1)[:, None]
  np.testing.assert_allclose(tables[3], want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("kind", list(KINDS))
def test_gradients_equal_the_row_exchange(kind, world):
  """The reduce-scatter that autodiff transposes the all-gather into lands
  on each owner the sum the reverse row exchange delivered."""
  plan, cats, weights = _plan(kind, world), _inputs(kind), _weights()
  params = _class_params(plan, weights)
  outs = _forward(plan, params, cats)
  rng = np.random.default_rng(5)
  cots = [jnp.asarray(rng.standard_normal(o.shape), jnp.float32)
          for o in outs]
  g_tables = _forward(plan, params, cats, cots=cots)
  g_rows = _forward(plan, params, cats, dp_input=False, cots=cots)
  plan1 = _plan(kind, 1)
  g_one = _forward(plan1, _class_params(plan1, weights), cats, cots=cots)
  for name in params:
    np.testing.assert_allclose(np.asarray(g_tables[name]),
                               np.asarray(g_rows[name]), rtol=0, atol=2e-5,
                               err_msg=name)
  by_table = get_weights(plan, {n: np.asarray(g) for n, g in g_tables.items()})
  by_table_1 = get_weights(plan1, {n: np.asarray(g) for n, g in g_one.items()})
  for t, (a, b) in enumerate(zip(by_table, by_table_1)):
    np.testing.assert_allclose(a, b, rtol=0, atol=2e-5, err_msg=f"table {t}")
  _, dense = _dense_name(plan)
  assert np.abs(np.asarray(g_tables[dense])).max() > 0.1


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


def _one_step(kind, world, cats, weights, y):
  plan = _plan(kind, world)
  mesh = create_mesh(world) if world > 1 else None
  combiner, h = KINDS[kind]
  shape = (2, h, W) if combiner is None and h > 1 else (2, W)
  model = Tail()
  dense = model.init(jax.random.PRNGKey(2), jnp.zeros((2, 0)), None,
                     emb_acts=[jnp.zeros(shape)] * len(cats))["params"]
  rule, opt = sgd_rule(LR), optax.sgd(LR)
  state = shard_params(init_sparse_state(
      plan, {**dense, "embeddings": _class_params(plan, weights)}, rule,
      opt), mesh)
  num = jnp.zeros((B, 0))
  batch = (num, [jnp.asarray(c) for c in cats], y)
  step = make_sparse_train_step(model, plan, _loss, opt, rule, mesh, state,
                                batch, donate=False)
  sharded = shard_batch(batch, mesh)
  jaxpr = jax.make_jaxpr(step)(state, *sharded)
  new_state, loss = step(state, *sharded)
  params, _ = unpack_sparse_state(plan, rule, jax.device_get(new_state))
  return (plan, model, dense, float(loss),
          get_weights(plan, params["embeddings"]), params, jaxpr)


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("kind", list(KINDS))
def test_one_train_step_equals_the_row_exchange_and_world_1(kind, world):
  cats, weights = _inputs(kind), _weights()
  y = jnp.asarray(np.random.default_rng(3).normal(size=(B, 3)), jnp.float32)
  plan, model, dense, loss, tables, params, _ = _one_step(
      kind, world, cats, weights, y)
  *_, loss_1, tables_1, params_1, _ = _one_step(kind, 1, cats, weights, y)
  assert abs(loss - loss_1) <= 1e-6 * abs(loss_1)
  for t, (a, b) in enumerate(zip(tables, tables_1)):
    np.testing.assert_allclose(a, b, rtol=0, atol=2e-6, err_msg=f"table {t}")
    assert np.abs(a - weights[t]).max() > 1e-4, f"table {t} did not train"
  for name in dense:
    np.testing.assert_allclose(params[name], params_1[name], rtol=0,
                               atol=2e-6, err_msg=name)

  # the row exchange's step: the outputs are its outputs to the bit (above),
  # so the model hands both the same cotangents; pushed through the
  # row-exchange engine they give the gradient its reverse all_to_all
  # delivers, and plain SGD at 1 / world of it is the step's dense tables
  class_params = _class_params(plan, weights)
  acts = [jnp.asarray(o) for o in _forward(plan, class_params, cats)]
  b_local = B // world

  def local_loss(acts_r, y_r):
    return _loss(model.apply({"params": dense}, None, None, emb_acts=acts_r),
                 y_r)

  cots = [[] for _ in acts]
  for r in range(world):
    sl = slice(r * b_local, (r + 1) * b_local)
    g = jax.grad(local_loss)([a[sl] for a in acts], y[sl])
    for i, gi in enumerate(g):
      cots[i].append(gi)
  cots = [jnp.concatenate(c) for c in cots]
  g_rows = _forward(plan, class_params, cats, dp_input=False, cots=cots)
  _, name = _dense_name(plan)
  want = np.asarray(class_params[name]) - LR / world * np.asarray(g_rows[name])
  np.testing.assert_allclose(params["embeddings"][name], want, rtol=0,
                             atol=2e-6)


def _collectives(jaxpr):
  counts = summarize(jaxpr.jaxpr).counts
  return {k: counts.get(k, 0) for k in (
      "all_gather", "reduce_scatter", "all_to_all", "ppermute")}


@pytest.mark.parametrize("kind", list(KINDS))
def test_collectives_of_the_traced_step(kind):
  """World 4: one all_gather forward and one reduce_scatter backward for
  the dense-kind class, and all_to_alls only for the sparse-kind buckets
  (ids, rows, cotangents: three each). World 1: no collective at all."""
  cats, weights = _inputs(kind), _weights()
  y = jnp.zeros((B, 3), jnp.float32)
  plan, *_, jaxpr = _one_step(kind, 4, cats, weights, y)
  hot = _hotness_of(kind)
  engine = DistributedLookup(plan)
  sparse_buckets = sum(
      len(engine._buckets(k, hot)) for k in plan.class_keys
      if plan.classes[k].kind == "sparse")
  assert sparse_buckets >= 1
  assert _collectives(jaxpr) == {
      "all_gather": 1, "reduce_scatter": 1,
      "all_to_all": 3 * sparse_buckets, "ppermute": 0}
  # a model of small tables alone crosses the mesh with no all_to_all
  rows = SMALL
  small = _plan(kind, 4, rows)
  eng = DistributedLookup(small)
  params = _class_params(small, _weights(rows))
  xs = [jnp.asarray(c) for c in _inputs(kind, rows)]
  mesh = create_mesh(4)
  pspec = {n: P("mp", None) for n in params}
  bspec = tuple(P("mp") for _ in xs)

  def local_grads(p, *x):
    return jax.grad(lambda q: sum(
        jnp.sum(o) for o in eng.forward(q, list(x))))(p)

  traced = jax.make_jaxpr(shard_map(
      local_grads, mesh=mesh, in_specs=(pspec,) + bspec,
      out_specs=pspec))(params, *xs)
  assert _collectives(traced) == {
      "all_gather": 1, "reduce_scatter": 1, "all_to_all": 0, "ppermute": 0}
  # world 1: nothing to choose, nothing crosses
  plan1, *_, jaxpr1 = _one_step(kind, 1, cats, weights, y)
  key, name = _dense_name(plan1)
  assert not DistributedLookup(plan1).tables_travel(key, hot, B)
  assert plan1.exchange_report()["classes"][name]["moves"] == "rows"
  assert not summarize(jaxpr1.jaxpr).collective_axes
  assert sum(_collectives(jaxpr1).values()) == 0


@pytest.mark.parametrize("kind", ["hot1", "mean"])
def test_a_batch_smaller_than_the_tables_keeps_the_row_exchange(kind):
  """The same plan at a batch of 8: 3,000-row tables are more bytes than
  the rows they would ship, so ids and rows cross as before (one
  all_to_all each way a bucket), and the outputs are the same rows."""
  rows, batch, world = (3000, 3000, 3000), 8, 4
  combiner, h = KINDS[kind]
  plan = DistEmbeddingStrategy(
      [TableConfig(r, W, combiner=combiner) for r in rows], world,
      "memory_balanced", dense_row_threshold=4096,
      input_hotness=[h] * len(rows), batch_hint=batch)
  key, name = _dense_name(plan)
  engine = DistributedLookup(plan)
  hot = _hotness_of(kind)
  assert not engine.tables_travel(key, hot, batch // world)
  assert engine.tables_travel(key, hot, 4096)   # a training batch would
  rep = plan.exchange_report()["classes"][name]
  assert rep["moves"] == "rows" and rep["rows_bytes"] < rep["tables_bytes"]
  weights = _weights(rows)
  cats = _inputs(kind, rows, batch)
  params = _class_params(plan, weights)
  got = _forward(plan, params, cats)
  want = _forward(plan, params, cats, dp_input=False)
  for a, b in zip(got, want):
    assert np.array_equal(a, b)
  mesh = create_mesh(world)
  traced = jax.make_jaxpr(shard_map(
      lambda p, *x: tuple(engine.forward(p, list(x))), mesh=mesh,
      in_specs=({name: P("mp", None)},) + tuple(P("mp") for _ in cats),
      out_specs=tuple(P("mp") for _ in cats)))(params, *cats)
  n_buckets = len(engine._buckets(key, hot))
  assert _collectives(traced) == {
      "all_gather": 0, "reduce_scatter": 0, "all_to_all": 2 * n_buckets,
      "ppermute": 0}


# ---- the chip's compiler ----------------------------------------------------

def _cell_plan(world, batch_hint=65536):
  import json
  import os
  import warnings
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  with open(os.path.join(
      root, "benchmark", "configs", "dlrm-criteo1tb-4chip.json")) as f:
    cfg = json.load(f)
  rows = [max(int(cfg["min_rows"]), int(v * cfg["vocab_scale"]))
          for v in cfg["vocab_sizes"]]
  with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    return dlrm_embedding_plan(rows, 128, world, "memory_balanced",
                               batch_hint=batch_hint)


@pytest.fixture(scope="module")
def one_chip():
  """A described, not attached, v5e chip to compile for; the persistent
  compile cache is off meanwhile (an entry written for a described chip
  cannot be read back and warns)."""
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  from jax.sharding import SingleDeviceSharding
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no TPU compiler in this installation
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", was)
  compilation_cache.reset_cache()


def test_the_chips_compiler_cuts_every_window_where_it_belongs(one_chip):
  """Compiled for the TPU (nothing runs), every one-hot window of
  ``dlrm_train_4chip``'s gathered class is one slice of the flat
  ``[world * rows, 128]`` block at its owner's rows. It was not always so:
  cut from ``[world, rows, 128]``, two windows of neighbouring ranks came
  out of this installation's TPU compiler as ONE slice at the first one's
  offset, and the second table read another's rows on the chip while every
  CPU test passed (PERF.md, PR 30)."""
  import re
  plan = _cell_plan(4)
  key, _ = _dense_name(plan)
  engine = DistributedLookup(plan)
  rows = padded_rows(plan, key)
  slots = plan.classes[key].slots_per_rank
  table = jax.ShapeDtypeStruct((4 * rows, 128), jnp.float32,
                               sharding=one_chip)
  seen = 0
  for bucket in engine._buckets(key, lambda i: 1):
    real = engine._real_slots(bucket)
    ids = jax.ShapeDtypeStruct((len(real), 1024), jnp.int32,
                               sharding=one_chip)
    text = jax.jit(
        lambda t, i, bucket=bucket: engine._z_dense(key, bucket, t, i, True)
    ).lower(table, ids).compile().as_text()
    got = sorted({(int(a), int(b)) for a, b in re.findall(
        r"slice=\{\[(\d+):(\d+)\], \[0:128\]\}", text)
                  if int(b) - int(a) == bucket.vcap})
    want = sorted(
        (rank * rows + slots[rank][idx].row_offset,
         rank * rows + slots[rank][idx].row_offset + bucket.vcap)
        for rank, _, idx in real)
    assert got == want, (bucket.vcap, got, want)
    seen += len(want)
  assert seen == 15


# ---- the choice alone -------------------------------------------------------

def test_the_choice_is_bytes_against_bytes():
  side = wire.dense_class_side
  # the four-chip benchmark cell: 11 padded slots, batch 65,536, 7,366 rows
  assert side(4, True, 11, 65536, 7366, 128) == (
      "tables", 276_824_064, 11_314_176)
  # nothing to choose: one rank; ids that are not on the dp side
  assert side(1, True, 11, 65536, 7366, 128)[0] == "rows"
  assert side(4, False, 11, 65536, 7366, 128) == (
      "rows", 276_824_064, 11_314_176)
  # a batch of 8 over 3,000-row tables
  assert side(4, True, 1, 8, 3000, 8)[0] == "rows"
  # equal bytes stay with the rows; one more sample moves the tables
  assert side(2, True, 1, 200, 100, 16)[0] == "rows"
  assert side(2, True, 1, 202, 100, 16)[0] == "tables"
  # a narrower wire halves the rows' bytes, not the tables'
  assert side(2, True, 1, 300, 100, 16, 4)[0] == "tables"
  assert side(2, True, 1, 300, 100, 16, 2)[0] == "rows"


def test_the_benchmark_cells_own_plan_moves_its_tables():
  """``dlrm_train_4chip``: 15 tables of at most 4,096 rows, 11 padded slots
  of the 14 the exchange carried, 277 MB against 11 MB each way."""
  plan, plan1 = _cell_plan(4), _cell_plan(1)
  key, name = _dense_name(plan)
  assert sum(len(s) for s in plan.classes[key].slots_per_rank) == 15
  assert padded_rows(plan, key) == 7366
  hot = lambda i: 1  # noqa: E731
  assert dense_class_traffic(plan, key, class_buckets(plan, key, hot),
                             16384) == (
      "tables", 276_824_064, 11_314_176)
  rep = plan.exchange_report()
  assert rep["classes"][name]["moves"] == "tables"
  assert rep["classes"][name]["rows_bytes"] == 276_824_064
  assert rep["classes"][name]["tables_bytes"] == 11_314_176
  # the sparse-kind classes are counted too (tests/test_sparse_table_gather.py)
  assert all(c["moves"] in ("rows", "tables") for c in rep["classes"].values()
             if c["kind"] == "sparse")
  # the same plan asked about a batch of 8, or about mp-side inputs
  assert plan.exchange_report(global_batch=8)["classes"][name][
      "moves"] == "rows"
  assert plan.exchange_report(dp_input=False)["classes"][name][
      "moves"] == "rows"
  # one chip: rows, whatever the batch; no batch and four chips: not known
  _, name1 = _dense_name(plan1)
  assert plan1.exchange_report()["classes"][name1]["moves"] == "rows"
  blind = _cell_plan(4, batch_hint=None)
  assert blind.exchange_report()["classes"][name]["moves"] is None
  engine = DistributedLookup(plan)
  assert engine.tables_travel(key, hot, 16384)
  assert not DistributedLookup(plan1).tables_travel(key, hot, 65536)
  assert not any(engine.tables_travel(k, hot, 16384)
                 for k in plan.class_keys if k != key)
