"""The apply kernel's VMEM-resident heads, as far as a CPU can see them.

What the kernel does with a head is the simulator's business
(`tests/test_pallas_apply_sim.py`) and the chip's
(`tools/smoke_pallas_apply.py`). Here: where the engine puts the heads
(per rank, from the plan's row offsets), that `apply_head_share` counts what
a numpy count of the same seeded ids gives, on one chip's layout and on a
two-rank mesh, that the guarded step carries it, and that every caller
which does not know where its tables start hands the kernel what it
always did.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distributed_embeddings_tpu.ops.packed_table as packed_table
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models import DLRM, bce_loss
from distributed_embeddings_tpu.ops import pallas_apply
from distributed_embeddings_tpu.ops.packed_table import (
    scatter_add_fused,
    sgd_rule,
    sparse_rule,
)
from distributed_embeddings_tpu.ops.pallas_apply import HEAD_PAD, HEAD_ROWS
from distributed_embeddings_tpu.parallel import create_mesh
from distributed_embeddings_tpu.parallel.lookup_engine import DistributedLookup
from distributed_embeddings_tpu.training import (
    init_sparse_state,
    make_sparse_train_step,
    shard_batch,
    shard_params,
)

WIDTH = 128
# every table at least one head long and a multiple of 8 rows, so that the
# independent count below is simply "ids under HEAD_ROWS"; the 24-row table
# is dense-kind (one-hot path) and has no part in any of this
VOCAB = [3 * HEAD_ROWS, 2 * HEAD_ROWS + 64, HEAD_ROWS + 8, 2 * HEAD_ROWS, 24]
SPARSE = [v for v in VOCAB if v > 32]


def build(world, rule=None):
  model = DLRM(vocab_sizes=VOCAB, embedding_dim=WIDTH, bottom_mlp=(16, WIDTH),
               top_mlp=(16, 1), world_size=world, dense_row_threshold=32)
  plan = DistEmbeddingStrategy(
      [dict(input_dim=v, output_dim=WIDTH,
            initializer={"name": "uniform", "scale": 0.05}) for v in VOCAB],
      world, "basic", dense_row_threshold=32)
  return model, plan, rule or sgd_rule(0.05), optax.sgd(0.05)


def make_batch(world, seed):
  """Ids that a frequency-sorted vocabulary sends: most of them low."""
  rng = np.random.default_rng(seed)
  b = 16 * world
  numerical = rng.standard_normal((b, 13)).astype(np.float32)
  cats = []
  for v in VOCAB:
    ids = np.floor(rng.random(b) ** 3 * v)  # in [0, v): far under 2^31
    cats.append(ids.astype(np.int32))
  labels = rng.integers(0, 2, b).astype(np.float32)
  return numerical, cats, labels


def numpy_head_share(cats):
  ids = np.concatenate([c for c, v in zip(cats, VOCAB) if v in SPARSE])
  return float(np.mean(ids < HEAD_ROWS))


@pytest.mark.parametrize("world", [1, 2])
def test_head_starts_are_each_ranks_table_starts(world):
  _, plan, rule, _ = build(world)
  engine = DistributedLookup(plan)
  (name, layout), = engine.fused_layouts(rule).items()
  cp = plan.classes[engine._key_of_class[name]]
  for rank in range(world):
    engine._my_rank = lambda rank=rank: rank
    starts = np.asarray(engine._apply_head_starts(name, layout, 64))
    live = [int(s) for s in starts if s != HEAD_PAD]
    assert live == cp.row_offsets_per_rank[rank], (rank, starts)
  if world == 2:  # the ranks hold other tables, so the starts differ
    assert cp.row_offsets_per_rank[0] != cp.row_offsets_per_rank[1]


def test_no_heads_where_the_kernel_would_not_run_or_ids_are_not_the_plans():
  _, plan, rule, _ = build(1)
  engine = DistributedLookup(plan)
  (name, layout), = engine.fused_layouts(rule).items()
  assert engine._apply_head_starts(name, layout, 64) is not None
  # XLA's fast scatter regime (ids >= 0.15 x rows): the kernel is not taken
  assert engine._apply_head_starts(name, layout, layout.phys_rows) is None
  # a compact layout (a host-tiered class's cache + staging rows)
  compact = engine.fused_layouts(rule, rows_overrides={name: 4096})[name]
  assert engine._apply_head_starts(name, compact, 64) is None
  # interleaved optimizer state: 256 lanes, which Mosaic's row DMA refuses
  wide = engine.fused_layouts(sparse_rule("adagrad", 0.05))[name]
  assert engine._apply_head_starts(name, wide, 64) is None
  assert engine._apply_head_starts("no_such_class", layout, 64) is None


@pytest.mark.parametrize("world", [1, 2])
def test_apply_head_share_equals_a_numpy_count(world):
  """The guarded step's metrics carry `apply_head_share`, and it is the
  share of the batch's sparse ids that lie under HEAD_ROWS in their table:
  on one chip's layout, and summed over a two-rank mesh whose ranks hold
  different tables."""
  mesh = create_mesh(world) if world > 1 else None
  model, plan, rule, opt = build(world)
  batches = [make_batch(world, seed) for seed in (5, 6)]
  numerical, cats, _ = batches[0]
  params = model.init(jax.random.PRNGKey(0), jnp.asarray(numerical),
                      [jnp.asarray(c) for c in cats])["params"]
  state = init_sparse_state(plan, params, rule, opt)
  state = shard_params(state, mesh) if mesh is not None else state
  step = make_sparse_train_step(model, plan, bce_loss, opt, rule, mesh,
                                state, batches[0], donate=False, guard=True)
  name, = DistributedLookup(plan).fused_layouts(rule)
  for batch in batches:
    state, _, metrics = step(state, *shard_batch(batch, mesh))
    assert set(metrics) == {"bad_step", "oov", "apply_head_share"}
    assert set(metrics["apply_head_share"]) == {name}
    want = numpy_head_share(batch[1])
    assert 0.3 < want < 0.95  # the traffic reaches heads and tails
    assert float(metrics["apply_head_share"][name]) == pytest.approx(
        want, abs=1e-6)


def test_apply_head_counts_on_a_stream_with_sentinels():
  _, plan, rule, _ = build(1)
  engine = DistributedLookup(plan)
  (name, layout), = engine.fused_layouts(rule).items()
  offs = plan.classes[engine._key_of_class[name]].row_offsets_per_rank[0]
  ids = np.array([offs[0], offs[0] + HEAD_ROWS - 1, offs[0] + HEAD_ROWS,
                  offs[1] + 5, offs[1] - 1, layout.rows, -1, offs[2]])
  ids = ids.astype(np.int32)
  rows = jnp.zeros((len(ids), WIDTH), jnp.float32)
  got = engine.apply_head_counts({name: layout},
                                 {name: (jnp.asarray(ids), rows)})
  # in a head: offs[0], its last head row, offs[1] + 5, offs[2]; valid: all
  # but the sentinel (layout.rows) and the padding (-1)
  np.testing.assert_array_equal(np.asarray(got[name]), [4, 6])
  # a class the step has no stream for counts nothing
  got = engine.apply_head_counts({name: layout}, {})
  np.testing.assert_array_equal(np.asarray(got[name]), [0, 0])


# --- callers that know no table starts --------------------------------------


@pytest.fixture
def kernel_spy(monkeypatch):
  """Every call of the kernel's entry, with the gates answering as on the
  chip; the buffer comes back unchanged."""
  calls = []
  monkeypatch.setattr(packed_table, "_use_pallas_apply", lambda: True)
  monkeypatch.setattr(
      pallas_apply, "apply_rows_cached",
      lambda buf, ids, delta, **kw: calls.append(kw) or buf)
  return calls


def _apply_inputs(engine, plan, rule, seed=0):
  _, cats, _ = make_batch(1, seed)
  layouts = engine.fused_layouts(rule)
  fused = {n: jnp.zeros(lay.shape, jnp.float32)
           for n, lay in layouts.items()}
  ids_all = engine.route_ids([jnp.asarray(c) for c in cats])
  z, residuals = engine.lookup_sparse_fused(fused, layouts, ids_all)
  d_z = jax.tree_util.tree_map(jnp.ones_like, z)
  return fused, layouts, d_z, residuals


@pytest.mark.parametrize("path", ["streams", "exact", "chunked", "direct"])
def test_only_the_stream_path_hands_the_kernel_heads(kernel_spy, path):
  _, plan, rule, _ = build(1)
  engine = DistributedLookup(plan, apply_chunk=8 if path == "chunked"
                             else 1 << 22)
  fused, layouts, d_z, residuals = _apply_inputs(engine, plan, rule)
  step = jnp.zeros((), jnp.int32)
  if path == "direct":
    (name, layout), = layouts.items()
    scatter_add_fused(layout, fused[name], jnp.asarray([1, 2], jnp.int32),
                      jnp.ones((2, WIDTH), jnp.float32), prefer_pallas=True)
  else:
    engine.apply_sparse(fused, layouts, d_z, residuals, rule, step,
                        exact=path == "exact")
  assert kernel_spy, "the kernel's entry was not reached"
  for kw in kernel_spy:
    if path == "streams":
      assert kw["head_starts"].shape == (len(SPARSE),)
      assert kw["head_starts"].dtype == jnp.int32
    else:
      assert kw.get("head_starts") is None


@pytest.mark.parametrize("scaled", [False, True])
def test_without_heads_the_kernel_has_the_arguments_it_always_had(scaled):
  """Lowered for the TPU: the Mosaic call of a caller that names no heads
  takes the id stream as it came (no rewrite before it), the buffer, the
  deltas and the scale, and asks for no VMEM beyond the default; with heads
  the starts come last, after the operands the benchmark reads by
  position."""
  avals = (jax.ShapeDtypeStruct((2 * HEAD_ROWS, WIDTH), jnp.float32),
           jax.ShapeDtypeStruct((256,), jnp.int32),
           jax.ShapeDtypeStruct((256, WIDTH), jnp.float32))
  scale = jnp.float32(-0.5) if scaled else None

  def call_line(fn, *args):
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    line, = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert 'kernel_name = "de_apply_rows_cached"' in line
    return line

  plain = call_line(lambda b, i, d: pallas_apply.apply_rows_cached(
      b, i, d, scale=scale), *avals)
  operands = "(tensor<256xi32>, tensor<16384x128xf32>, tensor<256x128xf32>" \
      + (", tensor<1xf32>" if scaled else "")
  assert operands + ") ->" in plain
  assert "@tpu_custom_call(%arg1, %arg0, %arg2" in plain
  assert "scoped_memory_configs" not in plain

  heads = call_line(lambda b, i, d, h: pallas_apply.apply_rows_cached(
      b, i, d, scale=scale, head_starts=h),
                    *avals, jax.ShapeDtypeStruct((2,), jnp.int32))
  assert operands + ", tensor<2xi32>) ->" in heads
  assert "scoped_memory_configs" in heads
