"""Sequence inputs (a ``TableConfig(combiner=None)`` read at hotness ``L``
hands the model ``[B, L, width]``) and summed sparse rules
(``adam_rule(summed=True)``: one update per distinct row from the summed
gradient), on the sparse train step. What was there keeps its bits: the DLRM,
zoo and toy-sequence steps trace to the jaxpr recorded from the parent of
the PR that added both (`tests/jaxpr_baseline.py`)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import jaxpr_baseline
from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.ops.packed_table import adam_rule, sgd_rule
from distributed_embeddings_tpu.parallel import create_mesh
from distributed_embeddings_tpu.parallel.lookup_engine import (
    DistributedLookup,
)
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

V, W, L, B = 200, 16, 8, 8


class Tail(nn.Module):
  """``[B, L, W]`` rows -> ``[B, 5]``; the rows arrive as one sequence
  input or as L hotness-1 inputs that share the table (the toy family's
  way)."""
  sequence: bool

  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    x = emb_acts[0] if self.sequence else jnp.stack(list(emb_acts), axis=1)
    head = self.param("head", nn.initializers.normal(0.1), (L, W, 5))
    return jnp.einsum("blw,lwv->bv", jnp.tanh(x), head)


def _loss(out, y):
  return jnp.mean((out - y) ** 2)


def _run(sequence, world, rule, cats, steps=2, dense_row_threshold=0):
  mesh = create_mesh(world) if world > 1 else None
  if sequence:
    plan = DistEmbeddingStrategy(
        [TableConfig(V, W, combiner=None)], world, "memory_balanced",
        input_table_map=[0], dense_row_threshold=dense_row_threshold,
        input_hotness=[L], batch_hint=B)
    split = lambda m: [m]
    acts = [jnp.zeros((2, L, W))]
  else:
    plan = DistEmbeddingStrategy(
        [TableConfig(V, W, combiner="sum")], world, "memory_balanced",
        input_table_map=[0] * L, dense_row_threshold=dense_row_threshold,
        input_hotness=[1] * L, batch_hint=B)
    split = lambda m: [m[:, i] for i in range(L)]
    acts = [jnp.zeros((2, W))] * L
  model = Tail(sequence)
  dense = model.init(jax.random.PRNGKey(1), jnp.zeros((2, 0)), None,
                     emb_acts=acts)["params"]
  opt = optax.sgd(0.1)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(0), mesh=mesh)
  y = jnp.asarray(np.random.default_rng(3).normal(size=(B, 5)), jnp.float32)
  num = jnp.zeros((B, 0))
  step = make_sparse_train_step(model, plan, _loss, opt, rule, mesh, state,
                                (num, split(cats), y), donate=False)
  losses = []
  for _ in range(steps):
    state, loss = step(state, num, split(cats), y)
    losses.append(float(loss))
  tables = [np.asarray(v) for v in state["fused"].values()] \
      + [np.asarray(v) for v in state["emb_dense"].values()]
  return losses, tables, np.asarray(state["dense"]["head"])


def _ids(distinct):
  rng = np.random.default_rng(0)
  if distinct:  # no row read twice: the scatter's order cannot matter
    return jnp.asarray(rng.permutation(V)[:B * L].reshape(B, L), jnp.int32)
  return jnp.asarray(rng.integers(0, 20, (B, L)), jnp.int32)


@pytest.mark.parametrize("world", [1, 2])
@pytest.mark.parametrize("rule", ["sgd", "adam", "adam_summed"])
def test_a_sequence_input_is_its_hotness_1_inputs_bit_for_bit(world, rule):
  rule = {"sgd": sgd_rule(0.1), "adam": adam_rule(0.01),
          "adam_summed": adam_rule(0.01, summed=True)}[rule]
  seq = _run(True, world, rule, _ids(True))
  one = _run(False, world, rule, _ids(True))
  assert seq[0] == one[0]            # both steps' losses: the rows, forward
  assert np.array_equal(seq[2], one[2])
  assert len(seq[1]) == len(one[1]) == 1
  assert np.array_equal(seq[1][0], one[1][0])  # table, moments: backward
  # rows read many times: the same sums in another order
  seq, one = (_run(s, world, rule, _ids(False)) for s in (True, False))
  assert seq[0][0] == one[0][0]
  np.testing.assert_allclose(seq[1][0], one[1][0], rtol=0, atol=1e-6)
  assert not np.array_equal(seq[1][0], _run(True, world, rule, _ids(True),
                                            steps=0)[1][0])


def test_a_sequence_input_on_a_one_hot_class_table():
  """Under ``dense_row_threshold`` the table is a dense-class (MXU one-hot)
  table, updated by optax from its dense gradient: the same contract."""
  big = 10 ** 6
  seq = _run(True, 1, sgd_rule(0.1), _ids(False), dense_row_threshold=big)
  one = _run(False, 1, sgd_rule(0.1), _ids(False), dense_row_threshold=big)
  assert seq[0][0] == one[0][0]
  np.testing.assert_allclose(seq[0], one[0], rtol=1e-6)
  np.testing.assert_allclose(seq[1][0], one[1][0], rtol=0, atol=1e-6)
  np.testing.assert_allclose(seq[2], one[2], rtol=0, atol=1e-6)


@pytest.mark.parametrize("which", jaxpr_baseline.STEPS)
def test_the_old_steps_trace_to_the_parents_jaxpr(which, tmp_path):
  """A hotness-1 input, a summed input and ``adam_rule()`` without
  ``summed`` trace, equation for equation, to what the parent traced."""
  got = jaxpr_baseline.step_text(which,
                                 jaxpr_baseline._toy_root(str(tmp_path)))
  want = jaxpr_baseline.recorded(which)
  if got != want:
    a, b = got.splitlines(), want.splitlines()
    first = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                 min(len(a), len(b)))
    raise AssertionError(
        f"{which}: {len(a)} lines traced, {len(b)} recorded; first "
        f"difference at line {first}:\n  now:    {a[first:first + 1]}\n"
        f"  parent: {b[first:first + 1]}")


# ---- summed rules -----------------------------------------------------------
HOT = 75   # reads of row 7 in one batch


def _adam_case():
  rng = np.random.default_rng(5)
  cats = rng.integers(8, V, (B * 2, L))
  cats.reshape(-1)[rng.choice(cats.size, HOT, replace=False)] = 7
  assert int(np.sum(cats == 7)) == HOT
  target = rng.normal(size=(V, W)).astype(np.float32)
  return jnp.asarray(cats, jnp.int32), jnp.asarray(target)


class Rows(nn.Module):
  @nn.compact
  def __call__(self, numerical, cats, emb_acts=None):
    self.param("unused", nn.initializers.zeros, (1,))
    return emb_acts[0]


def _sparse_adam(rule, cats, target, steps):
  plan = DistEmbeddingStrategy(
      [TableConfig(V, W, combiner=None)], 1, "memory_balanced",
      input_table_map=[0], dense_row_threshold=0, input_hotness=[L],
      batch_hint=cats.shape[0])
  model = Rows()
  dense = model.init(jax.random.PRNGKey(0), None, None,
                     emb_acts=[jnp.zeros((2, L, W))])["params"]
  opt = optax.sgd(0.0)
  state = init_sparse_state_direct(plan, rule, dense, opt,
                                   jax.random.PRNGKey(2))
  loss = lambda rows, labels: jnp.sum((rows - labels) ** 2) / 8.0
  labels = jnp.take(target, cats, axis=0)
  num = jnp.zeros((cats.shape[0], 0))
  step = make_sparse_train_step(model, plan, loss, opt, rule, None, state,
                                (num, [cats], labels), donate=False)
  (name, buf), = state["fused"].items()
  layout = DistributedLookup(plan).fused_layouts(rule)[name]
  table0 = np.asarray(layout.unpack(buf)[0])[:V]
  tables = []
  for _ in range(steps):
    state, _ = step(state, num, [cats], labels)
    tables.append(np.asarray(layout.unpack(state["fused"][name])[0])[:V])
  return table0, tables


def test_summed_adam_is_optax_adam_on_the_dense_table():
  cats, target = _adam_case()
  lr = 0.05
  table0, summed = _sparse_adam(adam_rule(lr, summed=True), cats, target, 3)
  _, per_occ = _sparse_adam(adam_rule(lr), cats, target, 3)
  touched = np.unique(np.asarray(cats))
  # the dense equivalent: optax.adam on the whole table, the same loss
  tx = optax.adam(lr)
  table, opt_state = jnp.asarray(table0), tx.init(jnp.asarray(table0))
  loss = lambda t: jnp.sum((jnp.take(t, cats, axis=0)
                            - jnp.take(target, cats, axis=0)) ** 2) / 8.0
  for k in range(3):
    upd, opt_state = tx.update(jax.grad(loss)(table), opt_state, table)
    table = optax.apply_updates(table, upd)
    np.testing.assert_allclose(summed[k][touched], np.asarray(table)[touched],
                               rtol=0, atol=2e-6)
    # rows the batch never read: as they were (a dense Adam would keep
    # moving a row once its moments are non-zero; none is here)
    idle = np.setdiff1d(np.arange(V), touched)
    assert np.array_equal(summed[k][idle], table0[idle])
  # per occurrence, the hot row's first moment is counted 75 times over:
  # its first step is 75 times the rate, and it never comes back
  step_summed = np.abs(summed[0][7] - table0[7]).max()
  step_per_occ = np.abs(per_occ[0][7] - table0[7]).max()
  assert step_summed == pytest.approx(lr, rel=1e-3)
  assert step_per_occ > 0.9 * HOT * lr
  assert np.abs(per_occ[2][7] - np.asarray(table)[7]).max() > 10 * lr
  cold = touched[touched != 7]
  np.testing.assert_allclose(summed[0][cold], per_occ[0][cold], atol=1.5 * lr)


@pytest.mark.parametrize("kwargs,error,words", [
    (dict(guard=True), NotImplementedError, "guard=True with exact=True"),
    (dict(micro_batches=2), NotImplementedError,
     "micro_batches > 1 with exact=True"),
    (dict(wire_dtype="bf16"), ValueError,
     "exact=True requires wire_dtype='f32'"),
])
def test_a_summed_rule_is_refused_where_exact_is(kwargs, error, words):
  """The refusals of ``exact=True`` hold for a rule that says ``summed``,
  word for word, whatever ``exact=`` says."""
  wire = kwargs.pop("wire_dtype", "f32")
  plan = DistEmbeddingStrategy(
      [TableConfig(V, W, combiner=None)], 1, "memory_balanced",
      input_table_map=[0], dense_row_threshold=0, input_hotness=[L],
      batch_hint=B, wire_dtype=wire)
  for rule, exact in ((adam_rule(0.01, summed=True), False),
                      (adam_rule(0.01), True)):
    with pytest.raises(error, match=words):
      make_sparse_train_step(Rows(), plan, _loss, optax.sgd(0.1), rule, None,
                             {}, (), exact=exact, **kwargs)
  assert adam_rule(0.01).summed is False and sgd_rule(0.1).summed is False
