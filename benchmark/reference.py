"""The plain reference of one training step, shared by the families.

Float32 as the configurations state it: float32 parameters, activations,
gradients and updates, with matmuls at JAX's default precision for float32
on the device (on a TPU one bfloat16 pass on the MXU with float32
accumulation, which is also what the program multiplies with by design).
The gradients come from ``jax.grad`` of the model's plain equations on one
device; the optimizer's rule and the sums over a row's occurrences are numpy
in float64 on the host. No kernels, no packed layout, no sharding. It
imports nothing of the program and reads nothing the program made: the
weights come from :mod:`benchmark.weights` (a function of the seed),
evaluated only at the rows the batch touches. A family supplies its model's
equations (``reference_logits``: from the dense leaves, one activation per
input and the numerical features to the model's outputs) and the plain
description of its parameters, and may supply its loss (``ModelSpec.loss``,
of those outputs and the batch's labels; default: binary cross-entropy of
one logit a sample).

What it returns is the one-step change of every parameter the batch can
change, as a float32 state shows it (the float32 value after the step minus
the one before: a change far below the value's float32 step is mostly
rounding, in any float32 program, and PR 25's chip runs read that rounding
as the zoo's heavy-tailed gaps): per table the distinct touched rows with
their change (and the change of each optimizer accumulator that rides in the
row, where the rule keeps any), and the change of every dense leaf. The loss
is computed a second time, forward only, at ``highest`` matmul precision:
that is the loss the check compares.

With ``precision="bfloat16"`` the same equations are computed as the
lower-precision control, the step a later PR would be tempted by: weights
held in float32 and updated in float32, but the step's arithmetic in
bfloat16 (weights and embedding rows rounded to bfloat16 on the way in,
activations and gradients in bfloat16). What crosses the boundary is
rounded with ``lax.reduce_precision``: a plain ``astype`` round trip is
something XLA may skip ("excess precision"; PERF.md, PR 25).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import numpy as np

from benchmark import traffic, weights


@dataclasses.dataclass(frozen=True)
class TableSpec:
  rows: int
  width: int
  scale: float  # weights are uniform in (-scale, scale)


def bce_with_logits(jnp, logits, labels):
  return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                  + jnp.log1p(jnp.exp(-jnp.abs(logits))))


@dataclasses.dataclass(frozen=True)
class ModelSpec:
  """A model as its configuration file describes it, in plain terms."""
  tables: Tuple[TableSpec, ...]
  inputs: Tuple[traffic.CatInput, ...]
  n_numerical: int
  # name -> (shape, scale) or (shape, scale, offset): a leaf of any rank
  # that starts at offset + uniform(+-scale); a norm's gain is ((w,), 0, 1)
  dense_leaves: Dict[str, Tuple]
  optimizer: Dict[str, Any]  # name, learning_rate, and the rule's constants
  # tables whose update is computed once per distinct row from the summed
  # gradient (the configuration's small tables, updated densely); all other
  # tables are updated per occurrence
  summed_tables: frozenset = frozenset()
  # loss(jnp, outputs, labels) -> scalar: of what ``reference_logits``
  # returns (cast to float32) and of ``Batch.labels`` as the family drew them
  loss: Callable = bce_with_logits


@dataclasses.dataclass
class StepChange:
  loss: float
  table_rows: Dict[int, np.ndarray]      # table -> distinct touched ids
  table_delta: Dict[int, np.ndarray]     # table -> [n, width] change
  # table -> [n, groups * width] change of the rows' accumulators, the
  # rule's lane groups side by side (Adagrad: the sum of squares; Adam: the
  # first moment, then the second): per-occurrence tables under a rule that
  # keeps any (their accumulators ride in the row)
  acc_delta: Dict[int, np.ndarray]
  dense_delta: Dict[str, np.ndarray]
  dense_before: Dict[str, np.ndarray]


def table_name(t: int) -> str:
  return f"table_{t:03d}"


def dense_weights(spec: ModelSpec, seed: int) -> Dict[str, np.ndarray]:
  return {name: weights.dense_np(weights.leaf_key(seed, name), scale, shape,
                                 *offset)
          for name, (shape, scale, *offset) in spec.dense_leaves.items()}


def initial_accumulators(opt: Dict[str, Any]) -> Tuple[float, ...]:
  """The values the rule's accumulators start from, one per lane group the
  rule keeps beside a row; () where it keeps none."""
  if opt["name"] == "adagrad":
    return (float(opt["initial_accumulator_value"]),)
  if opt["name"] == "adam":
    return (0.0, 0.0)
  return ()


def update(opt: Dict[str, Any], g: np.ndarray):
  """One optimizer step from its initial state on float64 gradients:
  -> (the parameter's change, the change of each accumulator)."""
  lr = float(opt["learning_rate"])
  if opt["name"] == "sgd":
    return -lr * g, ()
  if opt["name"] == "adagrad":
    g2 = g * g
    acc_new = float(opt["initial_accumulator_value"]) + g2
    return -lr * g / np.sqrt(acc_new + float(opt["eps"])), (g2,)
  if opt["name"] == "adam":
    # the first step from zero moments, bias-corrected at t = 1
    b1, b2 = float(opt["b1"]), float(opt["b2"])
    m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
    m_hat, v_hat = m / (1.0 - b1), v / (1.0 - b2)
    return -lr * m_hat / (np.sqrt(v_hat) + float(opt["eps"])), (m, v)
  raise ValueError(f"no reference for optimizer {opt['name']!r}")


def stored_change(before: np.ndarray, change: np.ndarray) -> np.ndarray:
  """What a float32 state shows of ``change`` added to ``before``."""
  before = np.asarray(before, np.float32)
  return (before + change).astype(np.float32).astype(np.float64) - before


def _segment_sum(x: np.ndarray, seg: np.ndarray, n: int) -> np.ndarray:
  """Rows of ``x`` summed in float64 into ``n`` segments."""
  import scipy.sparse
  pick = scipy.sparse.csr_matrix(
      (np.ones(len(seg)), (seg, np.arange(len(seg)))), shape=(n, len(seg)))
  return pick @ np.asarray(x, np.float64)


def one_step(spec: ModelSpec, logits_fn: Callable, batch: traffic.Batch,
             seed: int, precision: str = "float32") -> StepChange:
  """The reference's change after one step on ``batch`` from the seed's
  weights. ``logits_fn(dense, embs, numerical)`` returns the model's outputs
  (an array or a tree of arrays) from ``embs``, one activation per input:
  ``[B, width]`` summed over its hotness, or ``[B, hotness, width]`` where
  the input is kept as a sequence. ``spec.loss`` makes the scalar of them."""
  import jax
  import jax.numpy as jnp

  if precision not in ("float32", "bfloat16"):
    raise ValueError(precision)
  low = precision == "bfloat16"
  dt = jnp.bfloat16 if low else jnp.float32
  opt = spec.optimizer
  spans = traffic.column_spans(spec.inputs)
  keys = np.array([weights.leaf_key(seed, table_name(t))
                   for t in range(len(spec.tables))], np.uint32)
  touched = traffic.touched_rows(batch, spec.inputs)
  dense0 = dense_weights(spec, seed)

  def rounded(x):  # what bfloat16 keeps of a float32 value
    if not low:
      return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

  def rows_of(keys, t, ids):  # [..., width] float32 weights of table t
    tb = spec.tables[t]
    u = weights.unit_uniform(
        jnp, keys[t], ids.astype(jnp.uint32)[..., None],
        jnp.arange(tb.width, dtype=jnp.uint32))
    return u * jnp.float32(tb.scale)

  def loss_of(dense, occ, numerical, labels):
    # sum combiner over the hotness, unless the input is a sequence
    embs = [o if i.sequence else o.sum(axis=1)
            for i, o in zip(spec.inputs, occ)]
    outputs = logits_fn(dense, embs, numerical.astype(dt))
    return spec.loss(jnp, jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), outputs), labels)

  # every shape follows from the configuration alone and the seed's keys are
  # arguments: one compiled program serves every seed
  def grads(dense0_f32, cats, numerical, labels, keys):
    dense = {k: rounded(v).astype(dt) for k, v in dense0_f32.items()}
    occ = [rounded(rows_of(keys, i.table, cats[:, a:b])).astype(dt)
           for i, (a, b) in zip(spec.inputs, spans)]  # [B, h, width] each
    loss, (g_dense, g_occ) = jax.value_and_grad(loss_of, argnums=(0, 1))(
        dense, occ, numerical, labels)
    if not low:
      with jax.default_matmul_precision("highest"):
        loss = loss_of(dense, occ, numerical, labels)
    as_f32 = lambda g: rounded(g.astype(jnp.float32))
    return loss, jax.tree_util.tree_map(as_f32, (g_dense, g_occ))

  loss, (g_dense, g_occ) = jax.device_get(jax.jit(grads)(
      {k: jnp.asarray(v) for k, v in dense0.items()},
      jnp.asarray(batch.cats), jnp.asarray(batch.numerical),
      jax.tree_util.tree_map(jnp.asarray, batch.labels), jnp.asarray(keys)))

  t_delta, a_delta, acc0 = {}, {}, initial_accumulators(opt)
  for t, ids in touched.items():
    width = spec.tables[t].width
    mine = [k for k, i in enumerate(spec.inputs) if i.table == t]
    g = np.concatenate([g_occ[k].reshape(-1, width) for k in mine])
    seg = np.searchsorted(ids, np.concatenate(
        [batch.cats[:, spans[k][0]:spans[k][1]].reshape(-1) for k in mine]))
    if t in spec.summed_tables:
      d, _ = update(opt, _segment_sum(g, seg, len(ids)))
    else:
      # per occurrence, from the accumulator as the step found it: the
      # semantics of a stock sparse optimizer apply, which the
      # configurations state as theirs
      d, acc = update(opt, g.astype(np.float64))
      d = _segment_sum(d, seg, len(ids))
      if acc:
        a_delta[t] = np.concatenate(
            [stored_change(a0, _segment_sum(a, seg, len(ids)))
             for a0, a in zip(acc0, acc)], axis=1)
    tb = spec.tables[t]
    t_delta[t] = stored_change(
        weights.rows_np(int(keys[t]), tb.scale, ids, tb.width), d)
  return StepChange(
      loss=float(loss), table_rows=touched, table_delta=t_delta,
      acc_delta=a_delta,
      dense_delta={k: stored_change(
          dense0[k], update(opt, np.asarray(g, np.float64))[0])
                   for k, g in g_dense.items()},
      dense_before=dense0)
