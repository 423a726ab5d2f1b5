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
equations (``reference_logits``) and the plain description of its
parameters.

What it returns is the one-step change of every parameter the batch can
change, as a float32 state shows it (the float32 value after the step minus
the one before: a change far below the value's float32 step is mostly
rounding, in any float32 program, and PR 25's chip runs read that rounding
as the zoo's heavy-tailed gaps): per table the distinct touched rows with
their change (and the change of their optimizer accumulator, where the rule
keeps one), and the change of every dense leaf. The loss is computed a
second time, forward only, at ``highest`` matmul precision: that is the loss
the check compares.

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
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from benchmark import traffic, weights


@dataclasses.dataclass(frozen=True)
class TableSpec:
  rows: int
  width: int
  scale: float  # weights are uniform in (-scale, scale)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
  """A model as its configuration file describes it, in plain terms."""
  tables: Tuple[TableSpec, ...]
  inputs: Tuple[traffic.CatInput, ...]
  n_numerical: int
  dense_leaves: Dict[str, Tuple[Tuple[int, ...], float]]  # name -> shape, scale
  optimizer: Dict[str, Any]  # name, learning_rate, and the rule's constants
  # tables whose update is computed once per distinct row from the summed
  # gradient (the configuration's small tables, updated densely); all other
  # tables are updated per occurrence
  summed_tables: frozenset = frozenset()


@dataclasses.dataclass
class StepChange:
  loss: float
  table_rows: Dict[int, np.ndarray]      # table -> distinct touched ids
  table_delta: Dict[int, np.ndarray]     # table -> [n, width] change
  # table -> [n, width] change of the rows' accumulator: per-occurrence
  # tables under a rule that keeps one (their accumulator rides in the row)
  acc_delta: Dict[int, np.ndarray]
  dense_delta: Dict[str, np.ndarray]
  dense_before: Dict[str, np.ndarray]


def table_name(t: int) -> str:
  return f"table_{t:03d}"


def dense_weights(spec: ModelSpec, seed: int) -> Dict[str, np.ndarray]:
  return {name: weights.dense_np(weights.leaf_key(seed, name), scale, shape)
          for name, (shape, scale) in spec.dense_leaves.items()}


def bce_with_logits(jnp, logits, labels):
  return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                  + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def initial_accumulator(opt: Dict[str, Any]) -> Optional[float]:
  """The value the rule's accumulator starts from; None where it has none."""
  if opt["name"] == "adagrad":
    return float(opt["initial_accumulator_value"])
  return None


def update(opt: Dict[str, Any], g: np.ndarray):
  """One optimizer step from its initial state on float64 gradients:
  -> (the parameter's change, the accumulator's change or None)."""
  lr = float(opt["learning_rate"])
  if opt["name"] == "sgd":
    return -lr * g, None
  if opt["name"] == "adagrad":
    g2 = g * g
    acc_new = float(opt["initial_accumulator_value"]) + g2
    return -lr * g / np.sqrt(acc_new + float(opt["eps"])), g2
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
  weights. ``logits_fn(dense, embs, numerical) -> [B]`` with ``embs`` one
  combined ``[B, width]`` activation per input."""
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
    embs = [o.sum(axis=1) for o in occ]  # sum combiner over the hotness
    logits = logits_fn(dense, embs, numerical.astype(dt))
    return bce_with_logits(jnp, logits.astype(jnp.float32), labels)

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
      jnp.asarray(batch.labels), jnp.asarray(keys)))

  t_delta, a_delta = {}, {}
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
      if acc is not None:
        a_delta[t] = stored_change(initial_accumulator(opt),
                                   _segment_sum(acc, seg, len(ids)))
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
