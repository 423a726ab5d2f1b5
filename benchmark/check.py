"""The one-step correctness check: one train step of the program, through the
window's own feed and call, against the plain reference's one step from the
same weights on the same batch. No second step enters any comparison.

Numbers compared, each with its own limit (the configuration's
``check_limits``, set from chip readings; PERF.md gives them):

- ``fill``: before the step, a sample of row blocks of every packed class
  drawn from the seed holds, on every lane, what the fill put there (the
  benchmark's weights, the rule's initial accumulator, 0 on padding), and
  every dense leaf holds the benchmark's weights, bit for bit (limit 0);
- ``loss_gap``: |loss - reference| / |reference| at step 0, against the
  reference's loss at ``highest`` matmul precision;
- ``table_change_gap`` / ``dense_change_gap``: per leaf, the norm of
  (program's change - reference's change) over the norm of the reference's
  change of that leaf or of the median leaf of its group, whichever is
  larger (a leaf whose gradient is all but zero is judged on the group's
  scale); the worst leaf is reported. The reference's step is float32 at
  the device's default matmul precision, as the configurations state it;
- ``accumulator_stray`` (rules that keep accumulators in the row; one number
  per lane group: ``accumulator_stray`` for the first, Adagrad's sum of
  squares or Adam's first moment, ``accumulator_2_stray`` for Adam's second
  moment): how many accumulator values of the rows the batch read lie
  farther from the reference's than float32 accumulation explains (limit 0).
  An accumulator that starts at 0 is judged in float32 steps of the
  reference's largest change. One step adds
  the squared gradients of a row's occurrences to an accumulator whose
  float32 step is far above most of them, and a float32 program that adds
  them one at a time may lose every one; so a value is astray only beyond
  ``ACC_STEPS`` float32 steps of the initial accumulator plus ``ACC_SHARE``
  of the reference's change. At the zoo cell's size the largest change is 8
  steps (PERF.md, PR 25), so there the number catches a corrupted
  accumulator, not a skipped one; where a step moves the accumulator
  visibly, as at test size, it catches both;
- ``untouched``: in the same sample of row blocks, every lane of a row the
  batch did not read, accumulator and padding lanes too, still holds its
  initial bits (limit 0).
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, Tuple

import numpy as np

from benchmark import reference

# what sequential float32 accumulation of a hot row's occurrences can differ
# by (a random walk of half-step roundings over some 50,000 adds), and room
# over the 4% that per-occurrence rounding costs a visible change at test
# size (CPU, PR 25)
ACC_STEPS, ACC_SHARE = 128, 0.1


@dataclasses.dataclass
class Compared:
  name: str
  value: float
  where: str
  limit: float

  @property
  def ok(self) -> bool:
    return bool(np.isfinite(self.value)) and self.value <= self.limit

  def line(self) -> str:
    return (f"compare {self.name}: {self.value:.6g} at {self.where} "
            f"(limit {self.limit:g}) {'ok' if self.ok else 'OUTSIDE'}")


def _norm(x) -> float:
  return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


def worst_gap(program: Dict[Any, np.ndarray], ref: Dict[Any, np.ndarray],
              label) -> Tuple[float, str]:
  """Worst leaf of ||program - ref|| / max(||ref||, median leaf's ||ref||),
  and where it is (with the median leaf's gap, to show the spread)."""
  norms = {k: _norm(v) for k, v in ref.items()}
  floor = statistics.median(norms.values())
  gaps = {}
  for k, r in ref.items():
    denom = max(norms[k], floor)
    gaps[k] = _norm(program[k].astype(np.float64) - r) / denom if denom > 0 \
        else float(np.any(program[k] != 0))
  where = max(gaps, key=lambda k: float("inf") if np.isnan(gaps[k])
              else gaps[k])
  return gaps[where], (f"{label(where)} (median leaf "
                       f"{statistics.median(gaps.values()):.3g})")


def accumulator_stray(program: Dict[int, np.ndarray],
                      ref: Dict[int, np.ndarray], initial: float
                      ) -> Tuple[float, str]:
  """Accumulator values of touched rows (one lane group) farther from the
  reference's than ``ACC_STEPS`` float32 steps of ``initial`` (of the
  reference's largest change where that is 0) plus ``ACC_SHARE`` of the
  reference's change, and what was looked at."""
  largest = max(np.abs(r).max() for r in ref.values())
  step = float(np.spacing(np.float32(initial if initial else largest)))
  stray = sum(int(np.sum(~(np.abs(program[t] - r) <= ACC_STEPS * step
                           + ACC_SHARE * np.abs(r)))) for t, r in ref.items())
  return float(stray), (
      f"values among {sum(r.size for r in ref.values())} of the rows read; "
      f"the reference's largest change is {largest / step:.0f} float32 steps")


def one_step(prog, state, step, batch, ref: reference.StepChange,
             limits: Dict[str, float]):
  """-> (state after the step, [Compared], the step's loss)."""
  touched = ref.table_rows
  before_d = prog.read_dense(state)
  bad = sum(int(np.sum(before_d[n] != ref.dense_before[n]))
            for n in ref.dense_before)
  looked = 0
  for name, rank, start, got in prog.read_blocks(state):
    want, _, _ = prog.expected_block(name, rank, start, got.shape[0])
    want = np.asarray(want, prog.table_dtype).astype(np.float32)
    bad += int(np.sum(got != want))
    looked += got.size
  out = [Compared("fill", float(bad),
                  f"values that differ among {looked} sampled and the dense "
                  "leaves", 0.0)]

  state, loss = step(state, *prog.put(batch))
  loss = float(loss)
  (changed_t, changed_a), after_d = prog.table_changes(state, touched), \
      prog.read_dense(state)
  out.append(Compared(
      "loss_gap", abs(loss - ref.loss) / max(abs(ref.loss), 1e-30),
      f"step 0 (program {loss:.7g}, reference {ref.loss:.7g})",
      limits["loss_gap"]))
  gap, where = worst_gap(changed_t, ref.table_delta, reference.table_name)
  out.append(Compared("table_change_gap", gap, where,
                      limits["table_change_gap"]))
  if ref.acc_delta:
    # one comparison per lane group the rule keeps beside a row
    for j, initial in enumerate(
        reference.initial_accumulators(prog.spec.optimizer)):
      group = lambda d: {t: x[:, j * prog.spec.tables[t].width:
                              (j + 1) * prog.spec.tables[t].width]
                         for t, x in d.items()}
      stray, where = accumulator_stray(group(changed_a),
                                       group(ref.acc_delta), initial)
      out.append(Compared(
          "accumulator_stray" if j == 0 else f"accumulator_{j + 1}_stray",
          stray, where, 0.0))
  gap, where = worst_gap(
      {n: after_d[n] - ref.dense_before[n] for n in ref.dense_delta},
      ref.dense_delta, str)
  out.append(Compared("dense_change_gap", gap, where,
                      limits["dense_change_gap"]))

  changed, looked = 0, 0
  for name, rank, start, got in prog.read_blocks(state):
    want, table, trow = prog.expected_block(name, rank, start, got.shape[0])
    idle = np.ones(table.shape, bool)
    for t in np.unique(table[table >= 0]):
      sel = table == t
      idle[sel] = ~np.isin(trow[sel], touched.get(int(t), ()))
    want = np.asarray(want, prog.table_dtype).astype(np.float32)
    changed += int(np.sum(got[idle] != want[idle]))
    looked += int(np.sum(idle))
  out.append(Compared("untouched", float(changed),
                      f"changed values among {looked} sampled", 0.0))
  return state, out, loss
