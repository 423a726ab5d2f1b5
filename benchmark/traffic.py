"""The one general traffic generator. A traffic mix is a data file under
``benchmark/workloads/`` (parameters only); the family supplies what its
model's inputs are (rows and hotness per categorical input, number of
numerical features, which may be 0) and, where its labels are not one coin a
sample, how they are drawn (``make_labels``). A batch draws, in this order
from one generator: the ids of every input, the numerical features, the
labels. Copied from the program's sound generator
(`models/synthetic.py::power_law_ids`, ``generate_batch``; the reference's
``InputGenerator``) so that a later change to the program cannot change the
traffic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Sequence, Tuple

import numpy as np

from . import weights


@dataclasses.dataclass(frozen=True)
class CatInput:
  """One categorical input: the table it reads, that table's rows, how many
  ids a sample carries (hotness), and whether the model sees their rows
  summed (``[B, width]``) or kept in order as a sequence
  (``[B, hotness, width]``). Either way every id is one occurrence."""
  table: int
  rows: int
  hotness: int
  sequence: bool = False


@dataclasses.dataclass(frozen=True)
class Batch:
  numerical: np.ndarray  # [B, n_numerical] float32
  cats: np.ndarray       # [B, sum(hotness)] int32, inputs side by side
  labels: Any            # [B] float32, or the family's tree of [B, ...] arrays


def power_law_ids(rng: np.random.Generator, n: int, num_rows: int,
                  alpha: float) -> np.ndarray:
  """``n`` ids in [0, num_rows): inverse-CDF power law with exponent alpha
  (alpha = 0 is uniform)."""
  if alpha == 0:
    return rng.integers(0, num_rows, size=n, dtype=np.int64)
  gamma = 1.0 - alpha
  r = rng.random(n)
  lo, hi = 1.0, float(num_rows + 1)
  y = (r * (hi ** gamma - lo ** gamma) + lo ** gamma) ** (1.0 / gamma)
  return (y.astype(np.int64) - 1).clip(0, num_rows - 1)


def coin_labels(rng: np.random.Generator, traffic: dict, cats: np.ndarray):
  """The default labels: one fair coin a sample, ``[B]`` float32."""
  del traffic
  return rng.integers(0, 2, size=(cats.shape[0],)).astype(np.float32)


def family_labels(family, config: dict) -> Callable:
  """The family's ``make_labels(rng, mix, config, cats)`` with its
  configuration bound; the coin where the family has none."""
  fn = getattr(family, "make_labels", None)
  if fn is None:
    return coin_labels
  return lambda rng, mix, cats: fn(rng, mix, config, cats)


def make_batch(traffic: dict, inputs: Sequence[CatInput], n_numerical: int,
               seed: int, index: int,
               make_labels: Callable = coin_labels) -> Batch:
  """Batch ``index`` of the pool. Every seed draws the same sizes from the
  same distributions; only the values differ. ``make_labels(rng, traffic,
  cats)`` returns a tree of arrays whose leading dimension is the batch; it
  draws from the batch's generator after the ids and the numerical
  features."""
  rng = np.random.default_rng(weights.seed_words(seed, 0x7A, index))
  batch = int(traffic["global_batch"])
  alpha = float(traffic["alpha"])
  cols = [power_law_ids(rng, batch * i.hotness, i.rows, alpha)
          .reshape(batch, i.hotness) for i in inputs]
  lo, hi = traffic["numerical_range"] if n_numerical else (0.0, 1.0)
  numerical = rng.uniform(lo, hi, size=(batch, n_numerical))
  cats = np.concatenate(cols, axis=1).astype(np.int32)
  return Batch(numerical.astype(np.float32), cats,
               make_labels(rng, traffic, cats))


def make_pool(traffic: dict, inputs: Sequence[CatInput], n_numerical: int,
              seed: int, make_labels: Callable = coin_labels) -> List[Batch]:
  return [make_batch(traffic, inputs, n_numerical, seed, i, make_labels)
          for i in range(int(traffic["pool_batches"]))]


def column_spans(inputs: Sequence[CatInput]) -> List[Tuple[int, int]]:
  """[start, end) columns of each input inside ``Batch.cats``."""
  spans, at = [], 0
  for i in inputs:
    spans.append((at, at + i.hotness))
    at += i.hotness
  return spans


def touched_rows(batch: Batch, inputs: Sequence[CatInput]) -> dict:
  """table -> sorted unique ids the batch reads from it (all its inputs)."""
  per_table = {}
  for i, (a, b) in zip(inputs, column_spans(inputs)):
    per_table.setdefault(i.table, []).append(batch.cats[:, a:b].reshape(-1))
  return {t: np.unique(np.concatenate(v)) for t, v in per_table.items()}
