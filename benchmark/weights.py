"""The benchmark's own weights: a value for every (leaf, row, column) as a
pure function of the run's seed, so that the program's state can be filled
on the device in one jitted call and the plain reference can evaluate any
row without holding a table and without reading anything the program made.

One 32-bit mixing function is written once over an array namespace: numpy
(reference, read-back comparisons) and ``jax.numpy`` (the on-device fill)
run the same integer arithmetic, and the float conversion uses only
operations that are exact in float32 up to the final correctly-rounded
multiply by the leaf's scale, so both produce the same bits.
"""

from __future__ import annotations

import zlib

import numpy as np

_M1 = 0x7FEB352D
_M2 = 0x846CA68B
_ROW = 0x9E3779B1
_COL = 0x85EBCA77


def seed_words(seed: int, *stream: int) -> np.random.SeedSequence:
  """The one way any stream is derived from ``--seed`` (0 .. 2**63 - 1):
  nothing casts the seed to a fixed-width integer."""
  if seed < 0:
    raise ValueError(f"seed must be >= 0, got {seed}")
  return np.random.SeedSequence([int(seed), *[int(s) for s in stream]])


def leaf_key(seed: int, name: str) -> int:
  """32-bit key of one parameter leaf (a table, a kernel, a bias)."""
  crc = zlib.crc32(name.encode())
  return int(seed_words(seed, 0x77, crc).generate_state(1, np.uint32)[0])


def _mix(xp, x):
  u = xp.uint32
  x = x ^ (x >> u(16))
  x = x * u(_M1)
  x = x ^ (x >> u(15))
  x = x * u(_M2)
  return x ^ (x >> u(16))


def unit_uniform(xp, key, rows, cols):
  """uniform in [-1, 1) at 2**-23 steps for each (key, row, col); the three
  broadcast against each other. ``key``/``rows``/``cols``: uint32 arrays."""
  u = xp.uint32
  h = _mix(xp, key ^ (rows * u(_ROW)))
  h = _mix(xp, h + cols * u(_COL))
  h = _mix(xp, h ^ key)
  return (h >> u(8)).astype(xp.float32) * xp.float32(2.0 ** -23) \
      - xp.float32(1.0)


def rows_np(key: int, scale: float, rows: np.ndarray, width: int
            ) -> np.ndarray:
  """``[len(rows), width]`` float32 values of one leaf's rows, on the host."""
  with np.errstate(over="ignore"):
    r = np.asarray(rows, np.int64).astype(np.uint32)[:, None]
    c = np.arange(width, dtype=np.uint32)[None, :]
    k = np.full((1, 1), key, np.uint32)
    return unit_uniform(np, k, r, c) * np.float32(scale)


def dense_np(key: int, scale: float, shape, offset: float = 0.0
             ) -> np.ndarray:
  """A whole dense leaf, ``offset + uniform(+-scale)``: a 1-D leaf is one
  row, a leaf of rank > 2 is hashed as (every leading index, row-major) x
  its last dimension. No offset is added where it is 0, so a leaf that
  states none keeps its bits (a bias of scale 0 holds -0.0 too)."""
  shape = tuple(int(d) for d in shape)
  if len(shape) == 1:
    out = rows_np(key, scale, np.zeros((1,), np.int64), shape[0])[0]
  else:
    rows = int(np.prod(shape[:-1]))
    out = rows_np(key, scale, np.arange(rows), shape[-1]).reshape(shape)
  return out + np.float32(offset) if offset else out
