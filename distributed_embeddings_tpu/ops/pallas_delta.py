"""Pallas delta-build kernel for the sparse apply (round 5).

Builds the per-occurrence fused update rows ``[n, phys_width]`` — hotness
broadcast of the per-sample cotangent, optimizer-state lane extraction,
the rule's delta math, and the sub-row window expansion — in ONE pass
through VMEM, emitting rows in the row-major layout the scatter wants.

Why: XLA stages this chain through batch-minor layouts (the h-broadcast
materializes `{0,1}`, the window-expansion einsum's output is occurrence-
minor) and transposes back at the EXPANDED stream right before the
scatter — ~14 ms/step of copies/reshapes/broadcast-multiplies on Tiny
(a round-5 device trace; the zoo cell's delta streams as traced now:
PERF.md section 5; two XLA-level reorderings and a layout-pin
identity kernel all measured neutral-to-negative before this kernel —
the layout choice is XLA's, not the graph's).

Everything in-kernel is 2-D with static lane slicing (Mosaic rejects the
[.., rpp, stride] -> [.., phys] minor-dim merges the XLA form relies on):
the h occurrences and the rpp windows unroll as static lane-slice
reads/writes on ``[Kb, h*lanes]`` blocks, and the rule math runs via
``SparseRule.delta_lanes`` (the flat-lanes twin of ``delta``; equality
pinned by ``tests/test_pallas_delta.py``).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_util import out_struct

PHYS = 128
_MAX_KB = 256
_BUDGET_ELEMS = 1 << 18  # ~1 MiB f32 per block before double-buffering


def pick_block(k: int, h: int, aux_last: int) -> int:
  """Largest divisor block of ``k`` whose in/out/aux VMEM footprint
  (``kb * h * (PHYS + aux_last + lanes-padded dz/sub)``) fits the budget;
  0 when none does (caller falls back to the XLA chain)."""
  per_row = h * (PHYS + max(aux_last, 1)) + 2 * PHYS  # dz + sub pads
  kb = min(_MAX_KB, max(1, _BUDGET_ELEMS // max(per_row, 1)), k)
  while kb > 1 and k % kb:
    kb -= 1
  if k % kb or kb * per_row > _BUDGET_ELEMS:
    return 0
  return kb


def _kernel(h, w, stride, rpp, n_aux, aux_last, delta_lanes,
            step_ref, dz_ref, sub_ref, aux_ref, out_ref):
  g = dz_ref[...]  # [Kb, w] f32
  step = step_ref[0]
  for j in range(h):
    subj = sub_ref[:, j:j + 1]  # [Kb, 1] int32
    aux_list = []
    if n_aux:
      aj = aux_ref[:, j * aux_last:(j + 1) * aux_last]
      if aux_last == stride:
        lanes = aj[:, w:]
      else:  # window-masked phys rows: exactly one window nonzero
        lanes = aj[:, w:stride]
        for s in range(1, rpp):
          lanes = lanes + aj[:, s * stride + w:(s + 1) * stride]
      aux_list = [lanes[:, a * w:(a + 1) * w] for a in range(n_aux)]
    parts = delta_lanes(g, aux_list, step)
    fused = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
    for r in range(rpp):
      out_ref[:, j * PHYS + r * stride:j * PHYS + (r + 1) * stride] = \
          jnp.where(subj == r, fused, 0.0)
    pad0 = rpp * stride
    if pad0 < PHYS:
      out_ref[:, j * PHYS + pad0:(j + 1) * PHYS] = jnp.zeros(
          (g.shape[0], PHYS - pad0), jnp.float32)


def build_delta_rows(layout, rule, dz, sub, aux, h: int, step,
                     interpret: bool = False):
  """``dz [K, w]`` per-sample cotangents, ``sub [K*h]`` window indices,
  ``aux [K*h, aux_last]`` forward-gathered rows (or None) ->
  ``[K*h, PHYS]`` f32 fused update rows (invalid-id masking stays in the
  scatter, which also validates/clamps the group indices)."""
  k, w = dz.shape
  n = k * h
  stride, rpp = layout.stride, layout.rows_per_phys
  n_aux = rule.n_aux
  aux_last = aux.shape[-1] if aux is not None else 0
  kb = pick_block(k, h, aux_last)
  if not kb:
    raise ValueError(f"no VMEM-feasible block for k={k}, h={h} "
                     f"(gate callers check pick_block first)")
  sub2 = sub.reshape(k, h)
  aux2 = (aux.reshape(k, h * aux_last) if aux is not None
          else jnp.zeros((k, 1), jnp.float32))
  a_last = aux2.shape[-1]
  step_arr = jnp.asarray(step, jnp.int32).reshape(1)
  out = pl.pallas_call(
      functools.partial(_kernel, h, w, stride, rpp, n_aux, aux_last,
                        rule.delta_lanes),
      grid=(k // kb,),
      in_specs=[
          pl.BlockSpec(memory_space=pltpu.SMEM),
          pl.BlockSpec((kb, w), lambda i: (i, 0)),
          pl.BlockSpec((kb, h), lambda i: (i, 0)),
          pl.BlockSpec((kb, a_last), lambda i: (i, 0)),
      ],
      out_specs=pl.BlockSpec((kb, h * PHYS), lambda i: (i, 0)),
      out_shape=out_struct((k, h * PHYS), jnp.float32, dz, sub2, aux2),
      interpret=interpret,
      name="de_delta_build",
  )(step_arr, dz, sub2, aux2)
  return out.reshape(n, PHYS)
