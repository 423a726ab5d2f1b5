"""Real-TPU smoke test for the sparse-attention kernels.

Runs `layers/sparse_index.py::sparse_attention`, forward and backward, at
the shapes of `keye_dsa_train_1chip` (8,192 positions, 32 query heads over 4
key-value heads of 128, 16 index heads of 64, ``topk`` 2,048, tiles of 512,
two documents with the boundary inside a block) through the Mosaic kernels
of `ops/pallas_sparse_attn.py`, which is the path a TPU takes, and through
the XLA tile loop, which is the path every other backend takes and the
kernels' oracle; and compares the output, the indexer's loss, the counters
and the six gradients. Interpret mode covers the kernels' semantics in
float32 (tests/test_pallas_sparse_attn.py); this validates what Mosaic makes
of the bodies with bfloat16 operands. Both paths round their operands to
bfloat16 and sum in another order, so the limits are those of one bfloat16
pass, as the other kernel smokes': a share of each leaf's largest value.

After the comparison it prints, outside any benchmark's window, the device
time of each path and of each kernel alone (median of a few calls on the
host's clock, each ended by ``block_until_ready``).

Run: python tools/smoke_pallas_sparse_attn.py   (leg D of chip_smoke.py)
Exit code 0 = pass; non-zero on any failure AND on a backend that is not
a TPU (there is nothing to validate off the chip).
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.compile_cache import enable_compile_cache
from distributed_embeddings_tpu.layers import sparse_index
from distributed_embeddings_tpu.ops import pallas_sparse_attn as psa
from distributed_embeddings_tpu.parallel.mesh import require_tpu

T, HKV, G, HD, HI, DI, TOPK, TILE = 8192, 4, 8, 128, 16, 64, 2048, 512
BOUNDARY = 3000          # the second document's first position
# of a leaf's largest value: output and gradients (one bfloat16 pass each
# way, sums in another order). The first readings on the chip (PR 41): 4.4e-4
# for the output, 9.7e-4 for dq, under 1.3e-4 for the five others
LIMIT = 5e-3
REPEATS = 5


def _operands(seed: int):
  rng = np.random.default_rng(seed)
  f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
  return (f(1, T, HKV, G, HD) * HD ** -0.5, f(1, T, HKV, HD),
          f(1, T, HKV, HD), f(1, T, HI, DI), f(1, T, DI),
          f(1, T, HI) * (HI * DI) ** -0.5)


def _loss(seg, *ops):
  o, kl, counters = sparse_index.sparse_attention(*ops, seg, topk=TOPK,
                                                  tile=TILE)
  return jnp.sum(jnp.sin(o)) + 3.0 * kl, (o, kl, counters)


def _median_ms(fn, *args):
  jax.block_until_ready(fn(*args))
  times = []
  for _ in range(REPEATS):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    times.append(time.perf_counter() - t0)
  return 1e3 * float(np.median(times))


def _kernels_alone(ops, seg):
  """Each kernel's time at these shapes under the selection's own mask."""
  q, k, v, qi, ki, wi = (x[0] for x in ops)
  _, residuals = jax.jit(functools.partial(sparse_index._forward, TOPK, TILE))(
      q, k, v, qi, ki, wi, seg[0])
  packed, o, lse = residuals[7], residuals[8], residuals[9]
  mask = jax.jit(lambda packed: sparse_index._whole_mask(
      sparse_index.unpacked_runs(packed, T, TILE), T))(packed)
  counts = psa.block_counts(mask, TILE, TILE)
  plan, plan_t = psa.block_plan(counts), psa.block_plan(counts.T)
  q2, k2, v2, do2 = sparse_index._kernel_operands(jnp.bfloat16, q, k, v,
                                                  jnp.cos(o))
  delta = jnp.sum(jnp.cos(o) * o, axis=-1)
  by_query = jnp.swapaxes(lse, 1, 2)
  at = dict(group=G, hd=HD, block_q=TILE, block_k=TILE)
  # (the dq kernel also sums the heads' probabilities: the backward's mean)
  return {
      psa.FWD_NAME: _median_ms(jax.jit(functools.partial(psa.attend, **at)),
                               q2, k2, v2, mask, plan),
      psa.MEAN_NAME: _median_ms(
          jax.jit(functools.partial(psa.head_mean, **at)), q2, k2, by_query,
          mask, plan),
      psa.DQ_NAME: _median_ms(
          jax.jit(functools.partial(psa.grad_q, **at)), q2, k2, v2, do2,
          by_query, jnp.swapaxes(delta, 0, 1), mask, plan),
      psa.DKV_NAME: _median_ms(
          jax.jit(functools.partial(psa.grad_kv, **at)), q2, k2, v2, do2,
          lse, jnp.moveaxis(delta, 0, 2), mask.T, plan_t),
      "attended_blocks": int(jnp.sum(counts > 0)),
      "blocks": int(counts.size),
  }


def main():
  print("device:", json.dumps(require_tpu("smoke_pallas_sparse_attn")),
        flush=True)
  ops = _operands(7)
  seg = jnp.asarray((np.arange(T) >= BOUNDARY).astype(np.int32))[None]
  if sparse_index.attention_kernels(T, HD, TILE) is not False:
    print("FAILED: on a TPU these shapes do not take the kernels' path")
    sys.exit(1)
  grad = lambda: jax.jit(jax.value_and_grad(
      functools.partial(_loss, seg), argnums=tuple(range(6)), has_aux=True))
  kernels = grad()
  (_, (o, kl, counters)), grads = kernels(*ops)
  lowered = kernels.lower(*ops).compile().as_text()
  names = [n for n in (psa.FWD_NAME, psa.MEAN_NAME, psa.DQ_NAME, psa.DKV_NAME)
           if n not in lowered]
  kernels_ms = _median_ms(kernels, *ops)

  chosen_path = sparse_index.attention_kernels
  sparse_index.attention_kernels = lambda *_: None      # the oracle's path
  try:
    tiles = grad()
    (_, (o_want, kl_want, counters_want)), grads_want = tiles(*ops)
    tiles_ms = _median_ms(tiles, *ops)
  finally:
    sparse_index.attention_kernels = chosen_path

  failed = [f"the compiled program lacks {names}"] if names else []
  counters, counters_want = (
      {n: int(v) for n, v in c.items()} for c in (counters, counters_want))
  print("counters:", json.dumps(counters))
  if counters != counters_want:
    failed.append(f"counters {counters} != the tile loop's {counters_want}")
  if counters["attended_blocks"] + counters["skipped_blocks"] \
      != (T // TILE) ** 2:
    failed.append("attended + skipped blocks are not the grid")
  for name, got, want in zip(("o", "q", "k", "v", "qi", "ki", "wi"),
                             (o,) + grads, (o_want,) + grads_want):
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    ok = np.isfinite(err) and err <= LIMIT * scale
    print(f"sparse attention {name:>2} vs the tile loop : "
          f"{'OK' if ok else 'FAIL'} (max err {err:.3e}, largest {scale:.3e})")
    if not ok:
      failed.append(name)
  ok = abs(float(kl) - float(kl_want)) <= LIMIT * abs(float(kl_want))
  print(f"sparse attention kl vs the tile loop : {'OK' if ok else 'FAIL'} "
        f"({float(kl):.6f} against {float(kl_want):.6f})")
  if not ok:
    failed.append("kl")

  print("forward + backward, ms a layer:",
        json.dumps({"kernels": round(kernels_ms, 3),
                    "tile_loop": round(tiles_ms, 3)}))
  print("kernels alone, ms:", json.dumps(
      {n: round(v, 3) if isinstance(v, float) else v
       for n, v in _kernels_alone(ops, seg).items()}))
  if failed:
    print(f"FAILED: {failed}")
    sys.exit(1)
  print("sparse attention smoke PASS")


if __name__ == "__main__":
  enable_compile_cache()
  main()
