"""The expert layer's combine alone: the kernel against XLA's two forms.

Times ON THE CHIP, at the two shapes the MoE cells run (the head of the
sorted stream is 32,768 rows of 2,048 float32 in all four; ``T`` = 8,192
tokens with ``top_k`` = 8, and ``T`` = 16,384 with ``top_k`` = 4), with
``pos`` from a seeded router's sort (random logits over 128 experts, 16 held
here, the held ones first, as ``layers/moe.py`` sorts):

- ``kernel``:      ``ops/pallas_moe_combine.combine`` (``de_moe_combine``);
- ``scatter_add``: ``zeros([T, d]).at[tok].add(rows)``, what the layer ran
  before PR 44 and still runs off the TPU;
- ``gather_sum``:  ``sum_j take(rows + a zero row, pos)[t, j]``, the same sum
  read token-major in XLA (a ``[T * k, d]`` temporary).

Prints one JSON line a (shape, variant): ms a call, ns a row of the head,
the share of the byte floor (the head read once and the output written once
at 819 GB/s: 332 MB at ``T`` = 8,192, 396 MB at 16,384) and the kernel's
largest difference from the scatter-add; then ``gate``: whether the kernel is
at least 2.4 times faster than the scatter-add at both shapes and within 5%
of what it read with the loop that starts a group's copies written out in
Python (PR 43: 1.04 ms and 1.14; ISSUE 44: a form of that loop lands only if
it holds them). ``--sweep`` times the kernel's block and depth besides;
``--pos`` what the inverse of the sorted order costs as a scatter, as a
second sort, and counted as the layer does (`moe.sorted_positions`). The lines also go to
``chiprun_out/bench_moe_combine.jsonl``.

Run: chiprun -- python tools/bench_moe_combine.py [--sweep] [--pos]
Exits non-zero without a TPU, or where the kernel disagrees with the
scatter-add.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.compile_cache import enable_compile_cache
from distributed_embeddings_tpu.layers import moe
from distributed_embeddings_tpu.ops import pallas_moe_combine as pmc
from distributed_embeddings_tpu.parallel.mesh import require_tpu

HBM_BYTES_PER_S = 819e9
SHAPES = ((8192, 8), (16384, 4))      # (tokens, top_k) of the four cells
# ms a call the kernel may take at each shape, and how many times faster than
# the scatter-add it has to be (ISSUE 44: PR 43's readings and 5%)
HOLD_MS = {(8192, 8): 1.10, (16384, 4): 1.20}
TIMES_FASTER = 2.4
D, EXPERTS, HELD = 2048, 128, 16
OUT = os.path.join(os.path.dirname(__file__), "..", "chiprun_out",
                   "bench_moe_combine.jsonl")


def routed(rng, tokens, top_k):
  """A seeded router's sort -> (key ``[T * k]`` as the layer makes it,
  order, pos ``[T, k]``, the head's rows): random logits, the ``top_k``
  largest an expert, the held experts' assignments first."""
  logits = rng.standard_normal((tokens, EXPERTS)).astype(np.float32)
  top_e = np.argsort(-logits, axis=1, kind="stable")[:, :top_k]
  key = np.where(top_e < HELD, top_e, HELD).reshape(-1).astype(np.int32)
  order = np.argsort(key, kind="stable").astype(np.int32)
  pos = np.empty_like(order)
  pos[order] = np.arange(order.size, dtype=np.int32)
  share = moe.MoEShare(EXPERTS, top_k, (0, HELD))
  return key, order, pos.reshape(tokens, top_k), share.head_rows(order.size)


def ms_a_call(fn, *args, iters=20):
  jax.block_until_ready(fn(*args))
  t0 = time.perf_counter()
  for _ in range(iters):
    out = fn(*args)
  jax.block_until_ready(out)
  return 1e3 * (time.perf_counter() - t0) / iters


def main():
  device = require_tpu("bench_moe_combine")
  ap = argparse.ArgumentParser()
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--sweep", action="store_true",
                  help="the kernel's block x depth besides its defaults")
  ap.add_argument("--pos", action="store_true",
                  help="time three ways to the inverse of the sorted order")
  args = ap.parse_args()
  print("device:", json.dumps(device), flush=True)
  rng = np.random.default_rng(args.seed)
  lines, faster, held = [], [], []
  for tokens, top_k in SHAPES:
    key, order, pos, head = routed(rng, tokens, top_k)
    rows = jax.random.normal(jax.random.PRNGKey(args.seed), (head, D),
                             jnp.float32)
    tok = jnp.asarray(order[:head] // top_k)
    pos_j = jnp.asarray(pos)
    ones = jnp.ones(pos.shape, jnp.float32)
    floor_ms = 1e3 * 4 * D * (head + tokens) / HBM_BYTES_PER_S
    variants = {
        "scatter_add": (jax.jit(
            lambda r, i: jnp.zeros((tokens, D), r.dtype).at[i].add(r)),
                        (rows, tok)),
        "gather_sum": (jax.jit(lambda r, p: jnp.sum(jnp.take(
            jnp.concatenate([r, jnp.zeros((1, D), r.dtype)]),
            jnp.minimum(p, head), axis=0), axis=1)), (rows, pos_j)),
        "kernel": (jax.jit(pmc.combine), (rows, pos_j, ones)),
    }
    if args.sweep:
      # what the default 16 MiB of scoped VMEM hold (the kernel asks no more)
      for block in (128, 256, 512):
        for depth in (2, 4, 8):
          if (depth * top_k * 8 * D * 4 <= pmc.RING_BYTES
              and (block * top_k) % pmc.SMEM_TILE == 0):
            variants[f"kernel block={block} depth={depth}"] = (
                jax.jit(lambda r, p, s, b=block, dp=depth: pmc.combine(
                    r, p, s, block=b, depth=dp)), (rows, pos_j, ones))
    want = np.asarray(variants["scatter_add"][0](rows, tok))
    ms = {}
    for name, (fn, operands) in variants.items():
      err = float(np.max(np.abs(np.asarray(fn(*operands)) - want)))
      ms[name] = ms_a_call(fn, *operands)
      line = {"tokens": tokens, "top_k": top_k, "head_rows": head,
              "variant": name, "ms": round(ms[name], 4),
              "ns_per_row": round(1e6 * ms[name] / head, 2),
              "byte_floor_ms": round(floor_ms, 4),
              "share_of_floor": round(floor_ms / ms[name], 4),
              "max_abs_diff": err}
      lines.append(line)
      print(json.dumps(line), flush=True)
      if err > 1e-4:
        print(f"FAIL: {name} disagrees with the scatter-add", flush=True)
        sys.exit(1)
    faster.append(ms["scatter_add"] / ms["kernel"])
    held.append(ms["kernel"] <= HOLD_MS[tokens, top_k])
    if args.pos:
      classes = HELD + 1
      n = order.size
      ways = {
          "pos scatter": jax.jit(lambda o: jnp.zeros((n,), jnp.int32).at[o].set(
              jnp.arange(n, dtype=jnp.int32), unique_indices=True)),
          "pos argsort": jax.jit(lambda o: jnp.argsort(o).astype(jnp.int32)),
      }
      got = {w: np.asarray(f(jnp.asarray(order))) for w, f in ways.items()}
      ways["pos counted"] = jax.jit(
          lambda ky: moe.sorted_positions(ky, classes))
      got["pos counted"] = np.asarray(ways["pos counted"](jnp.asarray(key)))
      for w, f in ways.items():
        arg = jnp.asarray(key if w == "pos counted" else order)
        line = {"tokens": tokens, "top_k": top_k, "variant": w,
                "ms": round(ms_a_call(f, arg), 4),
                "equal": bool(np.array_equal(got[w], pos.reshape(-1)))}
        lines.append(line)
        print(json.dumps(line), flush=True)
  gate = {"gate": f"kernel >= {TIMES_FASTER}x scatter_add and <= "
                  f"{list(HOLD_MS.values())} ms at the two shapes",
          "times_faster": [round(x, 3) for x in faster],
          "met": bool(min(faster) >= TIMES_FASTER and all(held))}
  lines.append(gate)
  print(json.dumps(gate), flush=True)
  os.makedirs(os.path.dirname(OUT), exist_ok=True)
  with open(OUT, "a") as f:
    for line in lines:
      f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
  enable_compile_cache()
  main()
