"""One expert layer's grouped matmuls, alone on the chip, by what the kernel
is handed.

  chiprun -- python tools/bench_ragged_dot.py [--shapes 32768x2048x768x16,...]
      [--forms f32,rounded,...] [--gmm 512x1024x768,...]

For each `ROWSxDxFxHELD` (the sorted stream's head x model width x expert
width x experts held here; the defaults are the four MoE cells') the group
sizes are what `layers/moe.py::groups_of(0, head, True)` hands the kernels at
one expected load: a quarter of the rows live, dealt over the held experts by
a seeded draw, the zeros past them in the last group. It times, as device time
of the XLA module from a profiler trace of `--iters` calls:

one grouped matmul alone (`product`: `fwd` `x w`, `dx` `dy w^T`, `dw` `x^T dy`
a group) on float32 operands (`f32`: what `lax.ragged_dot` compiles to today)
and on operands rounded to bfloat16 before the call (`bf16`), float32 out;

then one expert layer (`product` `layer_grad`): the three products of
`silu(x w_gate) * (x w_up)) w_down` under `jax.checkpoint` with their backward,
as a decoder layer runs them (3 forward, 3 rebuilt, 6 backward),
`value_and_grad` over
`x` and the three weights, in the forms

  f32      `lax.ragged_dot` on float32 operands, JAX's own transpose (the
           layer before PR 45, and still off the TPU)
  rounded  `layers/dense.py::grouped_dots_rounded`, as the package has it
  cast     the same backward with every cast left to the compiler
  left_w   the rows' and cotangents' casts written, the weights' left to the
           compiler
  held_w   every cast written behind an `optimization_barrier` (these three
           compile to one program around XLA's kernel, whose operands are
           materialised either way: PERF.md, PR 45, read them equal to 0.01 ms)
  gmm      (one a tiling of `--gmm`) the rounded operands through
           `jax.experimental.pallas.ops.tpu.megablox` (`gmm`, `tgmm` for `dw`)
           at the tiling `TMxTKxTN`, every cast written

One JSON line a reading: `tflops` is the products' `2 rows d f` each over the
time, `peak_pct` that over 197 TF/s, on the WHOLE head (every row multiplied,
the zeros too: not the benchmark's `moe_experts_*_mxu_pct`, which count the
expected live rows). The lines also go to `chiprun_out/bench_ragged_dot.jsonl`.
PERF.md, PR 45, has the readings (three minutes of chip for the defaults and
two tilings).
"""

import argparse
import functools
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import bench_mxu_dot  # noqa: E402
from distributed_embeddings_tpu.layers import dense  # noqa: E402
from distributed_embeddings_tpu.layers.moe import HEAD_LOADS  # noqa: E402

BF16, F32 = jnp.bfloat16, jnp.float32
OUT = os.path.join(os.path.dirname(__file__), "..", "chiprun_out",
                   "bench_ragged_dot.jsonl")


def group_sizes(rows, held, seed=0):
  """int32 `[held]`: a quarter of `rows` live, dealt over the held experts
  by a seeded draw; the rest, zeros in the layer, in the last group."""
  rng = np.random.default_rng(seed)
  sizes = rng.multinomial(rows // HEAD_LOADS, np.full(held, 1.0 / held))
  sizes[-1] += rows - sizes.sum()
  return jnp.asarray(sizes, jnp.int32)


def _grouped(x, w, sizes):
  return lax.ragged_dot(x, w, sizes, preferred_element_type=F32)


PRODUCTS = {   # name -> (the product, which two of x, w, dy it takes)
    "fwd": (_grouped, ("x", "w")),
    "dx": (lambda dy, w, sizes: _grouped(dy, jnp.swapaxes(w, 1, 2), sizes),
           ("dy", "w")),
    "dw": (lambda x, dy, sizes: lax.ragged_dot_general(
        x, dy, sizes, dense._GROUPED_DW, preferred_element_type=F32),
           ("x", "dy")),
}


def written_dots(hold, dot=_grouped, dx_dot=PRODUCTS["dx"][0],
                 dw_dot=PRODUCTS["dw"][0]):
  """`grouped_dots_rounded`'s shape with the casts of the operands named in
  `hold` ("x", "w", "dy") written out behind a barrier and the others left
  to the compiler; `dot`, `dx_dot`, `dw_dot` the three kernels."""
  cast = lambda v, name: lax.optimization_barrier(v.astype(BF16)) \
      if name in hold else v.astype(BF16)

  @jax.custom_vjp
  def dots(x, ws, sizes):
    return fwd(x, ws, sizes)[0]

  def fwd(x, ws, sizes):
    x16, ws16 = cast(x, "x"), tuple(cast(w, "w") for w in ws)
    return tuple(dot(x16, w16, sizes) for w16 in ws16), (x16, ws16, sizes)

  def bwd(kept, dys):
    x16, ws16, sizes = kept
    dys16 = tuple(cast(dy, "dy") for dy in dys)
    dx = sum(dx_dot(dy16, w16, sizes) for dy16, w16 in zip(dys16, ws16))
    return dx, tuple(dw_dot(x16, dy16, sizes) for dy16 in dys16), None

  dots.defvjp(fwd, bwd)
  return dots


def megablox_dots(tiling):
  # the package's own `gmm` name is its differentiable wrapper (bfloat16
  # gradients); the two kernels are the module's
  mb = importlib.import_module(
      "jax.experimental.pallas.ops.tpu.megablox.gmm")
  tm, tk, tn = tiling
  tile = lambda m, k, n: (min(tm, m), min(tk, k), min(tn, n))
  return written_dots(
      ("x", "w", "dy"),
      dot=lambda x, w, sizes: mb.gmm(
          x, w, sizes, F32, tile(x.shape[0], w.shape[1], w.shape[2])),
      dx_dot=lambda dy, w, sizes: mb.gmm(
          dy, w, sizes, F32, tile(dy.shape[0], w.shape[2], w.shape[1]),
          transpose_rhs=True),
      dw_dot=lambda x, dy, sizes: mb.tgmm(
          x.T, dy, sizes, F32, tile(x.shape[0], x.shape[1], dy.shape[1])))


def plain_dots(x, ws, sizes):
  return tuple(lax.ragged_dot(x, w, sizes) for w in ws)


def layer_forms(gmm_tilings=()):
  """name -> `dots(x, ws, sizes)`, the layer's grouped products."""
  forms = {
      "f32": plain_dots,
      "rounded": functools.partial(dense.grouped_dots_rounded, BF16),
      "cast": written_dots(()),
      "left_w": written_dots(("x", "dy")),
      "held_w": written_dots(("x", "w", "dy")),
  }
  for tiling in gmm_tilings:
    forms["gmm_" + "x".join(map(str, tiling))] = megablox_dots(tiling)
  return forms


def layer_grad(dots):
  """`value_and_grad` of one checkpointed expert layer over `x` and its
  weights. What reads `y` lies inside the checkpoint, as the rest of a decoder
  layer does, so the rebuilt forward runs all three products."""
  @jax.checkpoint
  def experts(x, w_gate, w_up, w_down, sizes):
    gate, up = dots(x, (w_gate, w_up), sizes)
    y, = dots(jax.nn.silu(gate) * up, (w_down,), sizes)
    return jnp.sum(jnp.square(y))
  return jax.value_and_grad(experts, argnums=(0, 1, 2, 3))


def layer_args(rows, d, f, held):
  key = jax.random.PRNGKey(1)
  draw = lambda shape, scale: jax.random.normal(key, shape, F32) * scale
  return (draw((rows, d), 1.0), draw((held, d, f), 0.02),
          draw((held, d, f), 0.02), draw((held, f, d), 0.02),
          group_sizes(rows, held))


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("--shapes", default="32768x2048x768x16,32768x2048x512x32,"
                  "32768x2048x1536x8")
  ap.add_argument("--forms", default="f32,rounded,cast,left_w,held_w")
  ap.add_argument("--gmm", default="",
                  help="tilings TMxTKxTN of the megablox form, comma-joined")
  ap.add_argument("--iters", type=int, default=10)
  args = ap.parse_args(argv)
  from distributed_embeddings_tpu.parallel.mesh import require_tpu
  print("device:", json.dumps(require_tpu("bench_ragged_dot")), flush=True)
  tilings = [tuple(int(v) for v in t.split("x"))
             for t in args.gmm.split(",") if t]
  forms = layer_forms(tilings)
  wanted = [f for f in args.forms.split(",") if f] \
      + [f for f in forms if f.startswith("gmm_")]
  os.makedirs(os.path.dirname(OUT), exist_ok=True)
  with open(OUT, "a") as out:
    def timed(name, fn, fn_args, flops, **said):
      line = bench_mxu_dot.timed(name, fn, fn_args, args.iters, flops, **said)
      out.write(json.dumps(line) + "\n")
      out.flush()

    for shape in args.shapes.split(","):
      rows, d, f, held = (int(v) for v in shape.split("x"))
      flops = 2.0 * rows * d * f
      key = jax.random.PRNGKey(0)
      arrays = {"x": jax.random.normal(key, (rows, d), F32),
                "w": jax.random.normal(key, (held, d, f), F32) * 0.02,
                "dy": jax.random.normal(key, (rows, f), F32)}
      sizes = group_sizes(rows, held)
      for product, (fn, (a, b)) in PRODUCTS.items():
        for handed, dtype in (("f32", F32), ("bf16", BF16)):
          # a function of its own a reading: `timed` names it after the reading
          timed(f"{product}_{handed}", lambda p, q, s, fn=fn: fn(p, q, s),
                (arrays[a].astype(dtype), arrays[b].astype(dtype), sizes),
                flops, shape=shape, product=product, handed=handed)
      del arrays
      for form in wanted:
        timed(f"layer_{form}", layer_grad(forms[form]),
              layer_args(rows, d, f, held),
              12 * flops, shape=shape, product="layer_grad", handed=form)


if __name__ == "__main__":
  main()
