"""One dense product of a language-model cell, alone on the chip: forward,
`dx` and `dw`, by what the compiler is handed.

  chiprun -- python tools/bench_mxu_dot.py [--shapes 8192x3840x11008,...]

For each `MxKxN` (tokens x in x out) it times, as device time of the XLA
module from a profiler trace of `--iters` calls:

  f32    float32 operands at default precision (what `x @ w` compiles to)
  bf16   bfloat16 operands rounded before the call: the product alone
  cast   float32 arrays rounded to bfloat16 inside the program, the casts
         left to the compiler (it fuses them into the product's operands)
  held   the same with an `optimization_barrier` between the casts and the
         product: the rounded copies are written out, then multiplied
  x16, w16 (forward only)   one operand rounded before the call, the other
         float32: which stream carries the difference

every result float32. Then one SwiGLU MLP layer of that shape
(`value_and_grad` over `x` and the three weights under `jax.checkpoint`,
as a decoder layer runs it) with `jnp.dot`, with `dot_rounded` as the
package has it (the activations' casts written out, the weights' left to the
compiler), and with every cast left to the compiler (`cast`), every cast
written out (`held`) or the weights' alone (`held_w`).
One JSON line a reading; `tflops` is `2 M K N` over the time, `peak_pct` that
over 197 TF/s. Two minutes of chip for the two default shapes (PERF.md, PR 39,
has their readings).
"""

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from benchmark import roofline  # noqa: E402
from distributed_embeddings_tpu.layers.dense import dot_rounded  # noqa: E402

# one v5e chip, bfloat16: the benchmark's own table of peaks
PEAK_TFLOPS = roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] * 1e-12
BF16, F32 = jnp.bfloat16, jnp.float32


def _dot(a, b, contract):
  return lax.dot_general(a, b, (contract, ((), ())),
                         preferred_element_type=F32)


PASSES = {   # name -> (contraction, which two of x [M,K], w [K,N], dy [M,N])
    "fwd": (((1,), (0,)), ("x", "w")),
    "dx": (((1,), (1,)), ("dy", "w")),
    "dw": (((0,), (0,)), ("x", "dy")),
}


def module_ms(trace_dir, name):
  """Median device time of the XLA module `jit_<name>`, first chip."""
  path = sorted(glob.glob(
      os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
  data = jax.profiler.ProfileData.from_file(path)
  durs = sorted(e.duration_ns
                for plane in data.planes if plane.name == "/device:TPU:0"
                for line in plane.lines if line.name == "XLA Modules"
                for e in line.events if e.name.startswith(f"jit_{name}("))
  if not durs:
    raise SystemExit(f"the trace under {trace_dir} holds no module {name}")
  return durs[len(durs) // 2] * 1e-6, len(durs)


def timed(name, fn, args, iters, flops, **said):
  fn.__name__ = name
  step = jax.jit(fn)
  jax.block_until_ready(step(*args))
  with tempfile.TemporaryDirectory() as tdir:
    with jax.profiler.trace(tdir):
      for _ in range(iters):
        jax.block_until_ready(step(*args))
    ms, n = module_ms(tdir, name)
  tflops = flops / ms * 1e-9
  line = {**said, "device_ms": round(ms, 4), "calls": n,
          "tflops": round(tflops, 2),
          "peak_pct": round(100 * tflops / PEAK_TFLOPS, 1)}
  print(json.dumps(line), flush=True)
  return line


def products(m, k, n, iters):
  key = jax.random.PRNGKey(0)
  arrays = {"x": jax.random.normal(key, (m, k), F32),
            "w": jax.random.normal(key, (k, n), F32) * 0.02,
            "dy": jax.random.normal(key, (m, n), F32)}
  rounded = {name: a.astype(BF16) for name, a in arrays.items()}
  shape, flops = f"{m}x{k}x{n}", 2.0 * m * k * n
  for pass_name, (contract, (a, b)) in PASSES.items():
    handed = {"f32": (arrays[a], arrays[b]),
              "bf16": (rounded[a], rounded[b])}
    if pass_name == "fwd":
      handed["x16"] = (rounded[a], arrays[b])
      handed["w16"] = (arrays[a], rounded[b])
    for what, args in handed.items():
      timed(f"{pass_name}_{what}",
            lambda p, q, contract=contract: _dot(p, q, contract),
            args, iters, flops, shape=shape, product=pass_name, handed=what)
    timed(f"{pass_name}_cast",
          lambda p, q, contract=contract: _dot(p.astype(BF16), q.astype(BF16),
                                               contract),
          (arrays[a], arrays[b]), iters, flops, shape=shape,
          product=pass_name, handed="cast")
    timed(f"{pass_name}_held",
          lambda p, q, contract=contract: _dot(
              *lax.optimization_barrier((p.astype(BF16), q.astype(BF16))),
              contract),
          (arrays[a], arrays[b]), iters, flops, shape=shape,
          product=pass_name, handed="held")


def mlp_layer(m, k, n, iters):
  """A decoder layer's SwiGLU MLP, forward + rebuilt + backward."""
  key = jax.random.PRNGKey(1)
  x = jax.random.normal(key, (m, k), F32)
  ws = tuple(jax.random.normal(key, s, F32) * 0.02
             for s in ((k, n), (k, n), (n, k)))

  def layer(dot):
    @jax.checkpoint
    def mlp(x, w_gate, w_up, w_down):
      return x + dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)
    return jax.grad(lambda x, *w: jnp.sum(jnp.square(mlp(x, *w))),
                    argnums=(0, 1, 2, 3))

  flops = 4 * 3 * 2.0 * m * k * n   # three matrices, four passes each
  def rounded(hold):
    """A product with a written-out backward on operands rounded inside the
    program; those named in `hold` are written out before they are used."""
    cast = lambda v, name: lax.optimization_barrier(v.astype(BF16)) \
        if name in hold else v.astype(BF16)

    @jax.custom_vjp
    def dot(a, b):
      return fwd(a, b)[0]

    def fwd(a, b):
      a16, b16 = cast(a, "x"), cast(b, "w")
      return _dot(a16, b16, ((a.ndim - 1,), (0,))), (a16, b16)

    def bwd(res, dy):
      a16, b16 = res
      dy16 = cast(dy, "dy")
      return (_dot(dy16, b16, ((1,), (1,))), _dot(a16, dy16, ((0,), (0,))))

    dot.defvjp(fwd, bwd)
    return dot

  for what, dot in (("f32", jnp.dot),
                    ("mxu_dot", lambda a, b: dot_rounded(BF16, a, b)),
                    ("cast", rounded(())),
                    ("held", rounded(("x", "w", "dy"))),
                    ("held_w", rounded(("w",)))):
    timed(f"mlp_{what}", layer(dot), (x, *ws), iters, flops,
          shape=f"{m}x{k}x{n}", product="mlp_layer_grad", handed=what)


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("--shapes", default="8192x3840x11008,8192x2048x8192")
  ap.add_argument("--iters", type=int, default=10)
  args = ap.parse_args(argv)
  from distributed_embeddings_tpu.parallel.mesh import require_tpu
  print("device:", json.dumps(require_tpu("bench_mxu_dot")), flush=True)
  for shape in args.shapes.split(","):
    m, k, n = (int(v) for v in shape.split("x"))
    products(m, k, n, args.iters)
    mlp_layer(m, k, n, args.iters)


if __name__ == "__main__":
  main()
