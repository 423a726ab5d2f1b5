"""One expert layer's grouped matmuls, alone on the chip: XLA's ragged-dot
kernel and the kernels of `ops/pallas_grouped_matmul.py` side by side.

  chiprun -- python tools/bench_ragged_dot.py [--cells glm,solar,...]
      [--shapes 32768x2048x768x16,...] [--forms f32,xla,kernel] [--sweep]
      [--gmm 512x1024x768,...]
  python tools/bench_ragged_dot.py --rule       (no chip: the tiles chosen)

A shape is `ROWSxDxFxHELD`: the sorted stream's head x model width x expert
width x experts held here. `--cells` names the six MoE cells' (`SHAPES`: Solar,
SDAR, Keye, Laguna, LFM2, GLM; SDAR's and Keye's are one shape and are timed
once). The group sizes are what `layers/moe.py::groups_of(0, head, True)` hands
the kernels at one expected load: a quarter of the rows live, dealt over the
held experts by a seeded draw, the zeros past them in the last group. For each
shape it prints the tiles `grouped_matmul.tiles` chose for each of the layer's
products, then times, as device time of the XLA module from a profiler trace of
`--iters` calls:

each of the layer's six grouped products alone (`product`: `fwd` `x w_gate`,
`fwd_down` `act w_down`, `dx` `dy w_gate^T`, `dx_down` `dy w_down^T`, `dw` `x^T
dy` a group, `dw_down` `act^T dy`), float32 out, `handed` `xla`: bfloat16
operands through `lax.ragged_dot` / `ragged_dot_general` (the parent's kernel),
and `kernel`: the same operands through `ops/pallas_grouped_matmul.py` under
its own tiles; with `--sweep` also under the other tilings of `sweep_tiles`
(where the rule's choice is not the fastest, the rule is what to repair);

then one expert layer (`product` `layer_grad`): the three products of
`silu(x w_gate) * (x w_up)) w_down` under `jax.checkpoint` with their backward,
as a decoder layer runs them (3 forward, 3 rebuilt, 6 backward),
`value_and_grad` over `x` and the three weights, in the forms

  f32      `lax.ragged_dot` on float32 operands, JAX's own transpose (the
           layer before PR 45, and still off the TPU)
  xla      `layers/dense.py::grouped_dots_rounded` with `grouped_kernel`
           answering `None`: XLA's kernel on the rounded operands (the parent)
  kernel   `grouped_dots_rounded` as the package has it
  gmm      (one a tiling of `--gmm`) the rounded operands through
           `jax.experimental.pallas.ops.tpu.megablox` (`gmm`, `tgmm` for `dw`)
           at the tiling `TMxTKxTN`, every cast written

and with each `layer_grad` line the two trace-time counters of
`layers/dense.py` (`counted`: grouped products that went to the kernels and to
`lax.ragged_dot` while that form was traced).

One JSON line a reading: `tflops` is the products' `2 rows d f` each over the
time, `peak_pct` that over 197 TF/s, on the WHOLE head (every row is in a
group and is multiplied, the zeros too: not the benchmark's
`moe_experts_*_mxu_pct`, which count the expected live rows). The lines also
go to `chiprun_out/bench_ragged_dot.jsonl`. PERF.md, PR 53, has the readings
(four minutes of chip for the defaults).
"""

import argparse
import collections
import contextlib
import glob
import importlib
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import bench_mxu_dot  # noqa: E402
from distributed_embeddings_tpu.layers import dense  # noqa: E402
from distributed_embeddings_tpu.ops import (  # noqa: E402
    pallas_grouped_matmul as grouped_matmul,
)
from distributed_embeddings_tpu.telemetry import registry  # noqa: E402
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


# the six MoE cells' expert layers: rows of the head, model width, expert
# width, experts held
SHAPES = {
    "solar": (6560, 4096, 1280, 8),
    "sdar": (32768, 2048, 768, 16),
    "keye": (32768, 2048, 768, 16),
    "laguna": (32768, 2048, 512, 32),
    "lfm2": (32768, 2048, 1536, 8),
    "glm": (16384, 2048, 1536, 8),
}


def _grouped(x, w, sizes):
  return lax.ragged_dot(x, w, sizes, preferred_element_type=F32)


def _grouped_dx(dy, w, sizes):
  return _grouped(dy, jnp.swapaxes(w, 1, 2), sizes)


def _grouped_dw(x, dy, sizes):
  return lax.ragged_dot_general(x, dy, sizes, dense._GROUPED_DW,
                                preferred_element_type=F32)


def _kernel_dx(dy, w, sizes, t=None):
  return grouped_matmul.grouped_dot(dy, w, sizes, transposed=True, t=t)


# name -> (XLA's product, the kernels', its operands' shapes by (rows, d, f,
# held), (m, k, n) as `grouped_matmul.tiles` counts it)
PRODUCTS = {
    "fwd": (_grouped, grouped_matmul.grouped_dot,
            lambda r, d, f, g: ((r, d), (g, d, f)), lambda r, d, f: (r, d, f)),
    "fwd_down": (_grouped, grouped_matmul.grouped_dot,
                 lambda r, d, f, g: ((r, f), (g, f, d)),
                 lambda r, d, f: (r, f, d)),
    "dx": (_grouped_dx, _kernel_dx,
           lambda r, d, f, g: ((r, f), (g, d, f)), lambda r, d, f: (r, f, d)),
    "dx_down": (_grouped_dx, _kernel_dx,
                lambda r, d, f, g: ((r, d), (g, f, d)),
                lambda r, d, f: (r, d, f)),
    # `dx` with XLA writing `w^T` out first (not a default: the finding)
    "dx_written": (_grouped_dx, lambda dy, w, sizes, t=None:
                   grouped_matmul.grouped_dot(dy, jnp.swapaxes(w, 1, 2),
                                              sizes, t=t),
                   lambda r, d, f, g: ((r, f), (g, d, f)),
                   lambda r, d, f: (r, f, d)),
    "dw": (_grouped_dw, grouped_matmul.grouped_dw,
           lambda r, d, f, g: ((r, d), (r, f)), lambda r, d, f: (r, d, f)),
    "dw_down": (_grouped_dw, grouped_matmul.grouped_dw,
                lambda r, d, f, g: ((r, f), (r, d)),
                lambda r, d, f: (r, f, d)),
}

# `--sweep`: (tm, sub) of the row walk, the column blocks as cuts of (n, dw's
# k) and the columns one product in the kernel forms, each varied alone round
# the rule's choice
SWEEP_ROWS = ((512, 256), (256, 128), (1024, 128))
SWEEP_COLUMNS = ((2, 1), (1, 2))
SWEEP_CHUNKS = (128, 256, 512, 1 << 20)


def sweep_tiles(m, k, n, held, dw):
  """The rule's choice first, then the tilings worth a reading beside it."""
  rule = grouped_matmul.tiles(m, k, n, held, dw)
  lanes = grouped_matmul.NUM_LANES
  tried = [rule]
  tried += [rule._replace(tc=min(tc, rule.tn)) for tc in SWEEP_CHUNKS
            if rule.tn % min(tc, rule.tn) == 0]
  # `grouped_dw` masks a cut tile whole: `sub` is not its to read
  tried += [rule._replace(tm=tm, sub=rule.sub if dw else sub)
            for tm, sub in SWEEP_ROWS]
  tried += [rule._replace(tn=n // n_cut, tk=k // k_cut, tc=max(
      c for c in grouped_matmul._lane_divisors(n // n_cut) if c <= rule.tc))
            for n_cut, k_cut in SWEEP_COLUMNS[:2 if dw else 1]
            if not (n % (n_cut * lanes) or k % (k_cut * lanes))]
  return [t for t in dict.fromkeys(tried)
          if grouped_matmul.block_bytes(t, dw) <= 96 << 20]


def rule_lines(rows, d, f, held):
  """One line a product of the layer: what forms it and under which tiles."""
  for name, (_, _, _, dims) in PRODUCTS.items():
    if name == "dx_written":
      continue
    m, k, n = dims(rows, d, f)
    t = grouped_matmul.tiles(m, k, n, held, name.startswith("dw"))
    yield f"  {name:9s} {m} x {k} x {n}: " + (
        "lax.ragged_dot (no tiling fits)" if t is None else
        f"rows {t.tm} (cut: {t.sub}), columns {t.tn} ({t.tc} a product)"
        + (f", x's columns {t.tk}" if name.startswith("dw") else ""))


def longest_ops(name, fn, fn_args, top, calls=3):
  """The `top` longest kinds of device op of `fn` (an instruction's name less
  its number: `de_grouped_dot`, `convert_element_type`, ...), ms a call
  (their sum over a trace of `calls` calls), and the sum of all of them."""
  fn.__name__ = name
  step = jax.jit(fn)
  jax.block_until_ready(step(*fn_args))
  spent = collections.Counter()
  with tempfile.TemporaryDirectory() as tdir:
    with jax.profiler.trace(tdir):
      for _ in range(calls):
        jax.block_until_ready(step(*fn_args))
    path = sorted(glob.glob(os.path.join(
        tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
      if plane.name == "/device:TPU:0":
        for line in plane.lines:
          if line.name == "XLA Ops":
            for e in line.events:
              kind = re.sub(r"\.\d+$", "", e.name.split(" = ")[0].lstrip("%"))
              spent[kind] += e.duration_ns * 1e-6 / calls
  return {"all_ops_ms": round(sum(spent.values()), 3),
          "longest": {op: round(ms, 3) for op, ms in spent.most_common(top)}}


def against_xla(product, handed, rows, held):
  """The kernels' product against XLA's on the chip, as shares of XLA's
  largest value: with every row in a group, and with the live rows alone in
  groups (a piece of the tail), where the rows no group owns must come back
  as zeros (`unowned_nonzero`: how many values there do not)."""
  xla, kernel, _, _ = PRODUCTS[product]
  said = {}
  live = group_sizes(rows, held).at[-1].add(rows // HEAD_LOADS - rows)
  for name, sizes in (("whole", group_sizes(rows, held)), ("live", live)):
    owned = int(sizes.sum())
    want, got = xla(*handed, sizes), kernel(*handed, sizes)
    if not product.startswith("dw"):
      said["unowned_nonzero"] = int(jnp.sum(got[owned:] != 0))
      want, got = want[:owned], got[:owned]
    said[f"gap_{name}"] = float(jnp.max(jnp.abs(got - want))
                                / jnp.max(jnp.abs(want)))
  return said


def counted():
  """The trace-time counters of `layers/dense.py`."""
  return {"kernel": registry.counter(dense.KERNEL_PRODUCTS).value,
          "ragged_dot": registry.counter(dense.XLA_PRODUCTS).value}


def written_dots(hold, dot=_grouped, dx_dot=_grouped_dx, dw_dot=_grouped_dw):
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


def rounded_dots(x, ws, sizes):
  return dense.grouped_dots_rounded(BF16, x, ws, sizes)


@contextlib.contextmanager
def kernels_refused(refused=True):
  """`dense.grouped_kernel` answering `None` whatever the shape, for as long
  as a form is traced (its backward is traced after its forward returns)."""
  package = dense.grouped_kernel
  if refused:
    dense.grouped_kernel = lambda *_: None
  try:
    yield
  finally:
    dense.grouped_kernel = package


def layer_forms(gmm_tilings=()):
  """name -> `dots(x, ws, sizes)`, the layer's grouped products (`xla`:
  under `kernels_refused`)."""
  forms = {"f32": plain_dots, "xla": rounded_dots, "kernel": rounded_dots}
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
  ap.add_argument("--cells", default="solar,sdar,keye,laguna,lfm2,glm",
                  help="the cells whose expert layer to time, by name")
  ap.add_argument("--shapes", default="",
                  help="further shapes ROWSxDxFxHELD, comma-joined")
  ap.add_argument("--forms", default="f32,xla,kernel")
  ap.add_argument("--gmm", default="",
                  help="tilings TMxTKxTN of the megablox form, comma-joined")
  ap.add_argument("--products", default=",".join(
      p for p in PRODUCTS if p != "dx_written"))
  ap.add_argument("--sweep", action="store_true",
                  help="time each product alone under the tilings of "
                  "`sweep_tiles` too, not the rule's choice alone")
  ap.add_argument("--rule", action="store_true",
                  help="print the rule's choice a shape and stop (no chip)")
  ap.add_argument("--ops", type=int, default=0,
                  help="with each layer form, its N longest device ops")
  ap.add_argument("--iters", type=int, default=10)
  args = ap.parse_args(argv)
  named = {}
  for cell in filter(None, args.cells.split(",")):
    named.setdefault(SHAPES[cell], []).append(cell)
  for shape in filter(None, args.shapes.split(",")):
    named.setdefault(tuple(int(v) for v in shape.split("x")), []).append(shape)
  for shape, cells in named.items():
    print("+".join(cells), "x".join(map(str, shape)))
    print("\n".join(rule_lines(*shape)), flush=True)
  if args.rule:
    return
  from distributed_embeddings_tpu.parallel.mesh import require_tpu
  print("device:", json.dumps(require_tpu("bench_ragged_dot")), flush=True)
  tilings = [tuple(int(v) for v in t.split("x"))
             for t in args.gmm.split(",") if t]
  forms = layer_forms(tilings)
  wanted = [f for f in args.forms.split(",") if f] \
      + [f for f in forms if f.startswith("gmm_")]
  os.makedirs(os.path.dirname(OUT), exist_ok=True)
  with open(OUT, "a") as out:
    def say(line):
      print(json.dumps(line), flush=True)
      out.write(json.dumps(line) + "\n")
      out.flush()

    def timed(name, fn, fn_args, flops, **said):
      try:
        line = bench_mxu_dot.timed(name, fn, fn_args, args.iters, flops,
                                   **said)
      except Exception as e:  # pylint: disable=broad-except
        line = {**said, "refused": str(e).splitlines()[0][:200]}
        print(json.dumps(line), flush=True)
      out.write(json.dumps(line) + "\n")
      out.flush()

    for (rows, d, f, held), cells in named.items():
      said = {"cells": "+".join(cells), "shape": f"{rows}x{d}x{f}x{held}"}
      flops = 2.0 * rows * d * f
      sizes = group_sizes(rows, held)
      for product in filter(None, args.products.split(",")):
        xla, kernel, operands, dims = PRODUCTS[product]
        key = jax.random.PRNGKey(0)
        handed = tuple(
            (jax.random.normal(key, shape, F32)
             * (0.02 if len(shape) == 3 else 1.0)).astype(BF16)
            for shape in operands(rows, d, f, held))
        timed(f"{product}_xla", lambda p, q, s, fn=xla: fn(p, q, s),
              (*handed, sizes), flops, **said, product=product, handed="xla")
        m, k, n = dims(rows, d, f)
        dw = product.startswith("dw")
        tried = sweep_tiles(m, k, n, held, dw) if args.sweep \
            else [grouped_matmul.tiles(m, k, n, held, dw)]
        if tried[0] is not None:
          say({**said, "product": product,
               **against_xla(product, handed, rows, held)})
        for i, t in enumerate(tried):
          if t is None:
            continue
          timed(f"{product}_kernel_{i}",
                lambda p, q, s, fn=kernel, t=t: fn(p, q, s, t=t),
                (*handed, sizes), flops, **said, product=product,
                handed="kernel", tiles="x".join(map(str, t)), rule=i == 0)
        del handed
      for form in wanted:
        before = counted()
        with kernels_refused(form == "xla"):
          timed(f"layer_{form}", layer_grad(forms[form]),
                layer_args(rows, d, f, held), 12 * flops, **said,
                product="layer_grad", handed=form)
          say({**said, "handed": form, "counted": {
              name: value - before[name]
              for name, value in counted().items()}})
          if args.ops:
            say({**said, "handed": form, **longest_ops(
                f"ops_{form}", layer_grad(forms[form]),
                layer_args(rows, d, f, held), args.ops)})


if __name__ == "__main__":
  main()
