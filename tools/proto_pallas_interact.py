"""The interaction kernels alone: ns a sample on the chip, instructions a
sample from Mosaic's dump.

Times what the DLRM cells run: the production per-part kernels
`de_interact_parts_fwd` and `de_interact_parts_bwd`, entered the way the
step enters them (`models/dlrm.py::_pair_fwd/_pair_bwd`, so the selection
constants are the step's), forward and backward apart, at the cells'
shapes (27 bfloat16 parts of width 128; 65,536 samples a chip on one chip,
16,384 on four). The time is the kernel's own device time, read from a
profiler trace of `--iters` calls; the host clock over the same calls is
printed beside it.

    chiprun -- python tools/proto_pallas_interact.py [--batch 65536,16384]

With `--llo DIR` it instead compiles the two kernels with Mosaic dumping
into DIR and counts the instructions of one grid step in each kernel's
`*-post-finalize-llo.txt`, a sample. That needs no chip: off the TPU the
kernels are compiled for a described `v5e:2x2`
(`JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1`). A count is not a time.

    python tools/proto_pallas_interact.py --llo /root/scratch/llo

`--fwd_block` / `--bwd_block` run the kernels at another block of samples
a grid step than `ops/pallas_interact.py`'s (a sweep; the step's own
blocks are the module's constants).
"""

import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# the rows of PERF.md's table (section 6, PR 34), in its order
COUNTED = ("vmatmul", "vlatch", "vrot.slane", "slane.shuffle", "vselect",
           "vpack", "vunpack", "vector_store_masked",
           "vector_store_slane_stride", "vector_load_slane_stride")
NOT_INSTRUCTIONS = ("constant", "vbitcast")
# a statement's op: `%5 = llo.vpack ...`, `llo.vector_store ...` or the generic
# form `"llo.vmatmul"(...)`; an attribute (`#llo.vpack_format<...>`) is none
_LLO_OP = re.compile(r'^\s*(?:%[^=]*=\s*)?"?llo\.([\w.]+)')


def count_llo(path):
  """Instructions of one grid step by name, out of a `post-finalize-llo`
  dump: every `llo.<op>` statement but constants and bitcasts."""
  counts = collections.Counter()
  with open(path) as fh:
    for line in fh:
      m = _LLO_OP.match(line)
      if m and m.group(1) not in NOT_INSTRUCTIONS:
        counts[m.group(1)] += 1
  return counts


def _args():
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--batch", default="65536,16384",
                  help="comma-separated samples a chip")
  ap.add_argument("--parts", type=int, default=27)
  ap.add_argument("--width", type=int, default=128)
  ap.add_argument("--iters", type=int, default=20)
  ap.add_argument("--fwd_block", type=int, default=None)
  ap.add_argument("--bwd_block", type=int, default=None)
  ap.add_argument("--llo", metavar="DIR", default=None,
                  help="dump Mosaic's passes into DIR and count, not time")
  return ap.parse_args()


ARGS = _args()
if ARGS.llo:  # libtpu reads this once, when it is loaded
  os.makedirs(ARGS.llo, exist_ok=True)
  os.environ["LIBTPU_INIT_ARGS"] = (
      os.environ.get("LIBTPU_INIT_ARGS", "")
      + f" --xla_mosaic_dump_to={os.path.abspath(ARGS.llo)}").strip()
  os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_embeddings_tpu.models import dlrm  # noqa: E402
from distributed_embeddings_tpu.ops import pallas_interact as pi  # noqa: E402

K = -1  # no self-interaction, as the cells run it


def kernels(f, fwd_block, bwd_block):
  """(fwd, bwd) as the step calls them, or at the blocks asked for."""
  if fwd_block is None and bwd_block is None:
    return (lambda parts: dlrm._pair_fwd(parts, f, K)[0],
            lambda parts, da: dlrm._pair_bwd(f, K, parts, da)[0])
  m_np, _ = dlrm._tril_select_np(f, K)
  return (lambda parts: pi.interact_parts_fwd(
              parts, m_np, block=fwd_block or pi.FWD_BLOCK),
          lambda parts, da: pi.interact_parts_bwd(
              da, parts, m_np, block=bwd_block or pi.BWD_BLOCK))


def device_ms(trace_dir, name):
  """Per call: summed device time of the ops named `name*`, first chip."""
  path = sorted(glob.glob(
      os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
  data = jax.profiler.ProfileData.from_file(path)
  durs = [e.duration_ns
          for plane in data.planes if plane.name == "/device:TPU:0"
          for line in plane.lines if line.name == "XLA Ops"
          for e in line.events if e.name.lstrip("%").startswith(name)]
  if not durs:
    raise SystemExit(f"the trace under {trace_dir} holds no op named {name}*")
  return sum(durs) * 1e-6 / len(durs), len(durs)


def time_one(block, name, fn, args, b, iters):
  step = jax.jit(fn)
  jax.block_until_ready(step(*args))
  with tempfile.TemporaryDirectory() as tdir:
    with jax.profiler.trace(tdir):
      t0 = time.perf_counter()
      for _ in range(iters):
        out = step(*args)
      jax.block_until_ready(out)
      host_ms = (time.perf_counter() - t0) * 1e3 / iters
    ms, n = device_ms(tdir, name)
  print(json.dumps({"kernel": name, "batch": b, "block": block, "calls": n,
                    "device_ms": ms, "ns_per_sample": ms * 1e6 / b,
                    "host_clock_ms": host_ms}), flush=True)


def run_timed(args):
  from distributed_embeddings_tpu.parallel.mesh import require_tpu
  print("device:", json.dumps(require_tpu("proto_pallas_interact")),
        flush=True)
  f, d = args.parts, args.width
  fwd, bwd = kernels(f, args.fwd_block, args.bwd_block)
  rng = np.random.default_rng(0)
  for b in (int(x) for x in args.batch.split(",")):
    parts = tuple(jnp.asarray(rng.standard_normal((b, d)) * 0.3, jnp.bfloat16)
                  for _ in range(f))
    da = jnp.asarray(rng.standard_normal((b, f * (f + K) // 2)),
                     jnp.float32)
    time_one(args.fwd_block or pi.FWD_BLOCK, pi.PARTS_FWD_NAME, fwd,
             (parts,), b, args.iters)
    time_one(args.bwd_block or pi.BWD_BLOCK, pi.PARTS_BWD_NAME, bwd,
             (parts, da), b, args.iters)


def run_counted(args):
  f, d = args.parts, args.width
  b = int(args.batch.split(",")[0])
  fwd, bwd = kernels(f, args.fwd_block, args.bwd_block)
  if jax.default_backend() == "tpu":
    placed = {}
  else:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    placed = {"sharding": SingleDeviceSharding(topo.devices[0])}
  parts = tuple(jax.ShapeDtypeStruct((b, d), jnp.bfloat16, **placed)
                for _ in range(f))
  da = jax.ShapeDtypeStruct((b, f * (f + K) // 2), jnp.float32, **placed)
  jax.jit(fwd).lower(parts).compile()
  jax.jit(bwd).lower(parts, da).compile()
  for name, block in ((pi.PARTS_FWD_NAME, args.fwd_block or pi.FWD_BLOCK),
                      (pi.PARTS_BWD_NAME, args.bwd_block or pi.BWD_BLOCK)):
    dumps = sorted(glob.glob(
        os.path.join(args.llo, f"*-{name}-post-finalize-llo.txt")))
    if not dumps:
      raise SystemExit(f"Mosaic left no post-finalize-llo dump of {name} "
                       f"under {args.llo}")
    counts = count_llo(dumps[-1])
    line = {"kernel": name, "samples_a_grid_step": block}
    line.update({op: counts.get(op, 0) for op in COUNTED})
    line["instructions_per_sample"] = sum(counts.values()) / block
    print(json.dumps(line), flush=True)


def main():
  f = ARGS.parts
  print(f"parts {f} of width {ARGS.width}: samples_per_tile "
        f"{pi.samples_per_tile(f)}, rows_per_sample {pi.rows_per_sample(f)}",
        flush=True)
  if ARGS.llo:
    run_counted(ARGS)
  else:
    run_timed(ARGS)


if __name__ == "__main__":
  main()
