"""`apply_head_share` of a benchmark cell's traffic, per sparse class.

The benchmark times the unguarded step, whose outputs carry no metrics;
this counts, for one pool batch of a cell, what the guarded step's
``metrics['apply_head_share']`` would say: the share of each class's valid
occurrences that fall in a VMEM-resident head of the apply kernel
(`DistributedLookup.apply_head_counts` over the routed id streams, summed
over the mesh). A count, so any backend will do:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python tools/apply_head_share.py dlrm_train_4chip [--seed N]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmark import specs, traffic
from distributed_embeddings_tpu.compat import shard_map
from distributed_embeddings_tpu.parallel import create_mesh
from distributed_embeddings_tpu.parallel.lookup_engine import (
    DistributedLookup,
    class_param_name,
)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("cell")
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--batch", type=int, default=0, help="index in the pool")
  args = ap.parse_args()
  cell = specs.load_cell(args.cell)
  family, world = cell.family(), cell.chips
  spec = family.model_spec(cell.config)
  parts = family.build_parts(cell.config, world,
                             int(cell.traffic["global_batch"]))
  batch = traffic.make_batch(cell.traffic, spec.inputs, spec.n_numerical,
                             args.seed, args.batch)
  engine = DistributedLookup(parts.plan)
  layouts = engine.fused_layouts(parts.rule)

  def counts(cats):
    ids_all = engine.route_ids(parts.split_cats(cats))
    streams = {}
    for bk, ids in ids_all.items():
      name = class_param_name(*bk.class_key)
      if name in layouts:
        streams.setdefault(name, []).append(ids.reshape(-1))
    got = engine.apply_head_counts(
        layouts, {n: (jnp.concatenate(v), None) for n, v in streams.items()})
    if world > 1:
      got = {n: jax.lax.psum(c, engine.axis_name) for n, c in got.items()}
    return got

  if world > 1:
    mesh = create_mesh(world)
    counts = shard_map(counts, mesh=mesh, in_specs=P(engine.axis_name),
                       out_specs=P())
  got = jax.jit(counts)(jnp.asarray(batch.cats))
  out = {n: {"in_head": int(c[0]), "valid": int(c[1]),
             "apply_head_share": round(int(c[0]) / max(1, int(c[1])), 4)}
         for n, c in got.items()}
  print(json.dumps({"cell": args.cell, "seed": args.seed, "classes": out}))


if __name__ == "__main__":
  main()
