"""The expert layer's counters on a benchmark cell's traffic.

The benchmark times the unguarded step, whose outputs carry no metrics (and
a summed rule has no guarded step); this runs the cell's model forward on one
pool batch from the benchmark's own weights and prints what the counters of
`layers/moe.py::moe_share` say, per layer: the assignments that fell on the
experts held here, the largest held expert's load over the mean, the
assignments a capacity would have dropped (assigned less what the grouped
matmuls were handed: must read 0), the load over the expected count (what
`layers/moe.py::HEAD_LOADS` is held against) and, for a block-diffusion
model, the share of positions the noise masked. Counts, so any backend will
do, and attention goes the XLA way whatever the cell names (at the cell's
real size the CPU takes a minute or two a batch; ``--layers 1`` shortens it):

  JAX_PLATFORMS=cpu python tools/moe_load.py sdar_moe_train_1chip [--seed N]
  JAX_PLATFORMS=cpu python tools/moe_load.py laguna_moe_train_1chip --seed N
  JAX_PLATFORMS=cpu python tools/moe_load.py lfm2_moe_train_1chip --seed N
  JAX_PLATFORMS=cpu python tools/moe_load.py glm_mla_train_1chip --seed N
  JAX_PLATFORMS=cpu python tools/moe_load.py solar_kda_train_1chip --seed N

(the last but one: the trunk's four expert layers, then the prediction
module's; the last: 320 router outputs of which 8 are held, so the expected
load is 1,638.4 assignments a layer, about 205 an expert, and the head
holds four of them, 6,560 rows).
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, specs, traffic, weights


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("cell")
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--batch", type=int, default=0, help="index in the pool")
  ap.add_argument("--layers", type=int, default=0,
                  help="only the first N layers (0: all)")
  ap.add_argument("--root", default=specs.ROOT)
  args = ap.parse_args(argv)
  cell = specs.load_cell(args.cell, args.root)
  family = cell.family()
  spec = family.model_spec(cell.config)
  parts = family.build_parts(dict(cell.config, attention="xla"), cell.chips,
                             int(cell.traffic["global_batch"]))
  if not hasattr(parts.model, "with_counters"):
    raise SystemExit(f"{args.cell}: its model has no expert layer")
  batch = traffic.make_batch(cell.traffic, spec.inputs, spec.n_numerical,
                             args.seed, args.batch,
                             traffic.family_labels(family, cell.config))
  config = parts.model.config
  # a model names the layers that run here by count or, where a layer's
  # kinds follow from its published number, by number
  by_number = hasattr(config, "layers_here")
  if args.layers:
    config = dataclasses.replace(
        config, **({"layers_here": config.layers_here[:args.layers]}
                   if by_number else {"num_hidden_layers": args.layers}))
  n_layers = len(config.layers_here) if by_number \
      else config.num_hidden_layers
  model = type(parts.model)(config, with_counters=True)
  dense = {n: jnp.asarray(w) for n, w in
           reference.dense_weights(spec, args.seed).items()
           if not n.startswith("layer_") or int(n.split("_")[1]) < n_layers}
  table = spec.tables[0]
  ids, inverse = np.unique(batch.cats, return_inverse=True)
  rows = weights.rows_np(
      weights.leaf_key(args.seed, reference.table_name(0)), table.scale, ids,
      table.width)[inverse.reshape(batch.cats.shape)]
  out = jax.jit(lambda d, r, n: {
      k: v for k, v in model.apply({"params": d}, n, None,
                                   emb_acts=[r]).items()
      if k in ("moe", "masked")})(dense, jnp.asarray(rows),
                                  jnp.asarray(batch.numerical))
  moe = jax.tree_util.tree_map(np.asarray, out["moe"])
  # a block-diffusion model runs the noisy copy beside the clean one
  positions = int(batch.cats.size) * (2 if "masked" in out else 1)
  share = config.share
  expected = positions * share.top_k * share.held[1] / share.num_experts
  report = {
      "cell": args.cell, "seed": args.seed,
      "backend": jax.default_backend(),
      "positions_a_layer": positions,
      "experts_held": list(config.experts_held),
      "assignments_on_held_experts": moe["assignments"].tolist(),
      "load_over_expected": [round(float(a) / expected, 3)
                             for a in moe["assignments"]],
      "largest_load_over_mean": [
          float(l.max() / max(l.mean(), 1e-30)) for l in moe["loads"]],
      "dropped": (moe["assignments"] - moe["computed"]).tolist(),
  }
  if "masked" in out:
    report["masked_share"] = float(np.mean(np.asarray(out["masked"])))
  if "moved" in moe:
    report["moved_share"] = [
        round(float(m) / (positions * share.top_k), 4)
        for m in moe["moved"]]
  print(json.dumps(report))
  return report


if __name__ == "__main__":
  main()
