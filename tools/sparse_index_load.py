"""The sparse-attention indexer's counters on a benchmark cell's traffic.

The benchmark times the unguarded step, whose outputs carry no metrics; this
runs the cell's model forward on one pool batch from the benchmark's own
weights and prints what the counters of
`layers/sparse_index.py::sparse_attention` say, per layer: the pairs the
selection kept (counted on the mask itself), the visible pairs (causal, same
document) and the queries with more visible keys than the indexer keeps.
Beside them the same three numbers counted from the batch's documents alone
(the family's ``document_counts``: a query keeps ``min(visible, topk)``),
which every layer must equal; and with ``--reference`` the pairs the
benchmark's plain reference selects (``lax.top_k`` scattered into a mask,
`families/keye_sparse.py::reference_logits(counters=True)`), layer by layer.
Counts, so any backend will do (at the cell's real size the CPU takes a few
minutes a batch; ``--layers 1`` shortens it); it exits non-zero where a count
differs:

  JAX_PLATFORMS=cpu python tools/sparse_index_load.py keye_dsa_train_1chip \
      [--seed N] [--reference]
"""

import argparse
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
  ap.add_argument("--reference", action="store_true",
                  help="count the plain reference's selection too")
  ap.add_argument("--root", default=specs.ROOT)
  args = ap.parse_args(argv)
  cell = specs.load_cell(args.cell, args.root)
  family = cell.family()
  if not hasattr(family, "document_counts"):
    raise SystemExit(f"{args.cell}: its model has no sparse-attention indexer")
  config = dict(cell.config)
  if args.layers:
    config["num_hidden_layers_here"] = args.layers
  spec = family.model_spec(config)
  parts = family.build_parts(config, cell.chips,
                             int(cell.traffic["global_batch"]))
  batch = traffic.make_batch(cell.traffic, spec.inputs, spec.n_numerical,
                             args.seed, args.batch,
                             traffic.family_labels(family, config))
  model = type(parts.model)(parts.model.config, with_counters=True)
  dense = {n: jnp.asarray(w) for n, w in
           reference.dense_weights(spec, args.seed).items()}
  table = spec.tables[0]
  ids, inverse = np.unique(batch.cats, return_inverse=True)
  rows = jnp.asarray(weights.rows_np(
      weights.leaf_key(args.seed, reference.table_name(0)), table.scale, ids,
      table.width)[inverse.reshape(batch.cats.shape)])
  numerical = jnp.asarray(batch.numerical)
  index = jax.tree_util.tree_map(np.asarray, jax.jit(
      lambda d, r, n: model.apply({"params": d}, n, None, emb_acts=[r])[
          "index"])(dense, rows, numerical))
  documents = family.document_counts(config, batch.numerical)
  layers = len(index["selected_pairs"])
  report = {
      "cell": args.cell, "seed": args.seed,
      "backend": jax.default_backend(),
      "positions_a_layer": int(batch.cats.size),
      "topk": int(config["sa_config"]["topk"]),
      **{name: index[name].tolist() for name in sorted(index)},
      "from_the_documents": documents,
      "selected_share_of_visible": round(
          documents["selected_pairs"] / documents["visible_pairs"], 4),
  }
  agree = all(index[name].tolist() == [n] * layers
              for name, n in documents.items())
  if args.reference:
    kept = jax.jit(lambda d, r, n: family.reference_logits(
        config, d, [r], n, counters=True)["selected_pairs"])(
            dense, rows, numerical)
    report["reference_selected_pairs"] = np.asarray(kept).tolist()
    agree = agree and report["reference_selected_pairs"] \
        == index["selected_pairs"].tolist()
  report["counts_agree"] = agree
  print(json.dumps(report))
  if not agree:
    raise SystemExit(f"{args.cell}: the counters, the documents' counts and "
                     "the reference's do not agree")
  return report


if __name__ == "__main__":
  main()
