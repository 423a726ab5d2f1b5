"""Ground-truth Tiny step attribution by ablating the REAL train step.

Synthetic decompositions (profile_tiny_parts/buckets) have not matched the
end-to-end step: isolated micro-costs fuse differently in context. This
tool times the real fused train step with pieces surgically removed:

  full          : the real step (baseline, ~matches bench_synthetic)
  no_apply      : apply_sparse skipped (fused returned unchanged)
  no_model      : loss = mean(z_sparse) directly (no dense path/MLP/interact)
  no_gather     : z_sparse/residual aux replaced by zeros (routing + apply
                  with dummy deltas; gather cost removed)

Usage: PYTHONPATH=/root/repo python -u tools/profile_tiny_ablate.py [model] [batch]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_embeddings_tpu.layers.planner import DistEmbeddingStrategy
from distributed_embeddings_tpu.models import (
    SYNTHETIC_MODELS,
    SyntheticModel,
    bce_loss,
    expand_tables,
    generate_batch,
)
from distributed_embeddings_tpu.ops.packed_table import adagrad_rule
from distributed_embeddings_tpu.parallel.lookup_engine import DistributedLookup
from distributed_embeddings_tpu.training import init_sparse_state_direct

MODEL = sys.argv[1] if len(sys.argv) > 1 else "tiny"
BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 65536
K = 5


def main():
  cfg = SYNTHETIC_MODELS[MODEL]
  tables, tmap, hotness = expand_tables(cfg)
  model = SyntheticModel(config=cfg, world_size=1)
  plan = DistEmbeddingStrategy(tables, 1, "basic", input_table_map=tmap,
                               dense_row_threshold=model.dense_row_threshold,
                               input_hotness=hotness, batch_hint=BATCH)
  engine = DistributedLookup(plan)
  rule = adagrad_rule(0.01)
  layouts = engine.fused_layouts(rule)
  numerical, cats_np, labels = generate_batch(cfg, BATCH, alpha=1.05, seed=0)
  cats_np = [np.minimum(c, tables[t].input_dim - 1).astype(np.int32)
             for c, t in zip(cats_np, tmap)]
  cats = [jnp.asarray(c if h > 1 else c[:, 0])
          for c, h in zip(cats_np, hotness)]
  hotness_of = lambda i: hotness[i]  # noqa: E731
  numerical = jnp.asarray(numerical)
  labels = jnp.asarray(labels)

  dummy_acts = [jnp.zeros((2, tables[t].output_dim), jnp.float32)
                for t in tmap]
  dense_params = model.init(jax.random.PRNGKey(0), numerical[:2],
                            [c[:2] for c in cats], emb_acts=dummy_acts
                            )["params"]
  state = init_sparse_state_direct(plan, rule, dense_params,
                                   optax.adagrad(0.01), jax.random.PRNGKey(1))
  state = {"dense": state["dense"], "emb_dense": state["emb_dense"],
           "fused": state["fused"], "step": jnp.zeros((), jnp.int32)}
  first_fused = sorted(state["fused"])[0]
  float(state["fused"][first_fused][0, 0])

  def make_step(kind):
    def local(st, num, cats_, labels_):
      b = num.shape[0]
      ids_all = engine.route_ids(cats_, hotness_of)
      z_sparse, residuals = engine.lookup_sparse_fused(
          st["fused"], layouts, ids_all)
      if kind == "no_gather":
        z_sparse = {k: jnp.zeros_like(v) for k, v in z_sparse.items()}
        residuals.aux_rows = {k: jnp.zeros_like(v)
                              for k, v in residuals.aux_rows.items()}

      if kind == "no_model":
        def loss_with(z_sp):
          return sum(jnp.sum(jnp.tanh(zb * 1e-3)) for zb in z_sp.values()) \
              / (b * 1000.0)
        loss, d_z = jax.value_and_grad(loss_with)(z_sparse)
        dense, emb_dense = st["dense"], st["emb_dense"]
      else:
        def loss_with(dense_p, emb_dense, z_sp):
          acts = engine.finish_forward(z_sp, emb_dense, ids_all, b,
                                       hotness_of)
          logits = model.apply({"params": dense_p}, num, cats_,
                               emb_acts=acts)
          return bce_loss(logits, labels_)

        loss, (d_dense, d_emb_dense, d_z) = jax.value_and_grad(
            loss_with, argnums=(0, 1, 2))(st["dense"], st["emb_dense"],
                                          z_sparse)
        dense = jax.tree_util.tree_map(lambda p, g: p - 0.01 * g,
                                       st["dense"], d_dense)
        emb_dense = jax.tree_util.tree_map(lambda p, g: p - 0.01 * g,
                                           st["emb_dense"], d_emb_dense)

      if kind == "no_apply":
        fused = {k: v + 0.0 for k, v in st["fused"].items()}
      else:
        fused = engine.apply_sparse(st["fused"], layouts, d_z, residuals,
                                    rule, st["step"])
      return ({"dense": dense, "emb_dense": emb_dense, "fused": fused,
               "step": st["step"] + 1}, loss)

    return jax.jit(local, donate_argnums=(0,))

  results = {}
  for kind in ("full", "no_apply", "no_model", "no_gather", "full2"):
    step = make_step(kind if kind != "full2" else "full")
    st, loss = step(state, numerical, cats, labels)
    float(st["fused"][first_fused][0, 0])
    state = st

    def run(n, st):
      t0 = time.perf_counter()
      for _ in range(n):
        st, _ = step(st, numerical, cats, labels)
      float(st["fused"][first_fused][0, 0])
      return time.perf_counter() - t0, st

    _, state = run(1, state)
    t1, state = run(K, state)
    t2, state = run(2 * K, state)
    dt = (t2 - t1) / K
    results[kind] = dt
    print(f"{kind:12s}: {dt * 1e3:8.2f} ms/step", flush=True)

  full = (results["full"] + results["full2"]) / 2
  for kind in ("no_apply", "no_model", "no_gather"):
    print(f"  {kind[3:]:8s} contributes ~{(full - results[kind]) * 1e3:7.2f} ms")


if __name__ == "__main__":
  main()
