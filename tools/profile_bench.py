"""Piecewise timing of the bench.py (synthetic Tiny) train step on the chip.

Times: full step, forward-only (loss), route+fused-gather only, and
apply_sparse only, using chained-scan deltas so that dispatch overhead
cancels. Prints one line per part.

Usage: python tools/profile_bench.py [model] [batch]
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
from distributed_embeddings_tpu.training import (
    init_sparse_state_direct,
    make_sparse_train_step,
)

MODEL = sys.argv[1] if len(sys.argv) > 1 else "tiny"
BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 65536
K = 4


def timed_chain(fn, *args, k=K):
  """fn(*args) -> scalar; returns s/iter via (2K - K) delta timing."""

  def chain(length):
    @jax.jit
    def run(*a):
      def body(acc, _):
        return acc + fn(*a), None

      acc, _ = jax.lax.scan(body, jnp.zeros(()), None, length=length)
      return acc

    return run

  r1, r2 = chain(k), chain(2 * k)
  float(r1(*args))
  float(r2(*args))
  t0 = time.perf_counter()
  float(r1(*args))
  t1 = time.perf_counter()
  t2 = time.perf_counter()
  float(r2(*args))
  t3 = time.perf_counter()
  return ((t3 - t2) - (t1 - t0)) / k


def main():
  cfg = SYNTHETIC_MODELS[MODEL]
  tables, tmap, hotness = expand_tables(cfg)
  model = SyntheticModel(config=cfg, world_size=1)
  plan = DistEmbeddingStrategy(tables, 1, "basic", input_table_map=tmap,
                               dense_row_threshold=model.dense_row_threshold)
  n_sparse = sum(1 for k in plan.class_keys if plan.classes[k].kind == "sparse")
  occ = BATCH * sum(h for h in hotness)
  print(f"model={MODEL} batch={BATCH} sparse_classes={n_sparse} "
        f"occurrences~{occ / 1e6:.1f}M")

  numerical, cats, labels = generate_batch(cfg, BATCH, alpha=1.05, seed=0)
  cats = [np.minimum(c, tables[t].input_dim - 1).astype(np.int32)
          for c, t in zip(cats, tmap)]
  cats = [jnp.asarray(c if h > 1 else c[:, 0])
          for c, h in zip(cats, hotness)]
  batch = (jnp.asarray(numerical), cats, jnp.asarray(labels))

  dense_opt = optax.adagrad(0.01)
  rule = adagrad_rule(0.01)
  dummy_acts = [jnp.zeros((2, tables[t].output_dim), jnp.float32)
                for t in tmap]
  small_cats = [c[:2] for c in cats]
  dense_params = model.init(jax.random.PRNGKey(0), batch[0][:2], small_cats,
                            emb_acts=dummy_acts)["params"]

  state = init_sparse_state_direct(plan, rule, dense_params, dense_opt,
                                   jax.random.PRNGKey(1))
  jax.block_until_ready(state)
  engine = DistributedLookup(plan)
  layouts = engine.fused_layouts(rule)

  hotness_of = lambda i: (cats[i].shape[1] if cats[i].ndim == 2 else 1)  # noqa

  # ---- route + gather only ----------------------------------------------
  def fwd_gather(fused, cats_):
    ids_all = engine.route_ids(cats_, hotness_of)
    z, res = engine.lookup_sparse_fused(fused, layouts, ids_all)
    return sum(zb.sum() for zb in z.values())

  dt = timed_chain(lambda f: fwd_gather(f, cats), state["fused"])
  print(f"route+gather_fused : {dt * 1e3:8.2f} ms")

  # ---- full forward (loss) ----------------------------------------------
  def fwd(fused, emb_dense, dp, nump, cats_, labels_):
    ids_all = engine.route_ids(cats_, hotness_of)
    z, res = engine.lookup_sparse_fused(fused, layouts, ids_all)
    acts = engine.finish_forward(z, emb_dense, ids_all, BATCH, hotness_of)
    logits = model.apply({"params": {**dp, "embeddings": emb_dense}},
                         nump, cats_, emb_acts=acts)
    return bce_loss(logits, labels_)

  dt = timed_chain(
      lambda f, ed, dp: fwd(f, ed, dp, batch[0], cats, batch[2]),
      state["fused"], state["emb_dense"], state["dense"])
  print(f"forward total      : {dt * 1e3:8.2f} ms")

  # ---- scatter only ------------------------------------------------------
  def scat(fused, cats_):
    ids_all = engine.route_ids(cats_, hotness_of)
    z, res = engine.lookup_sparse_fused(fused, layouts, ids_all)
    d_z = {bk: jnp.ones_like(zb) for bk, zb in z.items()}
    new = engine.apply_sparse(fused, layouts, d_z, res, rule,
                              jnp.zeros((), jnp.int32))
    return sum(v.sum() for v in new.values()) * 0 + sum(
        v[0, 0] for v in new.values())

  # NOTE: includes route+gather (needed for residuals); subtract part 1.
  dt = timed_chain(lambda f: scat(f, cats), state["fused"])
  print(f"gather+apply_sparse: {dt * 1e3:8.2f} ms   (minus line 1 = scatter)")

  # ---- full step ---------------------------------------------------------
  state_avals = jax.eval_shape(lambda s: s, state)
  step = make_sparse_train_step(model, plan, bce_loss, dense_opt, rule,
                                None, state_avals, batch)
  compiled = step.lower(state_avals, *batch).compile()
  s2, loss = compiled(state, *batch)
  jax.block_until_ready(loss)
  t0 = time.perf_counter()
  for _ in range(K):
    s2, loss = compiled(s2, *batch)
  float(loss)
  t1 = time.perf_counter()
  t2 = time.perf_counter()
  for _ in range(2 * K):
    s2, loss = compiled(s2, *batch)
  float(loss)
  t3 = time.perf_counter()
  print(f"full step          : {((t3 - t2) - (t1 - t0)) / K * 1e3:8.2f} ms")


if __name__ == "__main__":
  main()
