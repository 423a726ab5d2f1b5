"""Per-bucket decomposition of the Tiny gather+combine block.

For each sparse bucket of the real plan: raw phys-row take vs full
gather_fused vs gather+combine, on the real routed ids and real fused
buffers. Finds where route+gather+combine's time above the 11 ns/row
gather floor actually goes.

Usage: PYTHONPATH=/root/repo python -u tools/profile_tiny_buckets.py
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
    expand_tables,
    generate_batch,
)
from distributed_embeddings_tpu.ops.packed_table import (
    adagrad_rule,
    gather_fused,
)
from distributed_embeddings_tpu.parallel.lookup_engine import (
    DistributedLookup,
    class_param_name,
)
from distributed_embeddings_tpu.training import init_sparse_state_direct

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 65536
K = 5


def _sync(x):
  leaf = jax.tree_util.tree_leaves(x)[0]
  float(jnp.asarray(leaf).ravel()[0])


def timeit(name, fn, *args, n_norm=None):
  step = jax.jit(fn)
  carry = step(jnp.zeros((), jnp.float32), *args)
  _sync(carry)

  def run(n, carry):
    t0 = time.perf_counter()
    for _ in range(n):
      carry = step(carry, *args)
    _sync(carry)
    return time.perf_counter() - t0, carry

  _, carry = run(1, carry)
  t1, carry = run(K, carry)
  t2, carry = run(2 * K, carry)
  dt = (t2 - t1) / K
  per = f"  {dt / n_norm * 1e9:6.1f} ns/row" if n_norm else ""
  print(f"{name:58s}: {dt * 1e3:8.2f} ms{per}", flush=True)


def main():
  cfg = SYNTHETIC_MODELS["tiny"]
  tables, tmap, hotness = expand_tables(cfg)
  model = SyntheticModel(config=cfg, world_size=1)
  plan = DistEmbeddingStrategy(tables, 1, "basic", input_table_map=tmap,
                               dense_row_threshold=model.dense_row_threshold,
                               input_hotness=hotness, batch_hint=BATCH)
  engine = DistributedLookup(plan)
  rule = adagrad_rule(0.01)
  layouts = engine.fused_layouts(rule)
  numerical, cats, labels = generate_batch(cfg, BATCH, alpha=1.05, seed=0)
  cats = [np.minimum(c, tables[t].input_dim - 1).astype(np.int32)
          for c, t in zip(cats, tmap)]
  cats = [jnp.asarray(c if h > 1 else c[:, 0])
          for c, h in zip(cats, hotness)]
  hotness_of = lambda i: hotness[i]  # noqa: E731

  dummy_acts = [jnp.zeros((2, tables[t].output_dim), jnp.float32)
                for t in tmap]
  dense_params = model.init(jax.random.PRNGKey(0),
                            jnp.asarray(numerical[:2]), [c[:2] for c in cats],
                            emb_acts=dummy_acts)["params"]
  state = init_sparse_state_direct(plan, rule, dense_params,
                                   optax.adagrad(0.01), jax.random.PRNGKey(1))
  fused = state["fused"]
  _sync(fused[sorted(fused)[0]])

  ids_all = jax.jit(lambda c: engine.route_ids(c, hotness_of))(cats)
  ids_all = {k: jax.device_put(v) for k, v in ids_all.items()}

  for bk in sorted(ids_all):
    if engine.plan.classes[bk.class_key].kind != "sparse":
      print(f"bucket {bk.width}w h={bk.h} vcap={bk.vcap}: dense, skipped")
      continue
    ids = ids_all[bk]
    name = class_param_name(*bk.class_key)
    layout = layouts[name]
    buf = fused[name]
    n = int(np.prod(ids.shape))
    rpp = layout.rows_per_phys

    def raw_take(c, idb, buf=buf, rpp=rpp, layout=layout):
      idb = idb + jnp.minimum(c.astype(jnp.int32), 0)
      grp = jnp.where((idb >= 0) & (idb < layout.rows), idb // rpp,
                      layout.phys_rows)
      rows = jnp.take(buf, grp, axis=0, mode="fill", fill_value=0)
      return c + jnp.tanh(jnp.sum(rows) * 1e-9) * 0 + jnp.float32(0)

    def gfused(c, idb, buf=buf, layout=layout):
      idb = idb + jnp.minimum(c.astype(jnp.int32), 0)
      rows = gather_fused(layout, buf, idb)
      return c + jnp.tanh(jnp.sum(rows) * 1e-9) * 0 + jnp.float32(0)

    def gcombine(c, idb, buf=buf, layout=layout, bk=bk):
      idb = idb + jnp.minimum(c.astype(jnp.int32), 0)
      z, aux = engine._z_sparse_fused(bk.class_key, layout, buf, idb, bk.rs)
      return (c + jnp.tanh(jnp.sum(z) * 1e-9) * 0
              + jnp.tanh(jnp.sum(aux) * 1e-9) * 0 + jnp.float32(0))

    label = f"{bk.width}w h={bk.h} n={n} rpp={rpp}"
    timeit(f"[{label}] raw phys take", raw_take, ids, n_norm=n)
    timeit(f"[{label}] gather_fused", gfused, ids, n_norm=n)
    timeit(f"[{label}] gather+combine", gcombine, ids, n_norm=n)


if __name__ == "__main__":
  main()
