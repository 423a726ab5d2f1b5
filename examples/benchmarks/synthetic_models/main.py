"""Synthetic model zoo step-time benchmark.

Equivalent of `/root/reference/examples/benchmarks/synthetic_models/main.py`:
trains one synthetic config (tiny ... colossal) with Adagrad on power-law
inputs and reports mean step time.

  python examples/benchmarks/synthetic_models/main.py --model tiny \
      --batch_size 65536 [--platform cpu] [--shrink 0.01]
"""

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax


def parse_args():
  p = argparse.ArgumentParser()
  p.add_argument("--model", default="tiny",
                 choices=["criteo", "tiny", "small", "medium", "large",
                          "jumbo", "colossal"])
  p.add_argument("--batch_size", type=int, default=65536)
  p.add_argument("--steps", type=int, default=20)
  p.add_argument("--warmup_steps", type=int, default=3)
  p.add_argument("--alpha", type=float, default=1.05,
                 help="power-law exponent for ids (0 = uniform)")
  p.add_argument("--lr", type=float, default=0.01)
  p.add_argument("--strategy", default="memory_balanced")
  p.add_argument("--column_slice_threshold", type=int, default=None)
  p.add_argument("--world_size", type=int, default=None)
  p.add_argument("--num_batches", type=int, default=4,
                 help="distinct input batches to rotate through")
  p.add_argument("--shrink", type=float, default=1.0,
                 help="scale table rows (to fit small test machines)")
  p.add_argument("--amp", action="store_true", help="bf16 compute")
  p.add_argument("--platform", default=None)
  return p.parse_args()


def main():
  args = parse_args()
  if args.platform:
    jax.config.update("jax_platforms", args.platform)

  from distributed_embeddings_tpu.compile_cache import enable_compile_cache
  from distributed_embeddings_tpu.models import (
      SYNTHETIC_MODELS,
      SyntheticModel,
      bce_loss,
      expand_tables,
      generate_batch,
      model_size_gib,
  )
  from distributed_embeddings_tpu.parallel import create_mesh, device_summary
  from distributed_embeddings_tpu.training import (
      make_train_step,
      shard_batch,
      shard_params,
  )

  enable_compile_cache()
  cfg = SYNTHETIC_MODELS[args.model]
  if args.shrink != 1.0:
    groups = tuple(
        dataclasses.replace(g, num_rows=max(4, int(g.num_rows * args.shrink)))
        for g in cfg.embedding_groups)
    cfg = dataclasses.replace(cfg, embedding_groups=groups)

  dev = device_summary()
  world = args.world_size or dev["count"]
  mesh = create_mesh(world) if world > 1 else None
  tables, tmap, hotness = expand_tables(cfg)
  print(f"model={cfg.name} tables={len(tables)} inputs={len(tmap)} "
        f"size={model_size_gib(cfg):.1f} GiB world={world} "
        f"batch={args.batch_size} platform={dev['platform']} "
        f"device_kind={dev['kind']!r} devices={dev['count']}")

  model = SyntheticModel(config=cfg, world_size=world,
                         strategy=args.strategy,
                         column_slice_threshold=args.column_slice_threshold,
                         # the planner's scatter-regime cost model needs
                         # the expected batch (docs/BENCHMARKS.md)
                         batch_hint=args.batch_size,
                         compute_dtype=jnp.bfloat16 if args.amp
                         else jnp.float32)

  batches = []
  for i in range(args.num_batches):
    numerical, cats, labels = generate_batch(cfg, args.batch_size,
                                             alpha=args.alpha, seed=i)
    cats = [np.minimum(c, tables[t].input_dim - 1).astype(np.int32)
            for c, t in zip(cats, tmap)]
    batches.append((jnp.asarray(numerical),
                    [jnp.asarray(c) for c in cats], jnp.asarray(labels)))

  params = model.init(jax.random.PRNGKey(0), batches[0][0],
                      batches[0][1])["params"]
  optimizer = optax.adagrad(args.lr)
  opt_state = optimizer.init(params)
  params = shard_params(params, mesh)
  opt_state = shard_params(opt_state, mesh)

  def loss_fn(p, numerical, cats, labels):
    return bce_loss(model.apply({"params": p}, numerical, cats), labels)

  step = make_train_step(loss_fn, optimizer, mesh, params, opt_state,
                         batches[0])
  sharded = [shard_batch(b, mesh) for b in batches]

  for i in range(args.warmup_steps):
    params, opt_state, loss = step(params, opt_state,
                                   *sharded[i % len(sharded)])
  jax.block_until_ready(loss)
  t0 = time.perf_counter()
  for i in range(args.steps):
    params, opt_state, loss = step(params, opt_state,
                                   *sharded[i % len(sharded)])
  jax.block_until_ready(loss)
  ms = (time.perf_counter() - t0) / args.steps * 1000
  print(f"step time: {ms:.3f} ms  "
        f"({args.batch_size / ms * 1000:,.0f} samples/sec)  "
        f"loss {float(loss):.5f}")
  return ms


if __name__ == "__main__":
  main()
