"""DLRM training on Criteo (TPU-native).

Equivalent of `/root/reference/examples/dlrm/main.py`: trains DLRM on the
split-binary Criteo dataset (or dummy data) with hybrid model/data parallel
embeddings, warmup+poly-decay SGD, AUC evaluation, and a final global-view
numpy checkpoint.

Usage:
  python examples/dlrm/main.py --dataset dummy --steps 100 --batch_size 4096
  python examples/dlrm/main.py --dataset_path /data/criteo --amp

The run states what it ran on: platform, device kind and count first, then
where the compile cache lives, per-device memory after the state is built,
and — on the sparse path, whose train step is lowered and compiled
explicitly — the compile time and the hand-written (Mosaic) kernels the
compiled step contains. `chip_smoke.py` reads those lines.
"""

import argparse
import functools
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_embeddings_tpu.compile_cache import (
    cache_entries,
    enable_compile_cache,
)
from distributed_embeddings_tpu.layers import get_weights
from distributed_embeddings_tpu.models import DLRM, bce_loss
from distributed_embeddings_tpu.models.dlrm import dlrm_embedding_plan
from distributed_embeddings_tpu.parallel import create_mesh, device_summary
from distributed_embeddings_tpu.training import (
    make_eval_step,
    make_train_step,
    shard_batch,
    shard_params,
)
from distributed_embeddings_tpu.utils import (
    DummyDataset,
    RawBinaryCriteoDataset,
    dlrm_lr_schedule,
)

# --profile_dir: the trace starts after this many steps (the first compiles,
# the next fill the dispatch queue) and holds this many
PROFILE_AFTER, PROFILE_STEPS = 3, 5

CRITEO_1TB_VOCAB = [
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771,
    25641295, 39664984, 585935, 12972, 108, 36
]


def parse_args():
  p = argparse.ArgumentParser(description=__doc__)
  p.add_argument("--dataset", choices=["dummy", "criteo"], default="dummy")
  p.add_argument("--eval_every", type=int, default=0,
                 help="run the AUC eval every N train steps (0 = only at "
                      "the end, reference cadence is per-epoch)")
  p.add_argument("--dataset_path", default=None,
                 help="split-binary Criteo dir (model_size.json supported)")
  p.add_argument("--batch_size", type=int, default=8192,
                 help="global batch size")
  p.add_argument("--steps", type=int, default=100)
  p.add_argument("--epochs", type=int, default=1)
  p.add_argument("--lr", type=float, default=24.0)
  p.add_argument("--warmup_steps", type=int, default=2750)
  p.add_argument("--decay_start_step", type=int, default=49315)
  p.add_argument("--decay_steps", type=int, default=27772)
  p.add_argument("--embedding_dim", type=int, default=128)
  p.add_argument("--strategy", default="memory_balanced",
                 choices=["basic", "memory_balanced", "memory_optimized"])
  p.add_argument("--column_slice_threshold", type=int, default=None)
  p.add_argument("--amp", action="store_true", help="bf16 compute")
  p.add_argument("--world_size", type=int, default=None,
                 help="mesh size; default = all devices")
  p.add_argument("--eval", action="store_true")
  p.add_argument("--save_checkpoint", default=None,
                 help="path for final np.savez global checkpoint")
  p.add_argument("--sparse", action="store_true",
                 help="fused sparse training path (packed tables, "
                      "row-sparse SGD; the bench.py path)")
  p.add_argument("--micro_batches", type=int, default=1,
                 help="bounded-memory accumulation: run the sparse step "
                      "over N batch slices in a scan, capping "
                      "per-occurrence temporaries at 1/N (one-shot "
                      "numerics preserved; sparse path only)")
  p.add_argument("--checkpoint_dir", default=None,
                 help="full train-state checkpoint dir (sparse path only); "
                      "auto-resumes when it exists")
  p.add_argument("--checkpoint_every", type=int, default=0,
                 help="save the full state every N steps (0 = end only)")
  p.add_argument("--row_slice", type=int, default=None,
                 help="row (vocab) slice threshold in elements")
  p.add_argument("--vocab_scale", type=float, default=1.0,
                 help="scale Criteo vocab sizes (for memory-limited runs)")
  p.add_argument("--profile_dir", default=None,
                 help="write a jax.profiler trace of train steps "
                      f"{PROFILE_AFTER + 1}..{PROFILE_AFTER + PROFILE_STEPS} "
                      "there (docs/ARCHITECTURE.md, 'Reading a profile')")
  p.add_argument("--platform", default=None,
                 help="force a jax platform (e.g. 'cpu' for a rehearsal at "
                      "tiny sizes); same effect as JAX_PLATFORMS")
  return p.parse_args()


def load_vocab(args):
  if args.dataset_path:
    meta = os.path.join(args.dataset_path, "model_size.json")
    if os.path.exists(meta):
      # reference reads table sizes from the dataset's model_size.json
      # (`examples/dlrm/main.py:68-73`)
      with open(meta) as f:
        sizes = list(json.load(f).values())
      return [s + 1 for s in sizes]
  return [max(4, int(v * args.vocab_scale)) for v in CRITEO_1TB_VOCAB]


def auc(labels: np.ndarray, scores: np.ndarray) -> float:
  """Rank-based AUC (Mann-Whitney), no sklearn dependency."""
  order = np.argsort(scores, kind="mergesort")
  ranks = np.empty_like(order, dtype=np.float64)
  ranks[order] = np.arange(1, len(scores) + 1)
  # average ties
  sorted_scores = scores[order]
  i = 0
  while i < len(sorted_scores):
    j = i
    while j + 1 < len(sorted_scores) and sorted_scores[j + 1] == sorted_scores[i]:
      j += 1
    if j > i:
      ranks[order[i:j + 1]] = ranks[order[i:j + 1]].mean()
    i = j + 1
  pos = labels > 0.5
  n_pos, n_neg = pos.sum(), (~pos).sum()
  if n_pos == 0 or n_neg == 0:
    return float("nan")
  return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def mosaic_kernels(hlo_text: str):
  """Names of the Pallas (Mosaic) kernels in a compiled program's HLO."""
  names = set()
  for line in hlo_text.splitlines():
    if "tpu_custom_call" in line:  # the Mosaic custom call; its op_name
      # metadata ends in "<pallas_call name>/pallas_call"
      names.update(re.findall(r'op_name="[^"]*?(\w+)/pallas_call"', line))
  return sorted(names)


def print_memory(label: str):
  """One line of per-device memory as the backend reports it (TPU: HBM)."""
  cells = []
  for d in jax.devices():
    stats = d.memory_stats()
    if not stats:  # the CPU backend reports none
      cells.append(f"dev{d.id} n/a")
    else:
      cells.append(f"dev{d.id} in_use={stats['bytes_in_use']} "
                   f"peak={stats['peak_bytes_in_use']}")
  print(f"memory {label}: " + " | ".join(cells), flush=True)


def main():
  args = parse_args()
  if args.platform:
    jax.config.update("jax_platforms", args.platform)
  cache_dir = enable_compile_cache()
  dev = device_summary()
  world = args.world_size or dev["count"]
  mesh = create_mesh(world) if world > 1 else None
  vocab = load_vocab(args)
  print(f"device: {json.dumps(dev)} world={world} tables={len(vocab)} "
        f"total_rows={sum(vocab):,}")
  print(f"compile cache: {cache_dir} entries={cache_entries(cache_dir)}")

  model = DLRM(vocab_sizes=vocab,
               embedding_dim=args.embedding_dim,
               world_size=world,
               strategy=args.strategy,
               column_slice_threshold=args.column_slice_threshold,
               row_slice=args.row_slice,
               batch_hint=args.batch_size,
               compute_dtype=jnp.bfloat16 if args.amp else jnp.float32)

  local_bs = args.batch_size // world
  if args.dataset == "dummy":
    train_data = DummyDataset(args.batch_size, 13, vocab,
                              num_batches=args.steps)
    eval_data = DummyDataset(args.batch_size, 13, vocab, num_batches=4,
                             seed=777)
  else:
    train_data = RawBinaryCriteoDataset(
        args.dataset_path, local_bs, numerical_features=13,
        categorical_features=list(range(len(vocab))),
        categorical_feature_sizes=vocab, world_size=world)
    eval_data = RawBinaryCriteoDataset(
        args.dataset_path, local_bs, numerical_features=13,
        categorical_features=list(range(len(vocab))),
        categorical_feature_sizes=vocab, world_size=world, valid=True)

  print("building model/state ...", flush=True)
  _t_setup = time.time()
  numerical, cats, labels = train_data[0]
  batch_example = (jnp.asarray(numerical), [jnp.asarray(c) for c in cats],
                   jnp.asarray(labels))
  schedule = dlrm_lr_schedule(args.lr, args.warmup_steps,
                              args.decay_start_step, args.decay_steps)
  optimizer = optax.sgd(schedule)
  plan = dlrm_embedding_plan(vocab, args.embedding_dim, world,
                             args.strategy, args.column_slice_threshold,
                             row_slice=args.row_slice,
                             batch_hint=args.batch_size)

  if args.sparse:
    # fused sparse path: packed tables with row-sparse SGD, full-state
    # checkpoint/resume (beyond the reference, which checkpoints weights
    # only -- `examples/dlrm/main.py:245-248`)
    from distributed_embeddings_tpu import checkpoint as ckpt
    from distributed_embeddings_tpu.ops.packed_table import sgd_rule
    from distributed_embeddings_tpu.training import (
        init_sparse_state_direct,
        make_sparse_train_step,
    )
    rule = sgd_rule(schedule)
    # init the DENSE params only (dummy embedding activations skip the
    # table creation); the packed class buffers are drawn directly in
    # their physical layout by init_sparse_state_direct — materializing
    # simple-layout tables first would transiently need ~2.5x the class
    # bytes and grinds a near-HBM-sized model to a halt (bench.py:96)
    dummy_acts = [jnp.zeros((2, args.embedding_dim), jnp.float32)
                  for _ in vocab]
    dense_params = model.init(
        jax.random.PRNGKey(0), batch_example[0][:2],
        [c[:2] for c in batch_example[1]], emb_acts=dummy_acts)["params"]
    # mesh=mesh: every rank's block is drawn on its own device, so a model
    # larger than one chip never passes through chip 0
    state = init_sparse_state_direct(plan, rule, dense_params, optimizer,
                                     jax.random.PRNGKey(1), mesh=mesh)
    jax.block_until_ready(state)
    print("plan bytes per rank: "
          f"{plan.tier_capacity_report(rule.n_aux)['device_bytes_per_rank']}")
    print_memory("after init")
    if args.checkpoint_dir and os.path.isdir(args.checkpoint_dir):
      state = ckpt.restore(args.checkpoint_dir, plan, rule, state, mesh=mesh)
      print(f"resumed from {args.checkpoint_dir} at step "
            f"{int(jax.device_get(state['step']))}")
    print(f"sparse state ready in {time.time() - _t_setup:.1f}s", flush=True)
    sparse_step = make_sparse_train_step(model, plan, bce_loss, optimizer,
                                         rule, mesh, state, batch_example,
                                         donate=False,
                                         micro_batches=args.micro_batches)

    # One jitted wrapper that takes the cats as a SINGLE [B, n_tables]
    # matrix and splits it on device: feeding 26 separate feature arrays
    # pays one host->device dispatch EACH per step.
    n_tables = len(vocab)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step_fn(carry, numerical, cats_mat, labels):
      cats = [cats_mat[:, i] for i in range(n_tables)]
      return sparse_step(carry, numerical, cats, labels)

    carry = state
    # Lower and compile explicitly: the compile is timed on its own (a
    # warm compile cache adds no entry), and the compiled program is
    # searched for the Mosaic kernels — a step that compiled without them
    # is a different, slower program that nothing else would tell apart.
    t_compile, n_cached = time.time(), cache_entries(cache_dir)
    step_fn = step_fn.lower(carry, *shard_batch(
        (batch_example[0], jnp.stack(batch_example[1], axis=1),
         batch_example[2]), mesh)).compile()
    print(f"train step compiled in {time.time() - t_compile:.1f}s "
          f"({cache_entries(cache_dir) - n_cached} new cache entries); "
          f"mosaic kernels: "
          f"{' '.join(mosaic_kernels(step_fn.as_text())) or 'none'}",
          flush=True)
  else:
    params = model.init(jax.random.PRNGKey(0), batch_example[0],
                        batch_example[1])["params"]
    opt_state = optimizer.init(params)
    params = shard_params(params, mesh)
    opt_state = shard_params(opt_state, mesh)

    def loss_fn(params, numerical, cats, labels):
      logits = model.apply({"params": params}, numerical, cats)
      return bce_loss(logits, labels)

    dense_step = make_train_step(loss_fn, optimizer, mesh, params,
                                 opt_state, batch_example, donate=False)
    n_tables = len(vocab)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step_fn(carry, numerical, cats_mat, labels):
      cats = [cats_mat[:, i] for i in range(n_tables)]
      params, opt_state, loss = dense_step(*carry, numerical, cats, labels)
      return (params, opt_state), loss

    carry = (params, opt_state)

  _eval_cache = {}

  def run_eval(carry):
    """Rank-wise AUC over the eval split (reference main.py:222-243).
    The jitted eval step is built once and reused across cadenced calls."""
    if "step" not in _eval_cache:
      if args.sparse:
        from distributed_embeddings_tpu.training import make_sparse_eval_step
        raw_eval = make_sparse_eval_step(model, plan, rule, mesh, carry,
                                         batch_example[:2])
        _eval_cache["step"] = lambda st, *xs: jax.nn.sigmoid(
            raw_eval(st, *xs))
      else:
        def pred_fn(params, numerical, cats):
          return jax.nn.sigmoid(model.apply({"params": params}, numerical,
                                            cats))
        dense_eval = make_eval_step(pred_fn, mesh, carry[0],
                                    batch_example[:2])
        _eval_cache["step"] = lambda st, *xs: dense_eval(st[0], *xs)
    eval_fn = _eval_cache["step"]
    all_scores, all_labels = [], []
    for numerical, cats, labels in eval_data:
      sharded = shard_batch(
          (jnp.asarray(numerical), [jnp.asarray(c) for c in cats]), mesh)
      all_scores.append(np.asarray(eval_fn(carry, *sharded)))
      all_labels.append(labels)
    return auc(np.concatenate(all_labels), np.concatenate(all_scores))

  print(f"setup done in {time.time() - _t_setup:.1f}s", flush=True)
  t_start, losses = time.time(), []
  first_loss = float("nan")
  steps_done = 0
  tracing = False
  for epoch in range(args.epochs):
    for batch in train_data:
      numerical, cats, labels = batch
      # host->device conversion of batch k+1 overlaps the device compute
      # of step k because steps dispatch asynchronously — as long as
      # nothing here blocks. The loss is therefore kept as a DEVICE
      # scalar and only fetched at log points (fetching every step would
      # sync every step and serialize transfer behind compute), and the
      # cats travel as ONE stacked matrix (see step_fn).
      cats_mat = np.stack([np.asarray(c, np.int32) for c in cats], axis=1)
      sharded = shard_batch(
          (jnp.asarray(numerical), jnp.asarray(cats_mat),
           jnp.asarray(labels)), mesh)
      if args.profile_dir and steps_done == PROFILE_AFTER:
        jax.block_until_ready(carry)  # the warm-up's work stays out of it
        jax.profiler.start_trace(args.profile_dir)
        tracing = True
      if tracing:
        with jax.profiler.StepTraceAnnotation("train", step_num=steps_done):
          carry, loss = step_fn(carry, *sharded)
      else:
        carry, loss = step_fn(carry, *sharded)
      losses.append(loss)
      steps_done += 1
      if tracing and steps_done in (PROFILE_AFTER + PROFILE_STEPS, args.steps):
        jax.block_until_ready(loss)
        jax.profiler.stop_trace()
        tracing = False
        print(f"profile of steps {PROFILE_AFTER + 1}..{steps_done} -> "
              f"{args.profile_dir}", flush=True)
      if steps_done == 1:
        first_loss = loss
      if steps_done % 100 == 0:
        # ONE stacked fetch (a float() per scalar would pay the host
        # link's round-trip latency 100 times); trim the list so a long
        # run doesn't pin an unbounded set of device scalars
        window = np.asarray(jax.device_get(jnp.stack(losses[-100:])))
        losses = [float(x) for x in window]
        rate = steps_done * args.batch_size / (time.time() - t_start)
        print(f"step {steps_done} loss {window.mean():.5f} "
              f"{rate:,.0f} samples/sec")
      if args.eval_every and steps_done % args.eval_every == 0:
        score = run_eval(carry)
        print(f"step {steps_done} eval AUC: {score:.5f}")
      if args.sparse and args.checkpoint_dir and args.checkpoint_every \
          and steps_done % args.checkpoint_every == 0:
        ckpt.save(args.checkpoint_dir, plan, rule, carry)
        print(f"checkpointed step {steps_done} -> {args.checkpoint_dir}")
      if steps_done >= args.steps:
        break
    if steps_done >= args.steps:
      break
  # drain the dispatch queue before reading the clock: the loop above only
  # DISPATCHES steps (that is what lets transfer overlap compute), so the
  # throughput number must wait for the last step to actually finish
  if losses:
    losses = list(np.asarray(jax.device_get(jnp.stack(losses[-10:]))))
  elapsed = time.time() - t_start
  print(f"trained {steps_done} steps in {elapsed:.1f}s "
        f"({steps_done * args.batch_size / max(elapsed, 1e-9):,.0f} samples/sec)"
        f" first loss {float(first_loss):.5f}"
        f" final loss {np.mean(losses[-10:]):.5f}")
  print("last losses: " + " ".join(f"{x:.5f}" for x in losses[-10:]))
  print_memory("after training")

  if args.sparse and args.checkpoint_dir:
    ckpt.save(args.checkpoint_dir, plan, rule, carry)
    print(f"saved full train state -> {args.checkpoint_dir}")

  if args.eval:
    print(f"eval AUC: {run_eval(carry):.5f}")

  if args.save_checkpoint:
    # global-view numpy table checkpoint (reference
    # `examples/dlrm/main.py:245-248`)
    if args.sparse:
      from distributed_embeddings_tpu.training import unpack_sparse_state
      full_params, _ = unpack_sparse_state(plan, rule, carry)
      tables = get_weights(plan, full_params["embeddings"])
    else:
      tables = get_weights(plan, carry[0]["embeddings"])
    np.savez(args.save_checkpoint, *tables)
    print(f"saved {len(tables)} tables to {args.save_checkpoint}")


if __name__ == "__main__":
  main()
