"""How many of the splash kernels' grid steps a benchmark cell's documents
leave, and on a TPU what the kernels take with and without them skipped.

`layers/attention.py::attention_splash` folds a sample's documents into the
kernels' block maps (`documents_in_block_maps`): a step whose block of queries
and block of keys share no document is skipped. This traces the cell's model
once (shapes only) to see which attention calls it makes (mask description,
layout, whether segment ids are handed over; `calls_traced` is the layers of
that kind, or 1 where a model traces one layer function for all of them, as
SDAR's does), draws the seed's pool as the benchmark does, and prints per
kind of layer, from the plan function itself:

  attn_live_block_share   live steps after the documents / live steps of the
               static mask, per pass (`fwd`, `dq`, `dkv`: equal for the masks
               the cells have), the mean over the pool's batches; `by_batch`
               the forward's, a batch at a time (a step's time follows its
               batch's share: the pool's least and most are what
               `step_ms_p95` and the rate see)
  static_live_steps       the static mask's live steps a pass

1.0 where a batch is one document, where a window row's two blocks always
share one, and where the model hands no segment ids (block diffusion: the
plan does not engage). Counts, so any backend will do (seconds):

  JAX_PLATFORMS=cpu python tools/attn_block_load.py lfm2_moe_train_1chip \
      [--seed N]

On a TPU it also times one layer of each kind on pool batch `--batch`:
`value_and_grad` of `attention_splash` over seeded `q`, `k`, `v` of the
layer's shape, device time of the three kernels (`fwd`, `dq`, `dkv`, by their
names in a profiler trace of `--iters` calls), with the plan (`planned_ms`)
and with the static maps alone (`static_ms`: the call before PR 48), and
whether the two calls' value and three gradients are equal bit for bit
(`planned_equals_static`), a minute a kind:

  chiprun -- python tools/attn_block_load.py glm_mla_train_1chip --seed N
"""

import argparse
import collections
import glob
import json
import os
import sys
import tempfile
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import specs, trace_reduce, traffic
from distributed_embeddings_tpu.layers import attention
from distributed_embeddings_tpu.layers.decoder import document_segments

PASSES = ("fwd", "dq", "dkv")


def attention_calls(model, numerical, cats):
  """``{(mask, q's shape, v's head, with segment ids): calls}``: the
  attention calls of one forward of ``model``, from a trace of shapes
  alone."""
  calls = collections.Counter()

  def record(q, k, v, mask, seg=None, *_):
    calls[mask, q.shape, v.shape[-1], seg is not None] += 1
    return jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)

  module = sys.modules[type(model).__module__]
  if not hasattr(module, "attention_xla"):
    raise SystemExit(f"{module.__name__}: its attention is not "
                     "layers/attention.py's")
  with mock.patch.object(module, "attention_xla", record):
    jax.eval_shape(lambda n, r: model.init(jax.random.PRNGKey(0), n, None,
                                           emb_acts=[r]), numerical, cats)
  return calls


def live_steps(kernel):
  """Live grid steps of a kernel's three block maps."""
  infos, _ = kernel.tree_flatten()
  return [int(np.count_nonzero(np.asarray(info.block_mask)))
          for info in infos]


def block_shares(mask, q_shape, segs, block=attention.ATTENTION_BLOCK):
  """-> (the static mask's live steps a pass, ``[batches, 3]`` live share of
  them after each of ``segs [batches, S]``' documents)."""
  grouped = len(q_shape) == 5
  kernel = attention._splash_kernel(
      mask, q_shape[1], q_shape[3 if grouped else 2], grouped, block, False)
  static = live_steps(kernel)
  return static, np.array([
      np.divide(live_steps(attention.documents_in_block_maps(
          kernel, jnp.asarray(seg), block)), static) for seg in segs])


def kernel_ms(mask, q_shape, v_dim, seg, iters):
  """-> (device ms a call of the three kernels of one layer, by pass; the
  call's value and gradients)."""
  key = jax.random.PRNGKey(0)
  kv_shape = q_shape[:3] if len(q_shape) == 5 else q_shape[:-1]
  q = jax.random.normal(key, q_shape, jnp.float32) * q_shape[-1] ** -0.5
  k = jax.random.normal(key, kv_shape + q_shape[-1:], jnp.float32)
  v = jax.random.normal(key, kv_shape + (v_dim,), jnp.float32)
  step = jax.jit(jax.value_and_grad(
      lambda q, k, v: jnp.sum(attention.attention_splash(q, k, v, mask, seg)),
      argnums=(0, 1, 2)))
  out = jax.block_until_ready(step(q, k, v))
  with tempfile.TemporaryDirectory() as tdir:
    with jax.profiler.trace(tdir):
      for _ in range(iters):
        jax.block_until_ready(step(q, k, v))
    path = sorted(glob.glob(os.path.join(
        tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    trace = trace_reduce.load_xplane(path)
  ns = collections.Counter()
  for plane in trace["planes"]:
    for line in plane["lines"]:
      if plane["name"] == "/device:TPU:0" and line["name"] == "XLA Ops":
        for name, _, duration_ns in line["events"]:
          name = trace_reduce.op_name(name)
          ns.update({part: duration_ns for part in PASSES
                     if name.startswith("splash_") and f"_{part}" in name})
  return {part: round(ns[part] * 1e-6 / iters, 3) for part in PASSES}, out


def main(argv=None):
  ap = argparse.ArgumentParser()
  ap.add_argument("cell")
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--batch", type=int, default=0,
                  help="the pool batch a TPU times")
  ap.add_argument("--iters", type=int, default=5)
  ap.add_argument("--root", default=specs.ROOT)
  args = ap.parse_args(argv)
  cell = specs.load_cell(args.cell, args.root)
  family = cell.family()
  spec = family.model_spec(cell.config)
  parts = family.build_parts(dict(cell.config, attention="xla"), cell.chips,
                             int(cell.traffic["global_batch"]))
  pool = traffic.make_pool(cell.traffic, spec.inputs, spec.n_numerical,
                           args.seed,
                           traffic.family_labels(family, cell.config))
  numerical = np.stack([b.numerical for b in pool])   # [pool, B, L]
  rows = jax.ShapeDtypeStruct(pool[0].cats.shape + (spec.tables[0].width,),
                              jnp.float32)
  calls = attention_calls(parts.model, jnp.asarray(numerical[0]), rows)
  report = {"cell": args.cell, "seed": args.seed,
            "backend": jax.default_backend(), "pool_batches": len(pool),
            "layers": []}
  if any(documents for *_, documents in calls):
    segs = np.asarray(document_segments(
        jnp.asarray(numerical.reshape(-1, numerical.shape[-1])),
        parts.model.config.mean_document_length))      # [pool x B, L]
  for (mask, q_shape, v_dim, documents), traced in calls.items():
    line = {"mask": repr(mask), "calls_traced": traced, "q": list(q_shape),
            "segment_ids": documents}
    if not documents:
      line["attn_live_block_share"] = dict.fromkeys(PASSES, 1.0)
    else:
      static, shares = block_shares(mask, q_shape, segs)
      by_batch = shares.reshape(len(pool), -1, 3).mean(axis=1)
      line.update(
          static_live_steps=dict(zip(PASSES, static)),
          attn_live_block_share=dict(zip(
              PASSES, by_batch.mean(axis=0).round(4).tolist())),
          by_batch=by_batch[:, 0].round(4).tolist())
      if jax.default_backend() == "tpu":
        seg = jnp.asarray(segs.reshape(len(pool), -1, segs.shape[-1])
                          [args.batch])
        line["timed_batch"] = args.batch
        line["timed_share"] = line["by_batch"][args.batch]
        line["planned_ms"], planned = kernel_ms(mask, q_shape, v_dim, seg,
                                                args.iters)
        with mock.patch.object(attention, "documents_in_block_maps",
                               lambda kernel, seg, block: kernel):
          line["static_ms"], static = kernel_ms(mask, q_shape, v_dim, seg,
                                                args.iters)
        line["planned_equals_static"] = all(
            bool(jnp.array_equal(a, b)) for a, b in zip(
                jax.tree_util.tree_leaves(planned),
                jax.tree_util.tree_leaves(static)))
    report["layers"].append(line)
  print(json.dumps(report))
  return report


if __name__ == "__main__":
  main()
