"""Pallas fused DLRM interaction vs the XLA matmul-form (round 5).

The round-4 trace shows the interaction block costs ~13 ms of the ~52 ms
DLRM step, and over half of it is pure layout copies: XLA lowers the
per-sample product einsum ("bpd,bqd->bpq") to a convolution that wants
batch-minor operand layouts, so the step pays [B,27,128]/[B,3456] copies
on both sides of the matmul pair (copy.226/227/232/234/235 + fusion.6 in
tools/trace_dlrm.py output, ~7.5 ms/step at B=64k).

A Pallas kernel computes the per-sample products from feats in their
NATURAL row-major layout (batched MXU dot over a VMEM-resident block),
so no relayout copies exist at all; the tiny inter tensor ([B,27,27])
round-trips HBM in bf16, and the selection matmuls (dense [B,729]@
[729,351], already layout-friendly) stay in XLA.

Measures fwd+bwd (value_and_grad of a non-linear consumer) for:
  A. the production `_tril_products` custom-VJP path (models/dlrm.py)
  B. pallas inter/d_feats kernels + XLA selection matmuls

Usage: python tools/proto_pallas_interact.py [batch] [block]
"""

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_embeddings_tpu.models.dlrm import (  # noqa: E402
    _tril_products,
    _tril_select_np,
)

B = int(sys.argv[1]) if len(sys.argv) > 1 else 65536
S = int(sys.argv[2]) if len(sys.argv) > 2 else 256
F = 27
D = 128


def _inter_kernel(feats_ref, out_ref):
  f = feats_ref[...]  # [S, F, D] bf16, natural layout
  inter = jax.lax.dot_general(
      f, f, (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)  # [S, F, F]
  out_ref[...] = inter.astype(out_ref.dtype)


def _dfeats_kernel(dsym_ref, feats_ref, out_ref):
  ds = dsym_ref[...]  # [S, F, F] bf16 (symmetric)
  f = feats_ref[...]  # [S, F, D] bf16
  # d_feats = 2 * d_sym @ f  per sample ("spq,sqd->spd")
  d = jax.lax.dot_general(
      ds, f, (((2,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)
  out_ref[...] = (2.0 * d).astype(out_ref.dtype)


def pallas_inter(feats):
  b = feats.shape[0]
  return pl.pallas_call(
      _inter_kernel,
      grid=(b // S,),
      in_specs=[pl.BlockSpec((S, F, D), lambda i: (i, 0, 0))],
      out_specs=pl.BlockSpec((S, F, F), lambda i: (i, 0, 0)),
      out_shape=jax.ShapeDtypeStruct((b, F, F), jnp.bfloat16),
  )(feats)


def pallas_dfeats(dsym, feats):
  b = feats.shape[0]
  return pl.pallas_call(
      _dfeats_kernel,
      grid=(b // S,),
      in_specs=[
          pl.BlockSpec((S, F, F), lambda i: (i, 0, 0)),
          pl.BlockSpec((S, F, D), lambda i: (i, 0, 0)),
      ],
      out_specs=pl.BlockSpec((S, F, D), lambda i: (i, 0, 0)),
      out_shape=jax.ShapeDtypeStruct((b, F, D), jnp.bfloat16),
  )(dsym, feats)


def _fused_fwd_kernel(npair, m_ref, feats_ref, acts_ref):
  f = feats_ref[...]  # [S, F, D] bf16
  inter = jax.lax.dot_general(
      f, f, (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)  # [S, F, F]
  i16 = inter.astype(jnp.bfloat16)
  # Mosaic cannot shape-cast [S,F,F]->[S,F*F]; unroll the selection matmul
  # over the p axis instead: acts = sum_p inter[:,p,:] @ M[p]
  acc = jnp.zeros((f.shape[0], npair), jnp.float32)
  for p in range(F):
    acc = acc + jnp.dot(i16[:, p, :], m_ref[p],
                        preferred_element_type=jnp.float32)
  acts_ref[...] = acc


def _fused_bwd_kernel(mt_ref, dacts_ref, feats_ref, dflat_ref, dsym_ref):
  da = dacts_ref[...].astype(jnp.bfloat16)  # [S, npair]
  for p in range(F):
    row = jnp.dot(da, mt_ref[p], preferred_element_type=jnp.float32)
    dsym_ref[:, pl.dslice(p, 1), :] = row[:, None, :]
  f = feats_ref[...]  # [S, F, D]
  d = jax.lax.dot_general(
      dsym_ref[...].astype(jnp.bfloat16), f, (((2,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32)  # [S, F, D]
  dflat_ref[...] = (2.0 * d).astype(dflat_ref.dtype)


def make_fused_acts():
  m_np, _ = _tril_select_np(F, -1)
  npair = m_np.shape[-1]
  m3 = jnp.asarray(m_np, jnp.bfloat16)  # [F, F, npair]
  m3t = jnp.asarray(np.swapaxes(m_np, 1, 2), jnp.bfloat16)  # [F, npair, F]

  @jax.custom_vjp
  def acts_fn(flat):
    a, _ = fwd(flat)
    return a

  def fwd(flat):
    b = flat.shape[0]
    f16 = flat.astype(jnp.bfloat16).reshape(b, F, D)
    acts = pl.pallas_call(
        functools.partial(_fused_fwd_kernel, npair),
        grid=(b // S,),
        in_specs=[
            pl.BlockSpec((F, F, npair), lambda i: (0, 0, 0)),
            pl.BlockSpec((S, F, D), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((S, npair), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, npair), jnp.float32),
    )(m3, f16)
    return acts, f16

  def bwd(f16, d_acts):
    b = f16.shape[0]
    sb = min(128, S)  # f32 scratch + padded constants: keep VMEM bounded
    d_feats = pl.pallas_call(
        _fused_bwd_kernel,
        grid=(b // sb,),
        in_specs=[
            pl.BlockSpec((F, npair, F), lambda i: (0, 0, 0)),
            pl.BlockSpec((sb, npair), lambda i: (i, 0)),
            pl.BlockSpec((sb, F, D), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((sb, F, D), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, F, D), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((sb, F, F), jnp.float32)],
    )(m3t, d_acts, f16)
    return (d_feats.astype(jnp.float32).reshape(b, F * D),)

  acts_fn.defvjp(fwd, bwd)
  return acts_fn


def make_pallas_acts():
  m_np, _ = _tril_select_np(F, -1)
  mflat = jnp.asarray(m_np.reshape(F * F, -1), jnp.bfloat16)

  @jax.custom_vjp
  def acts_fn(flat):
    a, _ = fwd(flat)
    return a

  def fwd(flat):
    b = flat.shape[0]
    feats = flat.astype(jnp.bfloat16).reshape(b, F, D)
    inter = pallas_inter(feats)
    acts = jnp.dot(inter.reshape(b, F * F), mflat,
                   preferred_element_type=jnp.float32)
    return acts, feats

  def bwd(feats, d_acts):
    b = feats.shape[0]
    dsym = jnp.dot(d_acts.astype(jnp.bfloat16), mflat.T,
                   preferred_element_type=jnp.float32)
    d_feats = pallas_dfeats(dsym.astype(jnp.bfloat16).reshape(b, F, F),
                            feats)
    return (d_feats.astype(jnp.float32).reshape(b, F * D),)

  acts_fn.defvjp(fwd, bwd)
  return acts_fn


def _trace_device_ms(tag, step, *args, n=2):
  """Sum device-event time for n traced executions (device time from the
  trace, not wall clock)."""
  import glob
  import gzip
  import json
  tdir = f"/tmp/interact_trace_{tag}_{int(time.time())}"
  out = step(*args)
  jax.block_until_ready(out)
  with jax.profiler.trace(tdir):
    for _ in range(n):
      out = step(*args)
    jax.block_until_ready(out)
  path = sorted(glob.glob(f"{tdir}/plugins/profile/*/*.trace.json.gz"))[-1]
  with gzip.open(path) as f:
    t = json.load(f)
  names = {}
  for e in t.get("traceEvents", []):
    if e.get("ph") == "M" and e.get("name") == "process_name":
      names[e["pid"]] = e["args"]["name"]
  dev_pids = {p for p, nm in names.items() if "TPU" in nm}
  # the top-level module execution events carry the whole-step time
  tot = 0.0
  cnt = 0
  for e in t.get("traceEvents", []):
    if (e.get("ph") == "X" and e.get("pid") in dev_pids
        and e.get("name", "").startswith("jit_")):
      tot += e.get("dur", 0.0)
      cnt += 1
  if os.environ.get("DUMP", "0") == "1":
    from collections import defaultdict
    per = defaultdict(float)
    info = {}
    for e in t.get("traceEvents", []):
      if e.get("ph") == "X" and e.get("pid") in dev_pids:
        per[e.get("name", "?")] += e.get("dur", 0.0)
        a = e.get("args") or {}
        if a.get("long_name"):
          info[e.get("name", "?")] = a["long_name"][:90]
    for nm, us in sorted(per.items(), key=lambda kv: -kv[1])[:14]:
      print(f"    {us/n/1000.0:8.3f} ms  {nm[:40]} {info.get(nm, '')}")
  return tot / max(cnt, 1) / 1000.0


def timeit(name, fn, flat):
  step = jax.jit(jax.value_and_grad(lambda x: jnp.sum(fn(x) ** 2)))
  ms = _trace_device_ms(name.split(":")[0].strip(), step, flat)
  print(f"{name:40s}: {ms:8.2f} ms fwd+bwd (device)", flush=True)
  return step(flat)


def main():
  rng = np.random.default_rng(0)
  flat = jnp.asarray(rng.standard_normal((B, F * D)) * 0.1, jnp.float32)

  base = lambda x: _tril_products(x, F, -1)
  acts_p = make_pallas_acts()

  acts_c = make_fused_acts()

  (l_a, g_a) = timeit("A: XLA matmul-form (production)", base, flat)
  (l_b, g_b) = timeit(f"B: pallas inter+dfeats (S={S})", acts_p, flat)
  (l_c, g_c) = timeit(f"C: pallas fully fused (S={S})", acts_c, flat)

  scale = float(jnp.max(jnp.abs(g_a)))
  for nm, l, g in (("B", l_b, g_b), ("C", l_c, g_c)):
    rel_l = abs(float(l_a) - float(l)) / abs(float(l_a))
    err_g = float(jnp.max(jnp.abs(g_a - g)))
    print(f"parity {nm}: loss rel {rel_l:.2e}; grad max abs err {err_g:.2e} "
          f"(grad scale {scale:.2e})")


if __name__ == "__main__":
  main()
