"""Measure the primitives for a hot/cold-split power-law sparse path.

The Tiny/Small synthetic models are per-occurrence row-op bound
(docs/BENCHMARKS.md): 3.87M occurrences/step each pay ~19 ns gather +
~23 ns scatter + staging. Their power-law streams concentrate: with
alpha=1.05, ids < K cover ~47% (K=512) to ~63% (K=8192) of occurrences.
This tool measures every primitive a frequency-aware split would be built
from, on the REAL generator streams:

  1. full-stream fused scatter (today's apply)           [baseline]
  2. scatter with hot ids dropped (OOB sentinel)         [cold apply, no compaction]
  3. scatter on a compacted cold-only stream             [cold apply, compacted]
  4. masked one-hot head matmul fwd / fwd+bwd vs K       [hot fwd + hot apply]
  5. on-device cold compaction (searchsorted + gather)   [stream building]
  6. phys-row gather + bag-sum vs fused sub-row gather   [fwd extraction removal]
  7. cold-compacted fused gather + segment-sum combine   [cold fwd]

Usage: PYTHONPATH=/root/repo python tools/profile_hotcold.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.models.synthetic import power_law_ids
from distributed_embeddings_tpu.ops.packed_table import (
    PackedLayout,
    adagrad_rule,
    gather_fused,
)

B = 65536
ALPHA = 1.05
K_REPS = 6

# Tiny's 16-wide sparse class: (vocab, n_inputs_1hot, n_inputs_10hot)
TINY_W16 = [
    (1_000_000, 20, 1),   # 19 plain + 1 shared(1,10)
    (25_000_000, 2, 1),   # shared(1,10) + plain 1-hot
    (100_000, 2, 0),
]
RULE = adagrad_rule(0.01)
LAYOUT = PackedLayout(rows=52_200_000, width=16, n_aux=1)  # ~Tiny class rows


def build_class_stream(rng):
  """Concatenated routed id stream for the w16 class (logical ids)."""
  parts = []
  off = 0
  offsets = []
  for vocab, n1, n10 in TINY_W16:
    offsets.append((off, vocab))
    for _ in range(n1):
      parts.append(power_law_ids(rng, B, 1, vocab, ALPHA).ravel() + off)
    for _ in range(n10):
      parts.append(power_law_ids(rng, B, 10, vocab, ALPHA).ravel() + off)
    off += vocab
  return np.concatenate(parts).astype(np.int32), offsets


def _sync(x):
  jax.block_until_ready(x)


def timeit(name, fn, buf, *args, donate=True, n_norm=None):
  """Chained donated steps, two chain lengths differenced. Returns carry so
  callers can keep the live end of a donated chain (the input is consumed)."""
  step = jax.jit(fn, donate_argnums=(0,) if donate else ())
  carry = step(buf, *args)
  _sync(carry)

  def run(n, carry):
    t0 = time.perf_counter()
    for _ in range(n):
      carry = step(carry, *args)
    _sync(carry)
    return time.perf_counter() - t0, carry

  _, carry = run(1, carry)
  t1, carry = run(K_REPS, carry)
  t2, carry = run(2 * K_REPS, carry)
  dt = (t2 - t1) / K_REPS
  per = f"  {dt / n_norm * 1e9:6.1f} ns/elem" if n_norm else ""
  print(f"{name:54s}: {dt * 1e3:8.2f} ms{per}", flush=True)
  return carry


def hot_mask_np(ids, offsets, k):
  m = np.zeros(ids.shape, bool)
  for off, vocab in offsets:
    kk = min(k, vocab)
    m |= (ids >= off) & (ids < off + kk)
  return m


def main():
  rng = np.random.default_rng(0)
  ids_np, offsets = build_class_stream(rng)
  n = ids_np.shape[0]
  rpp = LAYOUT.rows_per_phys
  print(f"class stream: {n} occurrences, rpp={rpp}, "
        f"phys_rows={LAYOUT.phys_rows}")
  for k in (512, 4096, 65536):
    cov = hot_mask_np(ids_np, offsets, k).mean()
    print(f"  coverage ids<K per table, K={k}: {cov:.3f}")

  grp_np = (ids_np // rpp).astype(np.int32)
  upd = jnp.asarray(rng.standard_normal((n, 128)).astype(np.float32) * 1e-6)

  def scatter(b, g, u):
    return b.at[g].add(u, mode="drop")

  def fresh_buf():
    return jnp.zeros((LAYOUT.phys_rows + 1, 128), jnp.float32)

  # 1. baseline full stream
  carry = timeit("scatter full stream (today)", scatter, fresh_buf(),
                 jnp.asarray(grp_np), upd, n_norm=n)
  print(f"  checksum {float(jnp.sum(carry[:8, :4])):.3e}")
  del carry

  # 2. hot ids dropped via OOB sentinel: cold apply without compaction
  for k in (512, 4096, 65536):
    hot = hot_mask_np(ids_np, offsets, k)
    grp_drop = jnp.asarray(np.where(hot, np.int32(2**31 - 1), grp_np))
    c = timeit(f"scatter hot->dropped (K={k}, cold={1-hot.mean():.2f})",
               scatter, fresh_buf(), grp_drop, upd, n_norm=n)
    del c, grp_drop

  # 2b. hot ids redirected to one dummy row (keeps stream, mega-dup)
  hot = hot_mask_np(ids_np, offsets, 4096)
  grp_dummy = jnp.asarray(np.where(hot, np.int32(LAYOUT.phys_rows), grp_np))
  c = timeit("scatter hot->dummy row (K=4096)", scatter, fresh_buf(),
             grp_dummy, upd, n_norm=n)
  del c, grp_dummy

  # 3. compacted cold-only stream
  for k in (512, 4096, 65536):
    hot = hot_mask_np(ids_np, offsets, k)
    cold_ids = grp_np[~hot]
    cn = cold_ids.shape[0]
    c = timeit(f"scatter cold-compacted (K={k}, n={cn})", scatter,
               fresh_buf(), jnp.asarray(cold_ids), upd[:cn], n_norm=cn)
    del c

  del upd

  # 4. masked one-hot head matmul: fwd and fwd+bwd, per K.
  #    All occurrences flow through (cold ids one-hot to zero), like a
  #    dense-class window. Chunked like _z_dense to bound staging.
  ids_dev = jnp.asarray(ids_np)
  # local ids for a single concatenated head of size K*len(tables): use
  # per-table local id minus offset; cold -> -1 (no one-hot)
  for k in (256, 512, 1024):
    local = np.full(n, -1, np.int32)
    base = 0
    for off, vocab in offsets:
      kk = min(k, vocab)
      sel = (ids_np >= off) & (ids_np < off + kk)
      local[sel] = ids_np[sel] - off + base
      base += kk
    head_rows = base
    local_dev = jnp.asarray(local)
    head = jnp.asarray(
        rng.standard_normal((head_rows, 16)).astype(np.float32))

    def z_head(h, ids_l):
      chunk = max(1, (1 << 25) // h.shape[0])
      nchunks = -(-n // chunk)
      pad = nchunks * chunk - n
      idsp = jnp.concatenate([ids_l, jnp.full((pad,), -1, jnp.int32)])

      def body(c, i):
        oh = jax.nn.one_hot(i, h.shape[0], dtype=jnp.bfloat16)
        z = jnp.einsum("gv,vw->gw", oh, h,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        return c, z

      _, zs = jax.lax.scan(jax.checkpoint(body), None,
                           idsp.reshape(nchunks, chunk))
      return zs.reshape(-1, 16)[:n]

    def fwd_only(h, ids_l):
      z = z_head(h, ids_l)
      return h + 1e-12 * jnp.tanh(jnp.sum(z))  # non-linear consumer

    head = timeit(f"one-hot head fwd (K={k}, rows={head_rows})", fwd_only,
                  head, local_dev, n_norm=n)

    def fwd_bwd(h, ids_l):
      def loss(hh):
        z = z_head(hh, ids_l)
        return jnp.sum(jnp.tanh(z * 1e-3))
      g = jax.grad(loss)(h)
      return h - 1e-9 * g

    timeit(f"one-hot head fwd+bwd (K={k}, rows={head_rows})", fwd_bwd, head,
           local_dev, n_norm=n)
    del head

  # 5. on-device cold compaction: counts -> cumsum -> searchsorted -> gather
  cold_cap = int(n * 0.7)
  hot = hot_mask_np(ids_np, offsets, 4096)

  def compact(carry, ids_f):
    is_cold = ids_f < 0  # placeholder predicate; realistic: table-local < K
    # use a real predicate over concatenated offsets: approximate with two
    # range tests per table region (3 regions)
    m = jnp.zeros(ids_f.shape, bool)
    base = 0
    for off, vocab in offsets:
      kk = min(4096, vocab)
      m = m | ((ids_f >= off) & (ids_f < off + kk))
      base += kk
    is_cold = ~m
    csum = jnp.cumsum(is_cold.astype(jnp.int32))
    total = csum[-1]
    # positions of cold elements: searchsorted over csum for 1..cap
    tgt = jnp.arange(1, cold_cap + 1, dtype=jnp.int32)
    src = jnp.searchsorted(csum, tgt)
    vals = jnp.take(ids_f, jnp.clip(src, 0, n - 1), mode="clip")
    vals = jnp.where(tgt <= total, vals, -1)
    return carry + jnp.sum(vals == -12345), None

  def compact_step(carry, ids_f):
    c, _ = compact(carry, ids_f + (carry * 0).astype(jnp.int32))
    return c

  timeit("device compaction (mask+cumsum+searchsorted+take)",
         compact_step, jnp.zeros((), jnp.int32), ids_dev, donate=False,
         n_norm=n)

  # 6. phys-row gather + window-sum (10-hot bags) vs fused sub-row gather
  buf_g = jnp.zeros((LAYOUT.phys_rows + 1, 128), jnp.float32)
  ids10 = jnp.asarray(
      power_law_ids(rng, B, 10, 25_000_000, ALPHA).astype(np.int32)
      + 21_000_000)
  n10 = B * 10

  def fused_gather(c, idsb):
    idsb = idsb + (c * 0).astype(jnp.int32)
    rows = gather_fused(LAYOUT, buf_g, idsb)  # [B, 10, 32]
    z = jnp.sum(rows[..., :16], axis=1)
    return c + jnp.tanh(jnp.sum(z) * 1e-6) * 0 + jnp.float32(0)

  def phys_gather(c, idsb):
    idsb = idsb + (c * 0).astype(jnp.int32)
    grp_b = idsb // rpp
    rows = jnp.take(buf_g, grp_b, axis=0, mode="fill",
                    fill_value=0)  # [B, 10, 128]
    bag = jnp.sum(rows, axis=1)  # [B, 128]
    z = jnp.sum(bag.reshape(B, rpp, 32)[..., :16], axis=1)
    return c + jnp.tanh(jnp.sum(z) * 1e-6) * 0 + jnp.float32(0)

  timeit("fused sub-row gather 10-hot (today)", fused_gather,
         jnp.zeros((), jnp.float32), ids10, donate=False, n_norm=n10)
  timeit("phys-row gather + bag-sum 10-hot (BUT: wrong for "
         "sub-row-aliased bags? no - sum commutes)", phys_gather,
         jnp.zeros((), jnp.float32), ids10, donate=False, n_norm=n10)

  # 7. cold fused gather + segment-sum combine on a compacted ragged stream
  cold_ids10 = ids_np[~hot][:B * 4]  # ~4 cold per bag stand-in
  seg = np.sort(rng.integers(0, B, cold_ids10.shape[0])).astype(np.int32)
  cold_d = jnp.asarray(cold_ids10)
  seg_d = jnp.asarray(seg)
  nc = cold_ids10.shape[0]

  def cold_fwd(c, idsb, segb):
    idsb = idsb + (c * 0).astype(jnp.int32)
    rows = gather_fused(LAYOUT, buf_g, idsb)[:, :16]
    z = jax.ops.segment_sum(rows, segb, num_segments=B)
    return c + jnp.tanh(jnp.sum(z) * 1e-6) * 0 + jnp.float32(0)

  timeit(f"cold compacted gather+segsum (n={nc})", cold_fwd,
         jnp.zeros((), jnp.float32), cold_d, seg_d, donate=False, n_norm=nc)
  del buf_g

  # 8. WINDOW gather/scatter with 2-D (row, lane) starts: reads/writes the
  #    32-lane fused sub-row directly from/to the packed buffer — would kill
  #    both the gather-side extraction einsum and the apply-side expansion.
  stride = LAYOUT.stride  # 32
  grp_all = jnp.asarray(grp_np)
  # (id % rpp) * stride < 128 lanes of one physical row
  lane = jnp.asarray(((ids_np % rpp) * stride)  # graftlint: disable=GL106
                     .astype(np.int32))
  starts = jnp.stack([grp_all, lane], axis=1)  # [n, 2]
  bufw = jnp.zeros((LAYOUT.phys_rows + 1, 128), jnp.float32)

  gdn = jax.lax.GatherDimensionNumbers(
      offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0, 1))

  def win_gather(c, st):
    st = st + (c * 0).astype(jnp.int32)
    rows = jax.lax.gather(
        bufw, st, gdn, slice_sizes=(1, stride),
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
    return c + jnp.tanh(jnp.sum(rows) * 1e-6) * 0 + jnp.float32(0)

  timeit("window-gather 2-D starts [n,32]", win_gather,
         jnp.zeros((), jnp.float32), starts, donate=False, n_norm=n)

  sdn = jax.lax.ScatterDimensionNumbers(
      update_window_dims=(1,), inserted_window_dims=(0,),
      scatter_dims_to_operand_dims=(0, 1))
  upd32 = jnp.asarray(
      rng.standard_normal((n, stride)).astype(np.float32) * 1e-6)

  def win_scatter(b, st, u):
    return jax.lax.scatter_add(
        b, st, u, sdn, mode=jax.lax.GatherScatterMode.FILL_OR_DROP)

  c = timeit("window-scatter-add 2-D starts [n,32]", win_scatter, bufw,
             starts, upd32, n_norm=n)
  print(f"  checksum {float(jnp.sum(c[:64, :4])):.3e}")
  del c

  # 9. re-run the full-stream baseline at the end (first-test artifact)
  upd = jnp.asarray(rng.standard_normal((n, 128)).astype(np.float32) * 1e-6)
  c = timeit("scatter full stream (today, re-run)", scatter,
             jnp.zeros((LAYOUT.phys_rows + 1, 128), jnp.float32),
             jnp.asarray(grp_np), upd, n_norm=n)
  del c


if __name__ == "__main__":
  main()
