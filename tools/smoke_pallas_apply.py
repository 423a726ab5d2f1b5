"""Real-TPU smoke test for the Pallas RMW apply kernel.

Runs the directed duplicate/eviction/OOB cases plus a randomized power-law
check against XLA's scatter-add ON THE REAL CHIP (the kernel's DMA
aliasing semantics cannot be validated in interpret mode: interpret does
not alias input and output buffers, so reads see stale data).

Run: python tools/smoke_pallas_apply.py   (leg B of chip_smoke.py)
Exit code 0 = all cases pass; non-zero on any failure AND on a backend
that is not a TPU (there is nothing to validate off the chip).
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.compile_cache import enable_compile_cache
from distributed_embeddings_tpu.ops.pallas_apply import apply_rows_cached
from distributed_embeddings_tpu.parallel.mesh import require_tpu

W = 128
FAILED = []


def check(name, ids, rows=16, slots=4, chunk=128):
  ids = jnp.asarray(np.asarray(ids, np.int32))
  n = ids.shape[0]
  delta = jnp.arange(1, n + 1, dtype=jnp.float32)[:, None] \
      * jnp.ones((n, W), jnp.float32)
  clip = jnp.where((ids >= 0) & (ids < rows), ids, rows)
  want = jnp.zeros((rows + 1, W), jnp.float32).at[clip].add(delta)[:rows]
  got = apply_rows_cached(jnp.zeros((rows, W), jnp.float32), ids, delta,
                          slots=slots, chunk=chunk)
  ok = bool(jnp.allclose(got, want, atol=1e-5))
  print(f"{name:34s}: {'OK' if ok else 'FAIL'}")
  if not ok:
    FAILED.append(name)


def main():
  print("device:", json.dumps(require_tpu("smoke_pallas_apply")), flush=True)
  # The shared golden vectors (tests/pallas_goldens.py): the SAME
  # streams tier-1 runs through the numpy simulator, replayed here at
  # the kernel's 128-lane width against XLA's scatter AND against the
  # simulator — a hardware/sim divergence fails with a case name CI
  # already knows.
  sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                  "tests"))
  from pallas_goldens import CASE_NAMES, apply_vectors
  from distributed_embeddings_tpu.ops.pallas_apply_sim import (
      apply_rows_cached_sim,
  )
  for name in CASE_NAMES:
    buf, ids, delta, slots, _ = apply_vectors(name, width=W)
    got = apply_rows_cached(jnp.asarray(buf), jnp.asarray(ids),
                            jnp.asarray(delta), slots=slots)
    want = np.array(buf, np.float32)
    okm = (ids >= 0) & (ids < buf.shape[0])
    np.add.at(want, ids[okm], delta[okm])
    sim = apply_rows_cached_sim(buf, ids.astype(np.int64), delta,
                                slots=slots)
    err_xla = float(np.max(np.abs(np.asarray(got) - want)))
    err_sim = float(np.max(np.abs(np.asarray(got) - sim)))
    ok = err_xla < 1e-4 and err_sim < 1e-4
    print(f"golden:{name:27s}: {'OK' if ok else 'FAIL'} "
          f"(xla {err_xla:.2e}, sim {err_sim:.2e})")
    if not ok:
      FAILED.append(f"golden:{name}")
  # genuinely multi-grid-step: n > 8192 forces several chunks at
  # chunk=8192, with duplicates recurring across grid-step boundaries
  # (exercises c==0-only init and tag/wbuf persistence across steps)
  cross = (list(range(100)) * 100)[:10000]
  check("cross-chunk duplicates", cross, rows=128, slots=16, chunk=8192)

  rng = np.random.default_rng(0)
  rows, n = 1 << 18, 1 << 17
  base = jnp.asarray(rng.standard_normal((rows, W)), jnp.float32)
  ids = np.concatenate([rng.integers(0, rows, n // 2),
                        rng.zipf(1.3, n // 2) % rows]).astype(np.int32)
  rng.shuffle(ids)
  delta = jnp.asarray(rng.standard_normal((n, W)), jnp.float32)
  # The hottest row here takes ~16k occurrences, so two correct f32
  # implementations differ by their summation order alone: a sequential
  # f32 sum sits 1.1e-4 from the exact answer, the kernel (which sums a
  # run of hits in its cache before touching the row) 1.3e-5. Each is
  # therefore judged against a float64 host reference — the kernel at
  # 1e-4, XLA's scatter (whatever order this libtpu sums in) at 1e-3.
  base64 = np.asarray(base, np.float64)
  delta64 = np.asarray(delta, np.float64)
  ids_j = jnp.asarray(ids)

  def rel_err(got, want64):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want64)
                        / (1 + np.abs(want64))))

  for label, scale in (("randomized power-law", None),
                       ("in-kernel scale", -0.125)):
    want64 = base64.copy()
    np.add.at(want64, ids, delta64 * (1.0 if scale is None else scale))
    got = apply_rows_cached(
        base + 0, ids_j, delta,
        scale=None if scale is None else jnp.float32(scale))
    xla = base.at[ids_j].add(delta if scale is None else scale * delta)
    err, err_xla = rel_err(got, want64), rel_err(xla, want64)
    ok = err < 1e-4 and err_xla < 1e-3
    print(f"{label + ' vs f64':34s}: {'OK' if ok else 'FAIL'} "
          f"(kernel rel err {err:.2e}, XLA scatter {err_xla:.2e})")
    if not ok:
      FAILED.append(label)

  # narrow-class dispatch: lane-expanded sub-row deltas through the same
  # kernel at physical-row granularity (scatter_add_fused with rpp > 1).
  # The (128, 1) case is the 256-lane physical layout Mosaic cannot
  # serve (1-row dynamic slices of multi-tile rows); scatter_add_fused
  # must route it to XLA — the case asserts the fallback's correctness
  # under forced-kernel env (the gate must win over the force).
  from distributed_embeddings_tpu.ops.packed_table import (
      PackedLayout, scatter_add_fused)
  for width, n_aux in ((16, 1), (8, 1), (32, 1), (16, 0), (128, 1)):
    layout = PackedLayout(rows=4096, width=width, n_aux=n_aux)
    nids = 2048
    ids_n = jnp.asarray(rng.integers(-2, layout.rows + 2, nids), jnp.int32)
    delta_n = jnp.asarray(rng.standard_normal((nids, layout.stride)),
                          jnp.float32)
    base_n = jnp.asarray(rng.standard_normal(layout.shape), jnp.float32)
    # independent numpy reference built straight from the layout (for
    # the 256-lane (128,1) case the kernel gate sends BOTH env settings
    # to the XLA fallback, so an XLA-vs-XLA comparison would be vacuous)
    rpp = layout.rows_per_phys
    want_np = np.asarray(base_n).copy()
    ids_host = np.asarray(ids_n)
    delta_host = np.asarray(delta_n)  # ONE device fetch, not 2048
    for i, lid in enumerate(ids_host):
      if 0 <= lid < layout.rows:
        grp, sub = divmod(int(lid), rpp)
        lo = sub * layout.stride
        want_np[grp, lo:lo + layout.stride] += delta_host[i]
    want = jnp.asarray(want_np)
    saved = os.environ.get("DE_TPU_PALLAS_APPLY")
    os.environ["DE_TPU_PALLAS_APPLY"] = "0"   # the XLA path
    got_xla = scatter_add_fused(layout, base_n + 0, ids_n, delta_n)
    os.environ["DE_TPU_PALLAS_APPLY"] = "1"   # the kernel (gated wide)
    got = scatter_add_fused(layout, base_n + 0, ids_n, delta_n)
    err_xla = float(jnp.max(jnp.abs(got_xla - want)))
    if err_xla > 1e-4:
      print(f"{'XLA fallback w%d aux%d' % (width, n_aux):34s}: FAIL "
            f"(max err {err_xla:.2e})")
      FAILED.append(f"xla w{width}")
    if saved is None:
      del os.environ["DE_TPU_PALLAS_APPLY"]
    else:
      os.environ["DE_TPU_PALLAS_APPLY"] = saved
    err = float(jnp.max(jnp.abs(got - want)))
    ok = err < 1e-4
    print(f"{'narrow w%d aux%d kernel vs XLA' % (width, n_aux):34s}: "
          f"{'OK' if ok else 'FAIL'} (max err {err:.2e})")
    if not ok:
      FAILED.append(f"narrow w{width}")

  check_heads(rng)

  if FAILED:
    print("FAILED:", FAILED)
    sys.exit(1)
  print("ALL PASS")


def check_heads(rng):
  """VMEM-resident heads against ``buf.at[ids].add``: the DLRM cells'
  power-law stream (rank = id, most ids in a table's first rows) and a
  uniform one (hardly any), on one device and under ``shard_map`` with
  starts that differ from rank to rank; a short table whose block runs on
  into its neighbour; a stream of head ids only, which leaves every warm
  slot of the row cache unclaimed (the write-back order's hazard)."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P

  from distributed_embeddings_tpu.compat import shard_map
  from distributed_embeddings_tpu.models.synthetic import power_law_ids
  from distributed_embeddings_tpu.ops.pallas_apply import (
      HEAD_PAD, HEAD_ROWS, head_block_starts)
  from distributed_embeddings_tpu.parallel import create_mesh

  def rel_err(got, want64):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want64)
                        / (1 + np.abs(want64))))

  def stream(tables, per_table, alpha):
    """Per rank-local table ``(offset, rows)``: ids as the step's stream
    has them, tables one after another, plus a few out of range."""
    ids = np.concatenate(
        [off + power_law_ids(rng, per_table, 1, rows, alpha)[:, 0]
         for off, rows in tables] + [np.array([-1, 1 << 30, -7])])
    return ids.astype(np.int32)

  def want_of(base, ids, delta, scale):
    want = np.asarray(base, np.float64)
    ok = (ids >= 0) & (ids < want.shape[0])
    np.add.at(want, ids[ok], scale * np.asarray(delta, np.float64)[ok])
    return want

  scale = -0.125
  rows = 3 * HEAD_ROWS + 5000
  # rank-local layouts: two long tables; a 1,000-row table before a long one
  layouts = {
      "two long tables": [(0, HEAD_ROWS + 3000), (HEAD_ROWS + 3000,
                                                  2 * HEAD_ROWS + 2000)],
      "short table first": [(0, 1000), (1000, rows - 1000)],
  }
  base = jnp.asarray(rng.standard_normal((rows, W)), jnp.float32)
  for lname, tables in layouts.items():
    starts = head_block_starts(
        [(off, off + min(HEAD_ROWS, n)) for off, n in tables], rows)
    for sname, alpha in (("power-law", 1.05), ("uniform", 0.0)):
      ids = stream(tables, 20000, alpha)
      delta = jnp.asarray(rng.standard_normal((len(ids), W)), jnp.float32)
      got = apply_rows_cached(base + 0, jnp.asarray(ids), delta,
                              scale=jnp.float32(scale),
                              head_starts=jnp.asarray(starts, jnp.int32))
      err = rel_err(got, want_of(base, ids, delta, scale))
      name = f"heads {lname}, {sname}"
      ok = err < 1e-4
      print(f"{name:34s}: {'OK' if ok else 'FAIL'} (rel err {err:.2e}, "
            f"blocks at {starts})")
      if not ok:
        FAILED.append(name)

  # only head ids, fewer than the cache has slots: no warm slot is claimed,
  # each flushes the row it read at start-up, and the heads must land after
  ids = np.array([1, 1, 3, HEAD_ROWS + 3001, 127, 0])
  ids = ids.astype(np.int32)
  delta = jnp.asarray(rng.standard_normal((len(ids), W)), jnp.float32)
  starts = head_block_starts([(0, HEAD_ROWS), (HEAD_ROWS + 3000,
                                               2 * HEAD_ROWS + 3000)], rows)
  got = apply_rows_cached(base + 0, jnp.asarray(ids), delta,
                          head_starts=jnp.asarray(starts, jnp.int32))
  err = rel_err(got, want_of(base, ids, delta, 1.0))
  ok = err < 1e-5
  print(f"{'heads, warm slots never claimed':34s}: {'OK' if ok else 'FAIL'} "
        f"(rel err {err:.2e})")
  if not ok:
    FAILED.append("heads warm")

  # under shard_map: every rank its own block of the buffer, its own
  # tables and so its own starts (a constant indexed by the rank, as the
  # engine passes them); rank r's first table is r * 512 rows longer
  world = jax.device_count()
  mesh = create_mesh(world)
  per_rank = [[(0, HEAD_ROWS + 512 * r + 8),
               (HEAD_ROWS + 512 * r + 8, rows - HEAD_ROWS - 512 * r - 8)]
              for r in range(world)]
  starts = [head_block_starts([(o, o + HEAD_ROWS) for o, _ in t], rows)
            for t in per_rank]
  const = np.full((world, 3), HEAD_PAD, np.int32)  # one entry is padding
  for r, st in enumerate(starts):
    const[r, :len(st)] = st
  n = 30000 + 3
  ids = np.stack([stream(t, 15000, 1.05) for t in per_rank])
  delta = jnp.asarray(rng.standard_normal((world, n, W)), jnp.float32)
  bases = jnp.asarray(rng.standard_normal((world * rows, W)), jnp.float32)

  def local(buf, ids, delta):
    st = jnp.asarray(const)[jax.lax.axis_index("mp")]
    return apply_rows_cached(buf, ids[0], delta[0],
                             scale=jnp.float32(scale), head_starts=st)

  fn = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("mp"),) * 3,
                         out_specs=P("mp")))
  shard = NamedSharding(mesh, P("mp"))
  got = np.asarray(fn(jax.device_put(bases + 0, shard),
                      jax.device_put(jnp.asarray(ids), shard),
                      jax.device_put(delta, shard)))
  err = max(rel_err(got[r * rows:(r + 1) * rows],
                    want_of(bases[r * rows:(r + 1) * rows], ids[r],
                            delta[r], scale)) for r in range(world))
  ok = err < 1e-4
  print(f"{'heads under shard_map, world %d' % world:34s}: "
        f"{'OK' if ok else 'FAIL'} (rel err {err:.2e}, starts "
        f"{const.tolist()})")
  if not ok:
    FAILED.append("heads shard_map")


if __name__ == "__main__":
  enable_compile_cache()
  main()
