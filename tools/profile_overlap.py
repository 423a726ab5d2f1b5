"""Benchmark of the host-device overlap scheduler (`pipeline.py`).

Runs the SAME tiered power-law workload twice from identical initial
state — `overlap_host=False` (the serial loop: classify + stage +
dispatch + write-back in line) vs `overlap_host=True` (batch k+1's
classify/gather on the HostWorker while step k runs on device) — and
reports:

  - per-step wall time of both arms, and the reduction;
  - the serial step's host-pipeline vs device split (trace spans — what
    the scheduler CAN hide);
  - the hidden fraction: `tiered/overlap_hidden_s` (job seconds the
    device window absorbed) over `tiered/host_prepare` (total worker
    job seconds);
  - bit-exactness: the two arms' loss streams must be IDENTICAL — the
    overlap is a scheduling change, never a numerics change;
  - worker-track spans: the trace must show `tiered/host_prepare` on
    the `tiered-overlap` worker thread strictly inside a `device/step`
    window (the overlap, visible instead of asserted).

The bench workload is device-heavy on purpose (deep dense MLPs): the
overlap hides host time inside the device window, so the demonstrable
reduction is bounded by min(host, device) / (host + device). The gates
(`--smoke` checks machinery + parity only):

  - wall reduction >= 25%;
  - hidden fraction >= 70%;
  - overlapped wall <= 1.15 x max(host, device)  (the "toward
    max(host, device)" claim with 15% scheduling slack).

Usage: PYTHONPATH=/root/repo python tools/profile_overlap.py [--smoke]
"""

import argparse
import os
import sys
import time

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
  os.environ["XLA_FLAGS"] = (
      flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from distributed_embeddings_tpu import telemetry  # noqa: E402
from distributed_embeddings_tpu.layers.dist_model_parallel import (  # noqa: E402
    get_weights,
    set_weights,
)
from distributed_embeddings_tpu.layers.embedding import TableConfig  # noqa: E402
from distributed_embeddings_tpu.layers.planner import (  # noqa: E402
    DistEmbeddingStrategy,
)
from distributed_embeddings_tpu.models import DLRM, bce_loss  # noqa: E402
from distributed_embeddings_tpu.models.dlrm import _dlrm_initializer  # noqa: E402
from distributed_embeddings_tpu.models.synthetic import power_law_ids  # noqa: E402
from distributed_embeddings_tpu.ops.packed_table import sparse_rule  # noqa: E402
from distributed_embeddings_tpu.parallel import create_mesh  # noqa: E402
from distributed_embeddings_tpu.tiering import (  # noqa: E402
    HostTierStore,
    TieredTrainer,
    TieringConfig,
    TieringPlan,
    init_tiered_state_from_params,
)

WORLD = 4
WIDTH = 16
ALPHA = 1.05

# the serial host-pipeline stages vs the device window, summed from trace
# span durations
HOST_SPANS = ("tiered/classify", "tiered/stage", "tiered/write_back",
              "tiered/rerank")


def make_batches(vocab, batch, n, seed=7):
  r = np.random.default_rng(seed)
  out = []
  for _ in range(n):
    numerical = r.standard_normal((batch, 13)).astype(np.float32)
    cats = [power_law_ids(r, batch, 1, v, ALPHA).astype(np.int32)[:, 0]
            for v in vocab]
    labels = r.integers(0, 2, batch).astype(np.float32)
    out.append((numerical, cats, labels))
  return out


def build_trainer(vocab, batch, mlp, staging, frac, overlap, batch0):
  """One arm: a tiered trainer from DETERMINISTIC params (both arms
  init from the same seeds, so their states — and losses — match)."""
  tables = [TableConfig(input_dim=v, output_dim=WIDTH,
                        initializer=_dlrm_initializer(v)) for v in vocab]
  plan = DistEmbeddingStrategy(tables, WORLD, "memory_balanced",
                               dense_row_threshold=0,
                               host_row_threshold=1000)
  model = DLRM(vocab_sizes=vocab, embedding_dim=WIDTH, bottom_mlp=mlp[0],
               top_mlp=mlp[1], world_size=WORLD,
               strategy="memory_balanced", dense_row_threshold=0)
  mesh = create_mesh(WORLD)
  rule = sparse_rule("adagrad", 0.05)
  opt = optax.adam(1e-3)
  params_b = model.init(jax.random.PRNGKey(0), batch0[0],
                        batch0[1])["params"]
  plan_b = DistEmbeddingStrategy(tables, WORLD, "memory_balanced",
                                 dense_row_threshold=0)
  tables_t = set_weights(plan, get_weights(plan_b, params_b["embeddings"]))
  params = {k: v for k, v in params_b.items() if k != "embeddings"}
  params["embeddings"] = {k: jnp.asarray(v) for k, v in tables_t.items()}
  tplan = TieringPlan(plan, rule, TieringConfig(cache_fraction=frac,
                                                staging_grps=staging,
                                                rerank_interval=0))
  store = HostTierStore(tplan)
  from distributed_embeddings_tpu.training import shard_params
  state = shard_params(init_tiered_state_from_params(
      tplan, store, rule, params, opt, mesh=mesh), mesh)
  return TieredTrainer(model, tplan, store, bce_loss, opt, rule, mesh,
                       state, batch0, donate=False, overlap_host=overlap)


def timed_window(trainer, batches):
  """Run one traced, wall-clocked window; returns (losses, wall_s,
  chrome_trace)."""
  tracer = telemetry.Tracer()
  telemetry.install_tracer(tracer)
  try:
    t0 = time.perf_counter()
    losses = trainer.run(batches)
    wall = time.perf_counter() - t0
  finally:
    telemetry.uninstall_tracer()
  return losses, wall, tracer.to_chrome()


def span_ms_per_step(chrome, names, n_steps):
  tot = sum(e["dur"] for e in chrome["traceEvents"]
            if e.get("ph") == "X" and e["name"] in names)
  return tot / n_steps / 1e3  # trace ts/dur are in microseconds


def worker_overlap_spans(chrome):
  """Count `tiered/host_prepare` spans on the worker thread strictly
  inside a `device/step` window."""
  tracks = {e["tid"]: e["args"]["name"] for e in chrome["traceEvents"]
            if e.get("name") == "thread_name"}
  worker_tids = {t for t, n in tracks.items() if n == "tiered-overlap"}
  device_tids = {t for t, n in tracks.items() if n == "device"}
  dev = [e for e in chrome["traceEvents"] if e.get("ph") == "X"
         and e["name"] == "device/step" and e["tid"] in device_tids]
  inside = 0
  for c in (e for e in chrome["traceEvents"] if e.get("ph") == "X"
            and e["name"] == "tiered/host_prepare"
            and e["tid"] in worker_tids):
    if any(d["ts"] < c["ts"] and c["ts"] + c["dur"] < d["ts"] + d["dur"]
           for d in dev):
      inside += 1
  return inside


def run(smoke: bool) -> dict:
  if smoke:
    vocab, batch, steps, warm = [2000, 300, 40], 64, 8, 3
    mlp, staging, frac = ((32, WIDTH), (32, 1)), 64, 0.3
  else:
    vocab, batch, steps, warm = [200_000, 20_000, 300], 256, 20, 4
    # device-heavy dense stack: the overlap hides the host pipeline
    # inside a device window big enough to hold it
    mlp, staging, frac = ((1024, 512, WIDTH), (1024, 512, 1)), 2048, 0.15
  batches = make_batches(vocab, batch, warm + steps)
  result = {"world": WORLD, "vocab": vocab, "batch": batch,
            "steps": steps, "alpha": ALPHA}

  reg = telemetry.get_registry()
  arms = {}
  for name, overlap in (("serial", False), ("overlap", True)):
    t = build_trainer(vocab, batch, mlp, staging, frac, overlap,
                      batches[0])
    t.run(batches[:warm])  # compile + residency warmup outside the clock
    h0 = (reg.histogram("tiered/overlap_hidden_s").sum,
          reg.histogram("tiered/host_prepare").sum)
    losses, wall, chrome = timed_window(t, batches[warm:])
    arms[name] = {
        "losses": losses, "wall_ms": wall / steps * 1e3, "chrome": chrome,
        "hidden_s": reg.histogram("tiered/overlap_hidden_s").sum - h0[0],
        "job_s": reg.histogram("tiered/host_prepare").sum - h0[1],
    }

  ser, ovl = arms["serial"], arms["overlap"]
  host_ms = span_ms_per_step(ser["chrome"], HOST_SPANS, steps)
  dev_ms = span_ms_per_step(ser["chrome"], ("device/step",), steps)
  parity = bool(np.array_equal(np.asarray(ser["losses"]),
                               np.asarray(ovl["losses"])))
  reduction = 1.0 - ovl["wall_ms"] / ser["wall_ms"]
  hidden_frac = (ovl["hidden_s"] / ovl["job_s"]) if ovl["job_s"] else 0.0
  bound_ms = 1.15 * max(host_ms, dev_ms)
  spans_inside = worker_overlap_spans(ovl["chrome"])
  result.update({
      "serial_ms": ser["wall_ms"], "overlap_ms": ovl["wall_ms"],
      "host_ms": host_ms, "device_ms": dev_ms,
      "reduction": reduction, "hidden_frac": hidden_frac,
      "bound_ms": bound_ms,
      "worker_spans_inside_device_window": spans_inside,
      "losses_bit_exact": parity,
  })
  if smoke:
    # machinery gates only: CPU-mesh step times at toy scale are noise
    result["ok"] = bool(parity and spans_inside > 0
                        and np.isfinite(reduction) and ovl["job_s"] > 0)
  else:
    result["ok"] = bool(parity and spans_inside > 0
                        and reduction >= 0.25
                        and hidden_frac >= 0.70
                        and ovl["wall_ms"] <= bound_ms)
  return result


if __name__ == "__main__":
  ap = argparse.ArgumentParser()
  ap.add_argument("--smoke", action="store_true",
                  help="tiny tier for make verify (parity + worker "
                       "spans only; no perf gates)")
  args = ap.parse_args()
  res = run(smoke=args.smoke)
  res.pop("chrome", None)
  sys.exit(telemetry.emit_verdict(
      "overlap-smoke" if args.smoke else "overlap-bench", res))
