"""The quickest proof that the system still starts on the chip.

Drives the main path — the DLRM sparse trainer, through its own command
line — on a TPU at Criteo width, and fails unless what came out is right:

- leg B: the Pallas kernels against XLA on the chip
  (``tools/smoke_pallas_apply.py``: the apply kernel with and without its
  VMEM-resident heads, on a power-law and on a uniform stream, on one
  device and under ``shard_map`` with starts that differ from rank to rank;
  ``tools/smoke_pallas_interact.py``; ``tools/smoke_pallas_moe_combine.py``:
  the expert layer's combine kernel against the scatter-add at a small shape
  and at a cell's, and its ``custom_vjp`` pair through ``jax.grad``);
- leg D: the sparse-attention kernels (``ops/pallas_sparse_attn.py``)
  against the XLA tile loop at the shapes of ``keye_dsa_train_1chip``
  (``tools/smoke_pallas_sparse_attn.py``: 8,192 x 32 x 128, ``topk`` 2,048,
  two documents; output, the indexer's loss, counters and six gradients,
  outside any timed window), then that cell's model forward on the
  benchmark's own weights and traffic, seed 2147483659, through the kernels
  (``tools/sparse_index_load.py --reference``): every layer selects the
  14,681,088 pairs the plain reference and the documents' own counts give,
  and the blocks attended and skipped add up to the grid;
- leg A, one chip: ``examples/dlrm/main.py --sparse`` at 26 Criteo-1TB
  tables x 1/16 (11.8 M rows), width 128, global batch 65536, 8 steps and
  an eval. Passes only if it ran on a TPU, every loss is finite, the first
  lies in [0.6, 0.8] (dummy labels are coin flips), the AUC is finite, and
  the COMPILED train step contains the Mosaic kernels named below;
- leg C, four chips (when the machine shows >= 4 TPU devices): the same
  at vocabulary x 1/4 — 24 GiB of tables, more than one chip holds — plus:
  after initialisation no chip's peak memory exceeds twice what it ends up
  holding, and chip 0 holds no more than one rank's share of the plan,
  i.e. the state was born sharded.

A chip belongs to one process, so this parent never imports JAX and runs
the legs one after another, each in a process of its own with
``JAX_PLATFORMS=tpu``: a machine with no chip makes JAX raise there
instead of continuing on the CPU. There is no CPU mode. Each leg's full
output is kept under ``chiprun_out/chip_smoke/``.

Exit code 0 only if every leg that ran passed. Stdout carries one line per
leg, a ``summary:`` line with each leg's status, and as its last line (on a
failed leg too) exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
With no accelerator, or outside a checkout of the repository, it exits 2
and prints no result.
"""

import json
import math
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
MAIN = os.path.join("examples", "dlrm", "main.py")
KERNEL_SMOKES = (os.path.join("tools", "smoke_pallas_apply.py"),
                 os.path.join("tools", "smoke_pallas_interact.py"),
                 os.path.join("tools", "smoke_pallas_moe_combine.py"))

SPARSE_ATTN_SMOKE = os.path.join("tools", "smoke_pallas_sparse_attn.py")
INDEX_LOAD = os.path.join("tools", "sparse_index_load.py")
INDEX_LOAD_SEED = 2147483659
# a layer, on that seed: what the tool printed on the CPU, the plain
# reference's count and the documents' own (PERF.md section 6, PR 40)
SELECTED_PAIRS = 14681088
ATTENTION_BLOCKS = (8192 // 512) ** 2

# the names ops/pallas_apply.py and ops/pallas_interact.py give their
# pallas_calls (tests/test_chip_smoke.py keeps the two in step)
REQUIRED_KERNELS = ("de_apply_rows_cached", "de_interact_parts_fwd",
                    "de_interact_parts_bwd")

TRAINER_ARGS = ["--dataset", "dummy", "--sparse", "--batch_size", "65536",
                "--steps", "8", "--eval"]
LEG_A_ARGS = TRAINER_ARGS + ["--world_size", "1", "--vocab_scale", "0.0625"]
LEG_C_ARGS = TRAINER_ARGS + ["--world_size", "4", "--vocab_scale", "0.25"]

DEADLINE_S = 1140  # the whole check, under the driver's 1200 s
LEG_TIMEOUT_S = {"B": 240, "D": 300, "A": 480, "C": 540}
MIB = 1 << 20


class LegFailed(Exception):
  """A leg ran and what came out is wrong (or it did not finish)."""


def run_child(name: str, argv, timeout_s: float):
  """Run one child to its end under ``JAX_PLATFORMS=tpu``; returns
  ``(returncode, output, wall seconds)``. The child gets its own process
  group, which is killed whole on timeout — nothing it started outlives
  this call."""
  env = dict(os.environ, JAX_PLATFORMS="tpu", PYTHONUNBUFFERED="1")
  t0 = time.time()
  proc = subprocess.Popen(
      [sys.executable] + list(argv), cwd=HERE, env=env,
      stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
      start_new_session=True)
  try:
    out, _ = proc.communicate(timeout=max(1.0, timeout_s))
    rc = proc.returncode
  except subprocess.TimeoutExpired:
    os.killpg(proc.pid, signal.SIGKILL)
    out, _ = proc.communicate()
    out += f"\n[chip_smoke] killed after {timeout_s:.0f}s\n"
    rc = -signal.SIGKILL
  wall = time.time() - t0
  try:
    os.makedirs(LOG_DIR, exist_ok=True)
    with open(os.path.join(LOG_DIR, f"{name}.log"), "w") as f:
      f.write(out)
  except OSError:
    pass  # the log is a convenience; the verdict comes from `out`
  return rc, out, wall


def find(pattern: str, out: str, what: str):
  m = re.search(pattern, out, re.M)
  if not m:
    raise LegFailed(f"no '{what}' line in the output")
  return m


def device_of(out: str):
  """The ``device: {...}`` line every child prints first, or None."""
  m = re.search(r"^device: (\{.*?\})", out, re.M)
  return json.loads(m.group(1)) if m else None


def memory_line(out: str, label: str):
  """``memory <label>: dev0 in_use=.. peak=.. | dev1 ..`` -> [(in_use, peak)]."""
  line = find(rf"^memory {label}: (.*)$", out, f"memory {label}").group(1)
  cells = re.findall(r"dev\d+ in_use=(\d+) peak=(\d+)", line)
  if not cells:
    raise LegFailed(f"the backend reported no memory stats ({line!r})")
  return [(int(a), int(b)) for a, b in cells]


def check_trainer(out: str, rc: int, world: int) -> str:
  """Pass conditions of legs A and C; returns the summary for the leg's
  line, raises LegFailed naming the first condition that does not hold."""
  dev = device_of(out)
  if dev is None or dev["platform"] != "tpu":
    raise LegFailed(f"did not run on a TPU (device line: {dev})")
  if rc != 0:
    raise LegFailed(f"trainer exited with code {rc}")
  m = find(r"^train step compiled in ([\d.]+)s \((\d+) new cache entries\); "
           r"mosaic kernels: (.*)$", out, "train step compiled")
  compile_s, cold, kernels = (float(m.group(1)), int(m.group(2)) > 0,
                              m.group(3).split())
  missing = [k for k in REQUIRED_KERNELS if k not in kernels]
  if missing:
    raise LegFailed(f"compiled train step lacks Mosaic kernels {missing} "
                    f"(found: {kernels})")
  m = find(r"^trained (\d+) steps .* first loss (\S+) final loss (\S+)", out,
           "trained")
  steps, first = int(m.group(1)), float(m.group(2))
  losses = [float(x) for x in
            find(r"^last losses: (.*)$", out, "last losses").group(1).split()]
  if steps != 8 or len(losses) != steps:
    raise LegFailed(f"expected 8 steps and 8 losses, got {steps}/{losses}")
  if not all(math.isfinite(x) for x in [first] + losses):
    raise LegFailed(f"non-finite loss: first {first}, all {losses}")
  if not 0.6 <= first <= 0.8:
    raise LegFailed(f"first loss {first} outside [0.6, 0.8]")
  auc = float(find(r"^eval AUC: (\S+)", out, "eval AUC").group(1))
  if not math.isfinite(auc):
    raise LegFailed(f"eval AUC is {auc}")
  init_mem = memory_line(out, "after init")
  if world > 1:
    plan = int(find(r"^plan bytes per rank: (\d+)", out,
                    "plan bytes per rank").group(1))
    if len(init_mem) < world:
      raise LegFailed(f"{len(init_mem)} devices reported memory, "
                      f"world is {world}")
    for i, (in_use, peak) in enumerate(init_mem[:world]):
      if peak > 2 * in_use:
        raise LegFailed(
            f"chip {i} peaked at {peak / MIB:.0f} MiB during init but "
            f"holds {in_use / MIB:.0f} MiB: the state was not born sharded")
    # slack: the replicated dense params and the example batch on chip 0
    if init_mem[0][0] > 1.05 * plan + 256 * MIB:
      raise LegFailed(
          f"chip 0 holds {init_mem[0][0] / MIB:.0f} MiB after init, one "
          f"rank's share of the plan is {plan / MIB:.0f} MiB")
  peaks = [p // MIB for _, p in memory_line(out, "after training")]
  held = [u // MIB for u, _ in init_mem]
  return (f"compile={compile_s:.1f}s cache={'cold' if cold else 'warm'} "
          f"steps={steps} first_loss={first:.5f} last_loss={losses[-1]:.5f} "
          f"auc={auc:.5f} init_hbm_mib={held} peak_hbm_mib={peaks}")


def check_index_load(out: str, rc: int) -> str:
  """Pass conditions of leg D's second child (``tools/sparse_index_load.py``
  prints one JSON object); returns the summary for the leg's line."""
  if rc != 0:
    raise LegFailed(f"{INDEX_LOAD} exited with code {rc}")
  report = json.loads(find(r'^(\{"cell".*\})$', out, "report").group(1))
  if report["backend"] != "tpu":
    raise LegFailed(f"the counters were taken on {report['backend']!r}")
  layers = len(report["selected_pairs"])
  want = [SELECTED_PAIRS] * layers
  for name in ("selected_pairs", "reference_selected_pairs"):
    if report[name] != want:
      raise LegFailed(f"{name} {report[name]}, not {SELECTED_PAIRS} a layer")
  if not report["counts_agree"]:
    raise LegFailed("the counters, the documents' counts and the "
                    "reference's do not agree")
  blocks = [a + b for a, b in zip(report["attended_blocks"],
                                  report["skipped_blocks"])]
  if blocks != [ATTENTION_BLOCKS] * layers:
    raise LegFailed(f"attended + skipped blocks {blocks}, not "
                    f"{ATTENTION_BLOCKS} a layer")
  return (f"selected_pairs={SELECTED_PAIRS}x{layers} "
          f"attended_blocks={report['attended_blocks']}")


def result_line(ok: bool, device: dict) -> str:
  """The last line of stdout: the verdict and the device as JAX reported
  it (``jax.devices()[0].platform``, ``.device_kind``, ``len(jax.devices())``)
  and no other key — the driver reads exactly this object; everything else
  goes on the ``leg``/``summary`` lines above it."""
  return json.dumps({
      "ok": bool(ok),
      "device": {"platform": str(device["platform"]),
                 "kind": str(device["kind"]),
                 "count": int(device["count"])}})


def main() -> int:
  needed = (MAIN, SPARSE_ATTN_SMOKE, INDEX_LOAD) + KERNEL_SMOKES
  absent = [p for p in needed if not os.path.exists(os.path.join(HERE, p))]
  if absent:
    print(f"chip_smoke: not a checkout of the repository ({absent[0]} is "
          "missing next to chip_smoke.py)", file=sys.stderr)
    return 2
  platforms = os.environ.get("JAX_PLATFORMS", "")
  if platforms and "tpu" not in platforms.split(","):
    print(f"chip_smoke: JAX_PLATFORMS={platforms} holds JAX off the TPU; "
          "this check needs a TPU and has no CPU mode", file=sys.stderr)
    return 2

  t_start = time.time()

  def remaining(leg: str) -> float:
    return min(LEG_TIMEOUT_S[leg], DEADLINE_S - (time.time() - t_start))

  legs = {}
  device = None

  # leg B first: it is the cheapest way to learn there is no TPU, and a
  # kernel Mosaic refuses would fail leg A later and less clearly
  status, wall_b = "passed", 0.0
  for script in KERNEL_SMOKES:
    name = os.path.splitext(os.path.basename(script))[0]
    rc, out, wall = run_child(f"B_{name}", [script], remaining("B"))
    wall_b += wall
    device = device or device_of(out)
    if device is None:
      print(out[-2000:], file=sys.stderr)
      print("chip_smoke: no TPU: JAX found no accelerator "
            "(JAX_PLATFORMS=tpu in the child)", file=sys.stderr)
      return 2
    if rc != 0:
      status = f"FAILED: {script} exited with code {rc}"
      print(out[-2000:], file=sys.stderr)
      break
  legs["B"] = status
  print(f"leg B (kernels vs XLA): {status} wall={wall_b:.1f}s", flush=True)

  # leg D: the sparse-attention kernels against the tile loop, then the
  # cell's own selection through them
  rc, out, wall_d = run_child("D_smoke_pallas_sparse_attn",
                              [SPARSE_ATTN_SMOKE], remaining("D"))
  summary = ""
  try:
    if rc != 0:
      raise LegFailed(f"{SPARSE_ATTN_SMOKE} exited with code {rc}")
    rc, out, wall = run_child(
        "D_sparse_index_load",
        [INDEX_LOAD, "keye_dsa_train_1chip", "--seed", str(INDEX_LOAD_SEED),
         "--reference"], remaining("D"))
    wall_d += wall
    summary = check_index_load(out, rc)
    legs["D"] = "passed"
  except LegFailed as e:
    legs["D"] = f"FAILED: {e}"
    print(out[-2000:], file=sys.stderr)
  print(f"leg D (sparse attention vs XLA): {legs['D']} wall={wall_d:.1f}s "
        f"{summary}", flush=True)

  trainer_legs = [("A", LEG_A_ARGS, 1)]
  if device["count"] >= 4:
    trainer_legs.append(("C", LEG_C_ARGS, 4))
  for leg, args, world in trainer_legs:
    rc, out, wall = run_child(leg, [MAIN] + args, remaining(leg))
    try:
      summary = check_trainer(out, rc, world)
      legs[leg] = "passed"
    except LegFailed as e:
      summary = ""
      legs[leg] = f"FAILED: {e}"
      print(out[-3000:], file=sys.stderr)
    print(f"leg {leg} (trainer, world {world}): {legs[leg]} "
          f"wall={wall:.1f}s {summary}", flush=True)
  if "C" not in legs:
    legs["C"] = f"not run ({device['count']} chips)"
    print(f"leg C (trainer, world 4): {legs['C']}", flush=True)

  ok = all(v == "passed" or v.startswith("not run") for v in legs.values())
  print("summary: " + json.dumps(
      {"legs": legs, "wall_s": round(time.time() - t_start, 1)}), flush=True)
  print(result_line(ok, device), flush=True)
  return 0 if ok else 1


if __name__ == "__main__":
  sys.exit(main())
