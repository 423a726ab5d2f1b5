"""The on-chip check and its supports, as far as a machine with no chip can
tell: `chip_smoke.py` fails fast and says why; its parent process never
imports JAX (a parent that did would hold the chip against its own
children); the kernel smokes and `bench.py` refuse a CPU backend instead
of skipping; the compile cache lives where `JAX_COMPILATION_CACHE_DIR`
says, else at `<checkout>/.jax_cache`; and the verdict logic of the
trainer legs rejects each thing it exists to reject.
"""

import ast
import importlib.util
import os
import re
import subprocess
import sys
import time

import jax
import pytest

from distributed_embeddings_tpu import compile_cache
from distributed_embeddings_tpu.ops import pallas_apply, pallas_interact

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath: str):
  name = os.path.splitext(os.path.basename(relpath))[0]
  spec = importlib.util.spec_from_file_location(
      f"_under_test_{name}", os.path.join(ROOT, relpath))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


chip_smoke = _load("chip_smoke.py")


def test_chip_smoke_fails_fast_without_a_tpu():
  t0 = time.time()
  proc = subprocess.run(
      [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
      env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
      text=True, timeout=60)
  assert proc.returncode != 0
  assert time.time() - t0 < 30
  assert "TPU" in proc.stderr
  assert proc.stdout.strip() == ""  # no result line without a chip


def test_chip_smoke_parent_imports_no_jax():
  with open(os.path.join(ROOT, "chip_smoke.py")) as f:
    tree = ast.parse(f.read())
  imported = set()
  for node in ast.walk(tree):
    if isinstance(node, ast.Import):
      imported.update(a.name.split(".")[0] for a in node.names)
    elif isinstance(node, ast.ImportFrom):
      imported.add((node.module or "").split(".")[0])
  # stdlib only: neither jax nor anything of the package (whose import
  # pulls jax in)
  assert imported <= {"json", "math", "os", "re", "signal", "subprocess",
                      "sys", "time"}, imported


def test_chip_smoke_kernel_names_match_the_ops():
  assert set(chip_smoke.REQUIRED_KERNELS) == {
      pallas_apply.KERNEL_NAME, pallas_interact.PARTS_FWD_NAME,
      pallas_interact.PARTS_BWD_NAME}


@pytest.mark.parametrize("relpath", [
    "tools/smoke_pallas_apply.py", "tools/smoke_pallas_interact.py",
    "tools/smoke_pallas_sparse_attn.py", "tools/smoke_pallas_moe_combine.py",
    "tools/bench_moe_combine.py",
    "bench.py"])
def test_chip_only_programs_refuse_a_cpu_backend(relpath, capsys):
  assert jax.default_backend() == "cpu"
  mod = _load(relpath)
  with pytest.raises(SystemExit) as exc:
    mod.main()
  assert exc.value.code not in (0, None)
  assert "TPU" in str(exc.value.code)
  assert "SKIP" not in capsys.readouterr().out.upper()


def test_compile_cache_dir_follows_the_environment(monkeypatch):
  before = jax.config.jax_compilation_cache_dir
  monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
  assert compile_cache.enable_compile_cache() == "/some/dir"
  assert jax.config.jax_compilation_cache_dir == before  # nothing set in code
  monkeypatch.delenv(compile_cache.ENV_VAR)
  try:
    got = compile_cache.enable_compile_cache()
    assert got == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
  finally:
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_dir", [None, "/some/dir"],
                         ids=["default-dir", "dir-from-environment"])
def test_compile_cache_keys_on_the_ops_metadata(monkeypatch, env_dir):
  """On both paths: a cache that ignores the op names would serve a trace
  with another build's scopes (tests/test_compile_cache.py shows it)."""
  flag = "jax_compilation_cache_include_metadata_in_key"
  before = (getattr(jax.config, flag), jax.config.jax_compilation_cache_dir)
  if env_dir is None:
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
  else:
    monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
  try:
    jax.config.update(flag, False)
    compile_cache.enable_compile_cache()
    assert getattr(jax.config, flag) is True
  finally:
    jax.config.update(flag, before[0])
    jax.config.update("jax_compilation_cache_dir", before[1])


def test_compile_cache_path_is_fixed():
  """No temp dir, pid or clock in the path: a directory that moves between
  runs never hits."""
  with open(compile_cache.__file__) as f:
    tree = ast.parse(f.read())
  names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | {
      a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
      for a in n.names}
  assert not names & {"tempfile", "time", "uuid", "random", "getpid"}
  assert compile_cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")


def test_only_the_helper_places_the_compile_cache():
  offenders = []
  for top in ("distributed_embeddings_tpu", "examples", "tools", "tests"):
    for dirpath, _, files in os.walk(os.path.join(ROOT, top)):
      for fn in files:
        path = os.path.join(dirpath, fn)
        if not fn.endswith(".py") or path in (compile_cache.__file__,
                                              os.path.abspath(__file__)):
          continue
        with open(path) as f:
          if '"jax_compilation_cache_dir"' in f.read():
            offenders.append(os.path.relpath(path, ROOT))
  for fn in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
    with open(os.path.join(ROOT, fn)) as f:
      if "jax_compilation_cache_dir" in f.read():
        offenders.append(fn)
  assert not offenders, offenders


# --- the trainer legs' verdict, on a transcript of examples/dlrm/main.py ---

GIB = 1 << 30


def _transcript(world=1, platform="tpu", kernels=chip_smoke.REQUIRED_KERNELS,
                losses=(0.6931,) * 8, auc="0.50012", new_cache_entries=1,
                init_mem=None, plan_bytes=6 * GIB):
  init_mem = init_mem or [(6 * GIB, 7 * GIB)] * world
  mem = " | ".join(f"dev{i} in_use={u} peak={p}"
                   for i, (u, p) in enumerate(init_mem))
  first = losses[0]
  return "\n".join([
      f'device: {{"platform": "{platform}", "kind": "TPU v5 lite", '
      f'"count": {world}}} world={world} tables=26 total_rows=11,763,660',
      "compile cache: /x/.jax_cache entries=190",
      "building model/state ...",
      f"plan bytes per rank: {plan_bytes}",
      f"memory after init: {mem}",
      "sparse state ready in 9.1s",
      f"train step compiled in 41.5s ({new_cache_entries} new cache entries); "
      "mosaic kernels: " + " ".join(kernels),
      "setup done in 50.9s",
      f"trained {len(losses)} steps in 0.9s (600,000 samples/sec) first loss "
      f"{first} final loss {losses[-1]}",
      "last losses: " + " ".join(str(x) for x in losses),
      f"memory after training: {mem}",
      f"eval AUC: {auc}",
  ]) + "\n"


def test_trainer_leg_passes_a_good_run():
  summary = chip_smoke.check_trainer(_transcript(), 0, 1)
  assert "cache=cold" in summary and "compile=41.5s" in summary
  assert "cache=warm" in chip_smoke.check_trainer(
      _transcript(new_cache_entries=0), 0, 1)
  chip_smoke.check_trainer(_transcript(world=4), 0, 4)


@pytest.mark.parametrize("why,kwargs,rc", [
    ("ran on the CPU", dict(platform="cpu"), 0),
    ("trainer crashed", {}, 1),
    ("apply kernel missing from the compiled step",
     dict(kernels=chip_smoke.REQUIRED_KERNELS[1:]), 0),
    ("interaction bwd kernel missing",
     dict(kernels=chip_smoke.REQUIRED_KERNELS[:2]), 0),
    ("no kernels at all", dict(kernels=("none",)), 0),
    ("a NaN loss", dict(losses=(0.69,) * 5 + ("nan",) + (0.69,) * 2), 0),
    ("first loss far from ln 2", dict(losses=(1.9,) + (0.69,) * 7), 0),
    ("too few steps", dict(losses=(0.69,) * 4), 0),
    ("AUC not finite", dict(auc="nan"), 0),
])
def test_trainer_leg_rejects(why, kwargs, rc):
  with pytest.raises(chip_smoke.LegFailed):
    chip_smoke.check_trainer(_transcript(**kwargs), rc, 1)


def test_four_chip_leg_requires_a_state_born_sharded():
  # every rank's block built on chip 0, then spread: chip 0 peaked at 4x
  # what it ends up holding
  staged = [(6 * GIB, 25 * GIB)] + [(6 * GIB, 6 * GIB)] * 3
  with pytest.raises(chip_smoke.LegFailed, match="born sharded"):
    chip_smoke.check_trainer(_transcript(world=4, init_mem=staged), 0, 4)
  # chip 0 kept a second copy
  hoard = [(13 * GIB, 13 * GIB)] + [(6 * GIB, 6 * GIB)] * 3
  with pytest.raises(chip_smoke.LegFailed, match="chip 0 holds"):
    chip_smoke.check_trainer(_transcript(world=4, init_mem=hoard), 0, 4)
  # the CPU backend's "n/a" cells are not memory stats
  out = re.sub(r"dev(\d) in_use=\d+ peak=\d+", r"dev\1 n/a",
               _transcript(world=4))
  with pytest.raises(chip_smoke.LegFailed, match="no memory stats"):
    chip_smoke.check_trainer(out, 0, 4)


@pytest.mark.parametrize("chips,bad_leg,want_ok", [
    (1, None, True), (4, None, True), (1, "A", False), (4, "C", False),
    (1, "D_smoke_pallas_sparse_attn", False),
    (1, "D_sparse_index_load", False)])
def test_last_stdout_line_is_exactly_the_verdict_and_the_device(
    chips, bad_leg, want_ok, monkeypatch, capsys):
  """The parent run whole over canned children: the driver reads the last
  line as `{"ok", "device": {"platform", "kind", "count"}}` and refuses any
  other key, so the legs' detail lives on the lines above it."""
  import json

  def fake_child(name, argv, timeout_s):
    if name.startswith("B_"):
      dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}
      return 0, f"device: {json.dumps(dev)}\nOK\n", 1.0
    if name.startswith("D_"):
      report = _index_report() if name == "D_sparse_index_load" else "PASS\n"
      return (1 if name == bad_leg else 0), report, 1.0
    world = 4 if name == "C" else 1
    return (1 if name == bad_leg else 0), _transcript(world=world), 1.0

  monkeypatch.setattr(chip_smoke, "run_child", fake_child)
  monkeypatch.delenv("JAX_PLATFORMS")
  rc = chip_smoke.main()
  lines = capsys.readouterr().out.strip().splitlines()
  assert (rc == 0) == want_ok
  last = json.loads(lines[-1])
  assert last == {"ok": want_ok, "device": {
      "platform": "tpu", "kind": "TPU v5 lite", "count": chips}}
  assert list(last) == ["ok", "device"]
  assert type(last["ok"]) is bool and type(last["device"]["count"]) is int
  legs = json.loads(lines[-2].removeprefix("summary: "))["legs"]
  assert legs["B"] == "passed"
  assert legs["D"].startswith("FAILED" if str(bad_leg).startswith("D_")
                              else "passed")
  assert legs["C"].startswith("not run (1 chips)" if chips == 1 else
                              "FAILED" if bad_leg == "C" else "passed")


# --- what a CPU can still check about the chip's program ---


@pytest.mark.parametrize("world", [1, 4])
def test_sparse_step_lowers_for_the_chip_with_its_kernels(world, monkeypatch):
  """With the gates answering as they do on the chip, the DLRM sparse
  train step — under shard_map at world 4 — traces and lowers for the TPU
  platform, and the lowered program carries the three Mosaic kernels.
  (Lowering runs Pallas' own checks, e.g. that a kernel inside shard_map
  states how its result varies over the mesh; Mosaic's compile needs the
  chip and is chip_smoke.py's business.)"""
  import jax.numpy as jnp
  import optax

  from distributed_embeddings_tpu.models import DLRM, bce_loss
  from distributed_embeddings_tpu.models.dlrm import dlrm_embedding_plan
  from distributed_embeddings_tpu.ops.packed_table import sgd_rule
  from distributed_embeddings_tpu.parallel import create_mesh
  from distributed_embeddings_tpu.training import (
      init_sparse_state_direct,
      make_sparse_train_step,
  )

  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  vocab = [40000, 39000, 17, 7000, 20000, 3, 38000, 2900, 400, 10]
  batch, width = 2048, 128
  mesh = create_mesh(world) if world > 1 else None
  model = DLRM(vocab_sizes=vocab, embedding_dim=width, world_size=world,
               strategy="memory_balanced", batch_hint=batch)
  plan = dlrm_embedding_plan(vocab, width, world, "memory_balanced",
                             batch_hint=batch)
  avals = (jax.ShapeDtypeStruct((batch, 13), jnp.float32),
           [jax.ShapeDtypeStruct((batch,), jnp.int32) for _ in vocab],
           jax.ShapeDtypeStruct((batch,), jnp.float32))
  dense_params = jax.eval_shape(
      lambda: model.init(
          jax.random.PRNGKey(0), jnp.zeros((2, 13)),
          [jnp.zeros((2,), jnp.int32) for _ in vocab],
          emb_acts=[jnp.zeros((2, width)) for _ in vocab])["params"])
  rule, opt = sgd_rule(0.5), optax.sgd(0.5)
  state = jax.eval_shape(lambda: init_sparse_state_direct(
      plan, rule, dense_params, opt, jax.random.PRNGKey(1)))
  step = make_sparse_train_step(model, plan, bce_loss, opt, rule, mesh,
                                state, avals)
  text = step.trace(state, *avals).lower(
      lowering_platforms=("tpu",)).as_text()
  found = set(re.findall(r'kernel_name = "(\w+)"', text))
  assert found >= set(chip_smoke.REQUIRED_KERNELS), found


# --- leg D's verdict, on what tools/sparse_index_load.py prints ---


def _index_report(layers=4, **changed):
  pairs = [chip_smoke.SELECTED_PAIRS] * layers
  report = {"cell": "keye_dsa_train_1chip", "seed": 2147483659,
            "backend": "tpu", "selected_pairs": pairs,
            "reference_selected_pairs": list(pairs),
            "attended_blocks": [100] * layers,
            "skipped_blocks": [chip_smoke.ATTENTION_BLOCKS - 100] * layers,
            "counts_agree": True, **changed}
  return "some warning of the runtime\n" + chip_smoke.json.dumps(report) + "\n"


def test_index_load_leg_passes_a_good_run():
  summary = chip_smoke.check_index_load(_index_report(), 0)
  assert f"selected_pairs={chip_smoke.SELECTED_PAIRS}x4" in summary


@pytest.mark.parametrize("why,changed,rc", [
    ("the tool failed", {}, 1),
    ("counted on the CPU", dict(backend="cpu"), 0),
    ("a layer selected another number of pairs",
     dict(selected_pairs=[chip_smoke.SELECTED_PAIRS] * 3
          + [chip_smoke.SELECTED_PAIRS - 2]), 0),
    ("the reference selected another number",
     dict(reference_selected_pairs=[chip_smoke.SELECTED_PAIRS + 1] * 4), 0),
    ("the documents' counts disagree", dict(counts_agree=False), 0),
    ("blocks that are neither attended nor skipped",
     dict(skipped_blocks=[chip_smoke.ATTENTION_BLOCKS - 101] * 4), 0),
])
def test_index_load_leg_rejects(why, changed, rc):
  with pytest.raises(chip_smoke.LegFailed):
    chip_smoke.check_index_load(_index_report(**changed), rc)
