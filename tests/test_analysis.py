"""Static-analysis suite: AST lint rules + trace-time jaxpr audit.

Pins the three contracts `make lint` rests on:

- every GL1xx rule FIRES on a seeded violation and is SILENCED by a
  ``# graftlint: disable=...`` suppression;
- the repo at HEAD is clean (so lint failures always mean a regression,
  never noise);
- the jaxpr audit proves the structural invariants on the REAL step
  builders — exactly one scatter-add per fused class (sparse and
  tiered), guard ``pmin`` present iff guarded, eval writes nothing —
  and its fingerprints are stable across traces and match the committed
  baseline in ``tests/data/``.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_embeddings_tpu.analysis import astlint
from distributed_embeddings_tpu.analysis import jaxpr_audit
from distributed_embeddings_tpu.analysis.astlint import (
    LintContext,
    lint_paths,
    lint_source,
)
from distributed_embeddings_tpu.analysis.jaxpr_audit import (
    Expectation,
    audit_summary,
    diff_fingerprints,
    fingerprint,
    summarize,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CTX = LintContext(registered_markers=frozenset({"slow"}),
                  fault_sites=frozenset({"ckpt_write", "host_gather"}))


def _rules(findings):
  return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# AST rules: seeded violations fire; suppressions silence
# ---------------------------------------------------------------------------


def test_gl101_host_sync_in_step_builder():
  src = """
def make_train_step(opt):
  def local_step(state, batch):
    loss = jax.device_get(state)
    state.block_until_ready()
    return loss
  return local_step
"""
  assert _rules(lint_source(src, "m.py", CTX, ["GL101"])) == [
      "GL101", "GL101"]


def test_gl101_ignores_host_side_code():
  src = """
def trainer_loop(step, state):
  return jax.device_get(step(state))

def make_train_step(opt):
  setup = jax.device_get(opt)  # builder body itself runs at build time?
  def local_step(state):
    return state
  return local_step
"""
  # only functions NESTED in a builder are traced scope; the trainer
  # and the builder's own top-level body are host-side
  findings = lint_source(src, "m.py", CTX, ["GL101"])
  assert findings == []


def test_gl101_suppression():
  src = """
def make_eval_step(opt):
  def local_eval(state):
    return jax.device_get(state)  # graftlint: disable=GL101
  return local_eval
"""
  assert lint_source(src, "m.py", CTX, ["GL101"]) == []


def test_gl102_numpy_in_traced_scope():
  src = """
def make_sparse_train_step(plan):
  def body(carry, mb):
    return np.asarray(carry), None
  return body
"""
  assert _rules(lint_source(src, "m.py", CTX, ["GL102"])) == ["GL102"]
  ok = """
def build_plan(plan):
  return np.zeros((4, 4))
"""
  assert lint_source(ok, "m.py", CTX, ["GL102"]) == []


@pytest.mark.parametrize("piece,anchor,planted,rule", [
    ("forward", "ids_all = engine.route_ids(cats, hotness_of,",
     "ids_all.block_until_ready()", "GL101"),
    ("predict", "acts = engine.finish_forward(",
     "np.asarray(z_sp)", "GL102"),
    ("loss_with", "logits = predict(dense_p, emb_dense, z_sp)",
     "jax.device_get(logits)", "GL101"),
    ("reduce_and_apply_dense", "upd, dense_opt = dense_optimizer.update(",
     "loss.item()", "GL101"),
    ("commit", "step = state[\"step\"]", "jax.device_get(step)", "GL101"),
])
def test_host_sync_planted_in_a_shared_step_piece_is_found(
    piece, anchor, planted, rule):
  """The pieces the four fused-state builders share live in private
  ``_make_*step*`` factories of ``training.py``; the trace-reachable rules
  reach every one of them (the real file: clean as committed, one finding
  on the planted line)."""
  path = os.path.join(REPO, "distributed_embeddings_tpu", "training.py")
  with open(path) as f:
    lines = f.read().split("\n")
  assert lint_source("\n".join(lines), path, CTX, ["GL101", "GL102"]) == []
  (at,) = [i for i, line in enumerate(lines)
           if line.strip().startswith(anchor)]
  indent = lines[at][:len(lines[at]) - len(lines[at].lstrip())]
  lines.insert(at, indent + planted)
  found = lint_source("\n".join(lines), path, CTX, ["GL101", "GL102"])
  # (a closure nested twice is walked from each enclosing closure)
  assert {(f.rule, f.line) for f in found} == {(rule, at + 1)}, piece


def test_gl103_bare_except():
  src = """
def load(path):
  try:
    return open(path)
  except:
    return None
"""
  assert _rules(lint_source(src, "m.py", CTX, ["GL103"])) == ["GL103"]
  assert lint_source(src.replace("except:", "except OSError:"),
                     "m.py", CTX, ["GL103"]) == []


def test_gl104_unfsynced_rename_in_durable_module():
  bad = """
import os
def publish(tmp, live):
  with open(tmp, 'w') as f:
    f.write('data')
  os.rename(tmp, live)
"""
  assert _rules(lint_source(bad, "checkpoint.py", CTX, ["GL104"])) == [
      "GL104"]
  # same code outside a durable module: out of scope
  assert lint_source(bad, "loader.py", CTX, ["GL104"]) == []
  good = """
import os
def publish(tmp, live):
  with open(tmp, 'w') as f:
    f.write('data')
    os.fsync(f.fileno())
  os.rename(tmp, live)
"""
  assert lint_source(good, "checkpoint.py", CTX, ["GL104"]) == []


def test_gl105_wallclock_in_durable_module():
  src = """
import time
def build_manifest(files):
  return {"written_at": time.time(), "files": files}
"""
  assert _rules(lint_source(src, "durable.py", CTX, ["GL105"])) == [
      "GL105"]
  assert lint_source(src, "trainer.py", CTX, ["GL105"]) == []


def test_gl106_int32_narrowing():
  bad = """
def row_offset(rank, rows):
  return np.int32(rank * rows)
"""
  assert _rules(lint_source(bad, "m.py", CTX, ["GL106"])) == ["GL106"]
  # astype flavor, through a value-propagating call
  bad2 = """
def starts(n, cp, pr):
  return np.minimum(np.arange(n) * cp, pr - cp).astype(np.int32)
"""
  assert _rules(lint_source(bad2, "m.py", CTX, ["GL106"])) == ["GL106"]
  # a narrowed VALUE (no arithmetic) and the varying-zero idiom are fine
  ok = """
def f(ids, carry):
  a = ids.astype(jnp.int32)
  b = (carry * 0).astype(jnp.int32)
  c = jnp.asarray(rng.integers(0, rows + 2, 16), jnp.int32)
  return a, b, c
"""
  assert lint_source(ok, "m.py", CTX, ["GL106"]) == []
  sup = """
def row_offset(rank, rows):
  return np.int32(rank * rows)  # graftlint: disable=GL106
"""
  assert lint_source(sup, "m.py", CTX, ["GL106"]) == []


def test_gl107_unregistered_marker():
  src = """
import pytest
@pytest.mark.sloow
def test_x():
  pass
"""
  assert _rules(lint_source(src, "test_m.py", CTX, ["GL107"])) == ["GL107"]
  assert lint_source(src.replace("sloow", "slow"), "test_m.py", CTX,
                     ["GL107"]) == []
  # builtin marks are always registered
  assert lint_source(src.replace("sloow", "parametrize"), "test_m.py",
                     CTX, ["GL107"]) == []


def test_gl109_raw_all_to_all_in_step_builder():
  src = """
def make_sparse_train_step(plan):
  def local_step(state, batch):
    y = lax.all_to_all(batch, "mp", split_axis=0, concat_axis=0)
    return y
  return local_step
"""
  out = lint_source(src, "m.py", CTX, ["GL109"])
  assert _rules(out) == ["GL109"]
  assert "wire module" in out[0].message
  # the sanctioned wire module itself is exempt — by its REAL path only
  # (an unrelated wire.py elsewhere gets no blanket pass)
  wire_path = "distributed_embeddings_tpu/parallel/wire.py"
  assert lint_source(src, wire_path, CTX, ["GL109"]) == []
  assert _rules(lint_source(src, "serving/wire.py", CTX,
                            ["GL109"])) == ["GL109"]
  # host-side (non-step-builder) code outside the library is out of
  # scope... but INSIDE the library package every function is covered —
  # the engine's methods are where the real exchanges live
  host = """
def pack_inputs(x):
  return lax.all_to_all(x, "mp", split_axis=0, concat_axis=0)
"""
  assert lint_source(host, "m.py", CTX, ["GL109"]) == []
  assert _rules(lint_source(
      host, "distributed_embeddings_tpu/parallel/lookup_engine.py", CTX,
      ["GL109"])) == ["GL109"]


def test_gl109_suppression():
  src = """
def make_eval_step(plan):
  def local_eval(state, batch):
    return lax.all_to_all(batch, "mp", split_axis=0, concat_axis=0)  # graftlint: disable=GL109
  return local_eval
"""
  assert lint_source(src, "m.py", CTX, ["GL109"]) == []


def test_gl109_raw_ppermute_in_step_builder():
  """The round-7 extension: ppermute joined the guarded exchange set —
  a raw round in step code bypasses the wire knobs and the audit's
  (world-1) x chunks pins exactly like a raw all_to_all."""
  src = """
def make_sparse_train_step(plan):
  def local_step(state, batch):
    return lax.ppermute(batch, "mp", [(0, 1), (1, 0)])
  return local_step
"""
  out = lint_source(src, "m.py", CTX, ["GL109"])
  assert _rules(out) == ["GL109"]
  assert "ppermute" in out[0].message
  # the sanctioned wire module stays exempt; library modules covered
  wire_path = "distributed_embeddings_tpu/parallel/wire.py"
  assert lint_source(src, wire_path, CTX, ["GL109"]) == []
  host = """
def shuffle(x):
  return lax.ppermute(x, "mp", [(0, 1), (1, 0)])
"""
  assert lint_source(host, "m.py", CTX, ["GL109"]) == []
  assert _rules(lint_source(
      host, "distributed_embeddings_tpu/parallel/lookup_engine.py", CTX,
      ["GL109"])) == ["GL109"]
  # suppression works for the ppermute form too
  sup = """
def make_eval_step(plan):
  def local_eval(state, batch):
    return lax.ppermute(batch, "mp", [(0, 1)])  # graftlint: disable=GL109
  return local_eval
"""
  assert lint_source(sup, "m.py", CTX, ["GL109"]) == []


def test_gl108_unknown_fault_site():
  src = """
def chaos(inj):
  inj.crash_after("ckpt_writ", 3)
  fire("host_gather", rank=0)
"""
  out = lint_source(src, "test_m.py", CTX, ["GL108"])
  assert _rules(out) == ["GL108"]
  assert "ckpt_writ" in out[0].message
  assert lint_source(src.replace("ckpt_writ", "ckpt_write"), "test_m.py",
                     CTX, ["GL108"]) == []


def test_gl110_world_constant_in_durable_module():
  bad = """
import jax
def pick():
  if jax.process_count() == 4:
    return "the benchmark pod"
  if 2 < jax.process_index():
    return "tail"
"""
  out = lint_source(bad, "checkpoint.py", CTX, ["GL110"])
  assert _rules(out) == ["GL110", "GL110"]
  assert "hardcoded constant 4" in out[0].message
  # the world-shape-free idioms stay legal: controller check, multi-
  # controller check, and world facts derived from the plan
  ok = """
import jax
def pick(plan):
  if jax.process_index() == 0:
    pass
  if jax.process_count() > 1:
    pass
  if jax.process_count() == plan.world_size:
    pass
"""
  assert lint_source(ok, "durable.py", CTX, ["GL110"]) == []
  # scope: durable modules only; trainers may pin worlds for tests
  assert lint_source(bad, "trainer.py", CTX, ["GL110"]) == []


def test_gl110_suppression():
  src = """
import jax
def f():
  return jax.process_count() == 4  # graftlint: disable=GL110
"""
  assert lint_source(src, "checkpoint.py", CTX, ["GL110"]) == []


SERVING_PATH = "distributed_embeddings_tpu/serving/engine.py"


def test_gl111_optax_import_in_serving_module():
  src = """
import optax
def f(params, grads):
  return optax.apply_updates(params, grads)
"""
  out = lint_source(src, SERVING_PATH, CTX, ["GL111"])
  assert _rules(out) and all(r == "GL111" for r in _rules(out))
  assert "optax" in out[0].message
  # outside serving/, optax is business as usual
  assert lint_source(src, "distributed_embeddings_tpu/training.py", CTX,
                     ["GL111"]) == []


def test_gl111_guards_and_builders_in_serving_module():
  src = """
from distributed_embeddings_tpu.resilience import guards
from distributed_embeddings_tpu.training import make_sparse_train_step
"""
  out = lint_source(src, SERVING_PATH, CTX, ["GL111"])
  assert len(out) == 2 and set(_rules(out)) == {"GL111"}
  # references by name fire too (a scatter emitter smuggled via alias)
  ref = """
def serve(engine, state, layouts, dz, residuals, rule, step):
  return engine.apply_sparse(state, layouts, dz, residuals, rule, step)
"""
  out = lint_source(ref, SERVING_PATH, CTX, ["GL111"])
  assert _rules(out) == ["GL111"]
  assert "apply_sparse" in out[0].message
  # the same reference is fine outside serving/
  assert lint_source(ref, "distributed_embeddings_tpu/tiering/train.py",
                     CTX, ["GL111"]) == []


def test_gl111_allows_serving_legitimate_imports():
  # the export path rides the durable checkpoint machinery — its
  # faultinject sites and the lookup-engine surfaces are NOT train-only
  src = """
from distributed_embeddings_tpu.resilience import faultinject
from distributed_embeddings_tpu.parallel.lookup_engine import (
    DistributedLookup,
    class_param_name,
)
def f(plan):
  return DistributedLookup(plan)
"""
  assert lint_source(src, SERVING_PATH, CTX, ["GL111"]) == []


def test_gl111_suppression():
  src = """
import optax  # graftlint: disable=GL111
"""
  assert lint_source(src, SERVING_PATH, CTX, ["GL111"]) == []


FLEET_PATH = "distributed_embeddings_tpu/fleet/router.py"


def test_gl114_train_surfaces_in_fleet_module():
  """The fleet tier is the serving engine spread over processes — the
  same inference-only contract (GL111) at fleet scope."""
  src = """
import optax
def f(params, grads):
  return optax.apply_updates(params, grads)
"""
  out = lint_source(src, FLEET_PATH, CTX, ["GL114"])
  assert _rules(out) and all(r == "GL114" for r in _rules(out))
  assert "optax" in out[0].message
  # outside fleet/, optax is business as usual (and GL111 owns serving/)
  assert lint_source(src, "distributed_embeddings_tpu/training.py", CTX,
                     ["GL114"]) == []
  assert lint_source(src, SERVING_PATH, CTX, ["GL114"]) == []
  # guard/builder imports and by-name references fire too
  imp = """
from distributed_embeddings_tpu.resilience import guards
from distributed_embeddings_tpu.training import make_sparse_train_step
"""
  out = lint_source(imp, FLEET_PATH, CTX, ["GL114"])
  assert len(out) == 2 and set(_rules(out)) == {"GL114"}
  ref = """
def serve(engine, state, layouts, dz, residuals, rule, step):
  return engine.apply_sparse(state, layouts, dz, residuals, rule, step)
"""
  out = lint_source(ref, FLEET_PATH, CTX, ["GL114"])
  assert _rules(out) == ["GL114"]
  assert "apply_sparse" in out[0].message


def test_gl114_allows_fleet_legitimate_imports_and_suppression():
  # the fleet rides retry/faultinject and the serving engine by design
  src = """
from distributed_embeddings_tpu.resilience import faultinject, retry
from distributed_embeddings_tpu.serving.engine import ServeEngine
from distributed_embeddings_tpu.parallel.lookup_engine import (
    class_param_name,
)
"""
  assert lint_source(src, FLEET_PATH, CTX, ["GL114"]) == []
  sup = """
import optax  # graftlint: disable=GL114
"""
  assert lint_source(sup, FLEET_PATH, CTX, ["GL114"]) == []


def test_gl112_translator_call_in_step_builder():
  """The dynamic-vocab invariant: translation-state mutation lives in
  dynvocab/ host paths — a translator call inside a trace-reachable
  step closure would break tracing or freeze one translation into the
  compiled step."""
  src = """
def make_sparse_train_step(plan, translator):
  def local_step(state, cats, labels):
    cats, _, _ = translator.translate_batch(cats)
    return state
  return local_step
"""
  out = lint_source(src, "m.py", CTX, ["GL112"])
  assert _rules(out) == ["GL112"]
  assert "host state" in out[0].message
  # the dynvocab package itself is the sanctioned home
  assert lint_source(
      src, "distributed_embeddings_tpu/dynvocab/trainer.py", CTX,
      ["GL112"]) == []
  # host-side code (trainers, tools, tests) is unrestricted: the hook
  # itself lives OUTSIDE any step builder
  host = """
def drive(engine, translator, cats):
  return engine.translate_dynamic_ids(cats, translator)
"""
  assert lint_source(host, "m.py", CTX, ["GL112"]) == []
  assert lint_source(
      host, "distributed_embeddings_tpu/parallel/lookup_engine.py", CTX,
      ["GL112"]) == []


def test_gl112_constructors_and_suppression():
  src = """
def make_eval_step(plan):
  def local_eval(state, cats):
    table = IdTranslationTable(100)
    return state
  return local_eval
"""
  assert _rules(lint_source(src, "m.py", CTX, ["GL112"])) == ["GL112"]
  sup = """
def make_eval_step(plan):
  def local_eval(state, cats):
    table = IdTranslationTable(100)  # graftlint: disable=GL112
    return state
  return local_eval
"""
  assert lint_source(sup, "m.py", CTX, ["GL112"]) == []


def test_gl113_raw_timing_in_library_module():
  """Raw perf_counter/monotonic timing in a library module: spans (or
  the telemetry histogram type) are the sanctioned form — one trace,
  one registry, instead of ~30 hand-rolled timing loops."""
  src = """
import time

def stage(store):
  t0 = time.perf_counter()
  store.gather()
  return time.perf_counter() - t0

def deadline():
  return time.monotonic() + 30.0
"""
  out = lint_source(src, "distributed_embeddings_tpu/tiering/prefetch.py",
                    CTX, ["GL113"])
  assert _rules(out) == ["GL113", "GL113", "GL113"]
  assert "telemetry.span" in out[0].message


def test_gl113_from_import_and_alias_forms():
  """A from-import (or module alias) must not be a bypass: the rule
  tracks `from time import perf_counter [as pc]` and `import time as
  t` and flags the bare-name calls the same way."""
  src = """
from time import perf_counter as pc
import time as clk

def stage():
  t0 = pc()
  return clk.monotonic() - t0
"""
  out = lint_source(src, "distributed_embeddings_tpu/tiering/store.py",
                    CTX, ["GL113"])
  assert _rules(out) == ["GL113", "GL113"]
  assert "perf_counter" in out[0].message
  # an unrelated bare name is not flagged
  ok = """
def stage(perf_counter_like):
  return perf_counter_like()
"""
  assert lint_source(ok, "distributed_embeddings_tpu/tiering/store.py",
                     CTX, ["GL113"]) == []


def test_gl113_scope_and_suppression():
  src = """
import time

def stage():
  return time.perf_counter()
"""
  # telemetry/ is the sanctioned home of the clock reads themselves
  assert lint_source(
      src, "distributed_embeddings_tpu/telemetry/trace.py", CTX,
      ["GL113"]) == []
  # tools/tests drive their own harnesses — library-package scope only
  assert lint_source(src, "tools/profile_thing.py", CTX, ["GL113"]) == []
  assert lint_source(src, "tests/test_thing.py", CTX, ["GL113"]) == []
  # non-timing uses of the time module stay legal
  ok = """
import time

def backoff():
  time.sleep(0.1)
"""
  assert lint_source(
      ok, "distributed_embeddings_tpu/resilience/retry.py", CTX,
      ["GL113"]) == []
  sup = """
import time

def deadline():
  return time.monotonic() + 30.0  # graftlint: disable=GL113 (deadline)
"""
  assert lint_source(
      sup, "distributed_embeddings_tpu/checkpoint.py", CTX,
      ["GL113"]) == []


def test_gl115_raw_minting_in_request_path_packages():
  """Raw uuid/epoch minting in serving/fleet/streaming: ids minted
  outside telemetry never land on one trace, and a second clock-epoch
  source cannot be correlated into the merged timeline."""
  src = """
import os
import time
import uuid

def subscriber_id():
  return uuid.uuid4().hex[:8]

def epoch():
  return time.time_ns()

def token():
  return os.urandom(8).hex()
"""
  for path in ("distributed_embeddings_tpu/streaming/subscribe.py",
               "distributed_embeddings_tpu/fleet/stream.py",
               "distributed_embeddings_tpu/serving/batcher.py"):
    out = lint_source(src, path, CTX, ["GL115"])
    assert _rules(out) == ["GL115", "GL115", "GL115"], path
    assert "mint_id" in out[0].message


def test_gl115_from_import_and_alias_forms():
  src = """
from uuid import uuid4 as u4
from time import time_ns

def mint():
  return u4().hex, time_ns()
"""
  out = lint_source(src, "distributed_embeddings_tpu/fleet/router.py",
                    CTX, ["GL115"])
  assert _rules(out) == ["GL115", "GL115"]
  # a module alias is not a bypass either
  aliased = """
import uuid as u
import time as clk

def mint():
  return u.uuid4().hex, clk.time_ns()
"""
  out = lint_source(aliased,
                    "distributed_embeddings_tpu/fleet/router.py",
                    CTX, ["GL115"])
  assert _rules(out) == ["GL115", "GL115"]


def test_gl115_scope_and_suppression():
  src = """
import uuid

def mint():
  return uuid.uuid4().hex
"""
  # telemetry/ is the sanctioned mint; trainers/tools/tests mint freely
  for path in ("distributed_embeddings_tpu/telemetry/trace.py",
               "distributed_embeddings_tpu/resilience/trainer.py",
               "distributed_embeddings_tpu/dynvocab/table.py",
               "tools/profile_fleet.py", "tests/test_fleet.py"):
    assert lint_source(src, path, CTX, ["GL115"]) == [], path
  # non-minting uses of the modules stay legal (time.time wall anchors)
  ok = """
import time

def anchor():
  return time.time()
"""
  assert lint_source(ok, "distributed_embeddings_tpu/streaming/publish.py",
                     CTX, ["GL115"]) == []
  sup = """
import uuid

def legacy():
  return uuid.uuid4().hex  # graftlint: disable=GL115 (external id)
"""
  assert lint_source(sup, "distributed_embeddings_tpu/fleet/stream.py",
                     CTX, ["GL115"]) == []


def test_gl116_flags_raw_signaling_in_library_modules():
  src = """
import os
import signal

def hook():
  signal.signal(signal.SIGTERM, lambda s, f: None)

def reap(pid):
  os.kill(pid, 9)
  os.killpg(pid, 15)
"""
  for path in ("distributed_embeddings_tpu/serving/batcher.py",
               "distributed_embeddings_tpu/training.py",
               "distributed_embeddings_tpu/tiering/prefetch.py"):
    out = lint_source(src, path, CTX, ["GL116"])
    assert _rules(out) == ["GL116", "GL116", "GL116"], path
    assert "resilience" in out[0].message


def test_gl116_from_import_and_alias_forms():
  src = """
from signal import signal as sig
from os import kill

def hook():
  sig(15, None)
  kill(123, 0)
"""
  out = lint_source(src, "distributed_embeddings_tpu/fleet/owner.py",
                    CTX, ["GL116"])
  assert _rules(out) == ["GL116", "GL116"]
  aliased = """
import signal as sg
import os as o

def hook():
  sg.signal(15, None)
  o.kill(123, 9)
"""
  out = lint_source(aliased, "distributed_embeddings_tpu/fleet/owner.py",
                    CTX, ["GL116"])
  assert _rules(out) == ["GL116", "GL116"]


def test_gl116_scope_and_suppression():
  src = """
import os
import signal

def hook():
  signal.signal(signal.SIGTERM, lambda s, f: None)
  os.kill(os.getpid(), 0)
"""
  # resilience/ is the sanctioned home (the drain path, chaos kill_at,
  # membership probes); tools and tests drive their own processes
  for path in ("distributed_embeddings_tpu/resilience/trainer.py",
               "distributed_embeddings_tpu/resilience/elastic.py",
               "distributed_embeddings_tpu/resilience/faultinject.py",
               "tools/chaos_preempt.py", "tests/test_preempt.py"):
    assert lint_source(src, path, CTX, ["GL116"]) == [], path
  # non-signaling uses of the modules stay legal
  ok = """
import os
import signal

def fine():
  return os.getpid(), signal.getsignal(signal.SIGTERM)
"""
  assert lint_source(ok, "distributed_embeddings_tpu/serving/engine.py",
                     CTX, ["GL116"]) == []
  sup = """
import os

def probe(pid):
  os.kill(pid, 0)  # graftlint: disable=GL116 (liveness probe, reviewed)
"""
  assert lint_source(sup, "distributed_embeddings_tpu/fleet/owner.py",
                     CTX, ["GL116"]) == []


# ---------------------------------------------------------------------------
# repo-context parsing + HEAD cleanliness
# ---------------------------------------------------------------------------


def test_repo_context_parses_markers_and_sites():
  ctx = LintContext.for_repo(REPO)
  assert "slow" in ctx.registered_markers
  # SITES literal members plus register_site-registered extensions
  # ("sigkill" in faultinject.py, the streaming sites in
  # streaming/publish.py|subscribe.py|compact.py, the fleet RPC site in
  # fleet/transport.py, the in-run resize site in resilience/elastic.py —
  # all registered at module level) — test files' ad-hoc registrations
  # are deliberately NOT scanned
  assert ctx.fault_sites == frozenset(
      {"ckpt_write", "ckpt_rename", "host_gather", "ckpt_owner_write",
       "reshard_gather", "sigkill", "delta_extract", "delta_seal",
       "stream_attach", "stream_read", "delta_promote", "compact_fold",
       "fleet_rpc", "resize_gather"})
  assert "test_extension_site" not in ctx.fault_sites


def test_gl108_accepts_register_site_extensions():
  """A site registered through register_site (parsed from the repo)
  lints clean; a near-miss typo of it still fails."""
  ctx = LintContext.for_repo(REPO)
  src = """
from distributed_embeddings_tpu.resilience import faultinject
def marker():
  faultinject.fire("sigkill", batch=0)
"""
  assert lint_source(src, "tools/x.py", ctx, ["GL108"]) == []
  out = lint_source(src.replace('"sigkill"', '"sigkil"'), "tools/x.py",
                    ctx, ["GL108"])
  assert _rules(out) == ["GL108"]


def test_repo_is_lint_clean_at_head():
  paths = [os.path.join(REPO, p) for p in
           ("distributed_embeddings_tpu", "tests", "tools", "examples")]
  findings = lint_paths(paths, root=REPO)
  assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_exit_codes(tmp_path):
  bad = tmp_path / "m.py"
  bad.write_text("def f():\n  try:\n    pass\n  except:\n    pass\n")
  env = {**os.environ, "JAX_PLATFORMS": "cpu"}
  r = subprocess.run(
      [sys.executable, os.path.join(REPO, "tools", "graftlint.py"),
       "--ast-only", str(bad)], env=env, capture_output=True, text=True)
  assert r.returncode == 1, r.stdout + r.stderr
  assert "GL103" in r.stdout
  r = subprocess.run(
      [sys.executable, os.path.join(REPO, "tools", "graftlint.py"),
       "--ast-only"], env=env, capture_output=True, text=True)
  assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# jaxpr audit: structural invariants on the REAL artifacts
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def artifacts():
  return jaxpr_audit.build_artifacts()


def test_sparse_step_exactly_one_scatter_per_class(artifacts):
  for name in ("sparse_step", "sparse_step_guard", "tiered_step"):
    jaxpr, expect = artifacts[name]
    s = summarize(jaxpr)
    assert expect.class_shapes, name
    assert audit_summary(name, s, expect) == []
    # each class's local packed buffer shape receives exactly ONE scatter
    for cname, shape in expect.class_shapes.items():
      hits = [sh for sh in s.scatter_shapes if sh == tuple(shape)]
      assert len(hits) == 1, (name, cname, s.scatter_shapes)


def test_guard_pmin_present_iff_guarded(artifacts):
  s_plain = summarize(artifacts["sparse_step"][0])
  s_guard = summarize(artifacts["sparse_step_guard"][0])
  assert s_plain.counts.get("pmin", 0) == 0
  assert s_guard.counts.get("pmin", 0) == 1
  assert s_guard.counts.get("is_finite", 0) > 0


def test_eval_step_writes_nothing(artifacts):
  s = summarize(artifacts["eval_step"][0])
  assert s.scatter_shapes == []
  assert audit_summary("eval_step", s, artifacts["eval_step"][1]) == []


def test_serve_steps_write_nothing_anywhere(artifacts):
  """Round-12 pins: the serve artifacts carry ZERO scatter ops of any
  operand shape (reverse mode through a gather lowers to a scatter —
  this is the no-reverse-mode pin), zero host callbacks, and the same
  2-exchanges-per-bucket wire structure as eval."""
  nb_eval = summarize(artifacts["eval_step"][0]).counts["all_to_all"]
  for name in ("serve_step_f32", "serve_step_int8"):
    jaxpr, expect = artifacts[name]
    s = summarize(jaxpr)
    assert expect.scatter_total == 0
    assert s.scatter_shapes == [], (name, s.scatter_shapes)
    assert s.callback_prims == [], name
    assert s.counts.get("all_to_all", 0) == nb_eval, name
    assert audit_summary(name, s, expect) == []


def test_serve_int8_dequant_convert_present(artifacts):
  """The int8 artifact must really widen int8 -> f32 on device (the
  dequantize-on-gather evidence); the f32 artifact must NOT touch int8
  anywhere."""
  s8 = summarize(artifacts["serve_step_int8"][0])
  assert ("int8", "float32") in set(s8.convert_pairs)
  s32 = summarize(artifacts["serve_step_f32"][0])
  assert all("int8" not in p for pair in s32.convert_pairs for p in pair)


def test_collectives_ride_mesh_axes_only(artifacts):
  for name, (jaxpr, expect) in artifacts.items():
    s = summarize(jaxpr)
    for prim, axes in s.collective_axes:
      assert set(axes) <= set(expect.mesh_axes), (name, prim, axes)
    assert s.f64_prims == [], name
    assert s.callback_prims == [], name


def test_wire_dtype_per_mode(artifacts):
  """Round-6 wire invariants: float all_to_all payloads travel f32 on
  default plans and bf16 (every one of them) on the bf16-wire artifact;
  integer (id) payloads stay int32 everywhere."""
  for name in ("sparse_step", "sparse_step_guard", "eval_step",
               "tiered_step"):
    s = summarize(artifacts[name][0])
    floats = [d for d in s.a2a_dtypes if "float" in d]
    assert floats and set(floats) == {"float32"}, (name, s.a2a_dtypes)
  s = summarize(artifacts["sparse_step_wire"][0])
  floats = [d for d in s.a2a_dtypes if "float" in d]
  assert floats and set(floats) == {"bfloat16"}, s.a2a_dtypes
  assert all(d == "int32" for d in s.a2a_dtypes if "int" in d)


def test_all_to_all_count_per_mode(artifacts):
  """Exchange counts are pinned per mode: a train step exchanges exactly
  3x per padded bucket (ids dp->mp, activations mp->dp, the reverse
  cotangent exchange), eval 2x — and the dedup'd wire adds NO extra
  exchange (the inverse maps never cross)."""
  for name, (jaxpr, expect) in artifacts.items():
    assert expect.a2a_count is not None, name
    s = summarize(jaxpr)
    assert s.counts.get("all_to_all", 0) == expect.a2a_count, name
  n_plain = summarize(artifacts["sparse_step"][0]).counts["all_to_all"]
  n_wire = summarize(artifacts["sparse_step_wire"][0]).counts["all_to_all"]
  assert n_plain == n_wire


def test_dense_class_side_per_artifact(artifacts):
  """Which side of the dense-kind class travels is pinned per artifact:
  at the fixture's batch of 16 the 40-row table is more bytes than its
  rows, so every artifact gathers and reduce-scatters nothing and the
  class's bucket rides the all_to_alls counted above; at a batch of 512
  (``sparse_step_tables``) its table travels: one all_gather forward, one
  reduce_scatter backward, and three all_to_alls FEWER than
  ``sparse_step`` (the class's ids, rows and cotangents)."""
  for name, (jaxpr, expect) in artifacts.items():
    s = summarize(jaxpr)
    assert expect.all_gather_count is not None, name
    assert s.counts.get("all_gather", 0) == expect.all_gather_count, name
    assert s.counts.get("reduce_scatter", 0) == \
        expect.reduce_scatter_count, name
    if name != "sparse_step_tables":
      assert expect.all_gather_count == expect.reduce_scatter_count == 0
  jaxpr, expect = artifacts["sparse_step_tables"]
  s = summarize(jaxpr)
  assert audit_summary("sparse_step_tables", s, expect) == []
  assert (expect.all_gather_count, expect.reduce_scatter_count) == (1, 1)
  n_rows = summarize(artifacts["sparse_step"][0]).counts["all_to_all"]
  assert s.counts["all_to_all"] == n_rows - 3
  # a table that crossed where the byte rule keeps it at home is flagged
  bad = audit_summary("sparse_step_tables", s, dataclasses.replace(
      expect, all_gather_count=0, reduce_scatter_count=0))
  assert len(bad) == 2 and "all_gather" in bad[0]


def test_ppermute_rounds_per_pipelined_mode(artifacts):
  """Round-7 pins: each pipelined artifact flies ZERO all_to_alls and
  exactly ``3 buckets x (world-1) x chunks`` ppermute rounds, with every
  float round payload in the mode's wire dtype (the fp8 artifact's
  blocks really are float8_e4m3 on the wire — scales ride inside them);
  monolithic artifacts fly zero ppermutes."""
  for wname, dtype in (("f32", "float32"), ("bf16", "bfloat16"),
                       ("fp8", "float8_e4m3fn")):
    name = f"sparse_step_pipe_{wname}"
    jaxpr, expect = artifacts[name]
    s = summarize(jaxpr)
    assert audit_summary(name, s, expect) == []
    assert s.counts.get("all_to_all", 0) == 0, name
    assert expect.ppermute_count and \
        s.counts.get("ppermute", 0) == expect.ppermute_count, name
    floats = [d for d in s.ppermute_dtypes if "float" in d]
    assert floats and set(floats) == {dtype}, (name, s.ppermute_dtypes)
    ints = [d for d in s.ppermute_dtypes if "int" in d]
    assert ints and set(ints) == {"int32"}, (name, s.ppermute_dtypes)
  for name in ("sparse_step", "sparse_step_guard", "sparse_step_wire",
               "eval_step", "tiered_step", "tiered_step_guard"):
    assert summarize(artifacts[name][0]).counts.get("ppermute", 0) == 0, \
        name


def test_audit_flags_ppermute_round_drift(artifacts):
  """A drifting round count (a chunk falling out of — or sneaking into
  — the schedule) must be a named violation."""
  name = "sparse_step_pipe_f32"
  jaxpr, expect = artifacts[name]
  s = summarize(jaxpr)
  import dataclasses
  bad = dataclasses.replace(expect,
                            ppermute_count=expect.ppermute_count + 3)
  out = audit_summary(name, s, bad)
  assert len(out) == 1 and "ppermute round" in out[0]


def test_audit_flags_wire_violations():
  import jax.numpy as _jnp
  from distributed_embeddings_tpu.compat import shard_map
  from distributed_embeddings_tpu.parallel import create_mesh
  from jax.sharding import PartitionSpec as P

  mesh = create_mesh(4)
  f = shard_map(
      lambda x: jax.lax.all_to_all(x, "mp", split_axis=0, concat_axis=0),
      mesh=mesh, in_specs=(P("mp"),), out_specs=P("mp"))
  jx = jax.make_jaxpr(f)(jnp.ones((16, 2), jnp.float32))
  s = summarize(jx.jaxpr)
  # f32 payload under a bf16-wire expectation
  out = audit_summary("seed", s, Expectation({}, ("mp",),
                                             wire_float_dtype="bfloat16"))
  assert len(out) == 1 and "wire_dtype contract" in out[0]
  # count drift (expected 2 exchanges, traced 1)
  out = audit_summary("seed", s, Expectation({}, ("mp",), a2a_count=2))
  assert len(out) == 1 and "all_to_all" in out[0]
  # clean under the matching expectation
  assert audit_summary("seed", s, Expectation(
      {}, ("mp",), a2a_count=1, wire_float_dtype="float32")) == []
  del _jnp


def test_fingerprints_match_committed_baseline(artifacts):
  path = os.path.join(REPO, jaxpr_audit.FINGERPRINT_PATH)
  assert os.path.exists(path), (
      "run `python tools/graftlint.py --update-fingerprints` and commit")
  with open(path) as f:
    baseline = json.load(f)
  prints = {name: fingerprint(summarize(jaxpr))
            for name, (jaxpr, _) in artifacts.items()}
  drift = diff_fingerprints(baseline, prints)
  assert drift == [], "\n".join(drift)


def test_fingerprint_stable_across_two_traces(artifacts):
  fresh = jaxpr_audit.build_artifacts()
  for name, (jaxpr, _) in artifacts.items():
    a = fingerprint(summarize(jaxpr))
    b = fingerprint(summarize(fresh[name][0]))
    assert a == b, name


# ---------------------------------------------------------------------------
# jaxpr audit: seeded violations are detected
# ---------------------------------------------------------------------------


def test_audit_flags_scatter_chain():
  def chained(buf, ids, upd):
    return buf.at[ids].add(upd).at[ids].add(upd)

  jx = jax.make_jaxpr(chained)(
      jnp.zeros((8, 4)), jnp.arange(3), jnp.ones((3, 4)))
  s = summarize(jx.jaxpr)
  out = audit_summary("seed", s, Expectation({"c": (8, 4)}, ("mp",)))
  assert len(out) == 1 and "2 scatter-adds" in out[0]


def test_audit_flags_missing_update():
  def nothing(buf):
    return buf * 2.0

  jx = jax.make_jaxpr(nothing)(jnp.zeros((8, 4)))
  out = audit_summary("seed", summarize(jx.jaxpr),
                      Expectation({"c": (8, 4)}, ("mp",)))
  assert len(out) == 1 and "0 scatter-adds" in out[0]


def test_audit_flags_missing_guard_pmin():
  def no_pmin(x):
    return x + 1

  jx = jax.make_jaxpr(no_pmin)(jnp.zeros(()))
  out = audit_summary("seed", summarize(jx.jaxpr),
                      Expectation({}, ("mp",), guard=True))
  assert len(out) == 1 and "pmin" in out[0]


def test_audit_flags_foreign_collective_axis():
  from distributed_embeddings_tpu.compat import shard_map
  from distributed_embeddings_tpu.parallel import create_mesh
  from jax.sharding import PartitionSpec as P

  mesh = create_mesh(4)
  f = shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                in_specs=(P("mp"),), out_specs=P())
  jx = jax.make_jaxpr(f)(jnp.ones(4))
  out = audit_summary("seed", summarize(jx.jaxpr),
                      Expectation({}, ("other_axis",)))
  assert out and "unknown axis" in out[0]


def test_audit_flags_f64_leak():
  from distributed_embeddings_tpu.compat import enable_x64
  with enable_x64():
    jx = jax.make_jaxpr(lambda x: x * 2.0)(jnp.zeros((2,), jnp.float64))
  out = audit_summary("seed", summarize(jx.jaxpr), Expectation({}, ("mp",)))
  assert len(out) == 1 and "float64" in out[0]


def test_audit_flags_host_callback():
  def cb(x):
    return jax.pure_callback(
        lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((2,), jnp.float32),
        x)

  jx = jax.make_jaxpr(cb)(jnp.ones(2, jnp.float32))
  out = audit_summary("seed", summarize(jx.jaxpr), Expectation({}, ("mp",)))
  assert len(out) == 1 and "callback" in out[0]


def test_audit_flags_serve_scatter_and_missing_dequant():
  """Seeded serve violations: ANY scatter under scatter_total=0 fires,
  and a missing int8 -> f32 convert under require_convert fires."""
  def writes(buf, ids, upd):
    return buf.at[ids].add(upd)

  jx = jax.make_jaxpr(writes)(
      jnp.zeros((8, 4)), jnp.arange(3), jnp.ones((3, 4)))
  out = audit_summary("seed", summarize(jx.jaxpr),
                      Expectation({}, ("mp",), scatter_total=0))
  assert len(out) == 1 and "forward-only" in out[0]

  def no_dequant(x):
    return x * 2.0

  jx = jax.make_jaxpr(no_dequant)(jnp.ones((4,), jnp.float32))
  out = audit_summary("seed", summarize(jx.jaxpr),
                      Expectation({}, ("mp",),
                                  require_convert=("int8", "float32")))
  assert len(out) == 1 and "dequantize-on-gather" in out[0]

  def dequants(x):
    return x.astype(jnp.float32) * 2.0

  jx = jax.make_jaxpr(dequants)(jnp.ones((4,), jnp.int8))
  out = audit_summary("seed", summarize(jx.jaxpr),
                      Expectation({}, ("mp",),
                                  require_convert=("int8", "float32")))
  assert out == []


def test_fingerprint_drift_detected():
  base = {"sparse_step": {"scatter-add": 3, "all_to_all": 9}}
  cur = {"sparse_step": {"scatter-add": 4, "all_to_all": 9}}
  out = diff_fingerprints(base, cur)
  assert len(out) == 1 and "scatter-add: 3 -> 4" in out[0]
  assert diff_fingerprints(base, dict(base)) == []
  # vanished artifact and missing baseline both report
  assert diff_fingerprints(base, {}) != []
  assert diff_fingerprints({}, cur) != []


# ---------------------------------------------------------------------------
# GL117: fleet mutation surfaces are control-plane actuations
# ---------------------------------------------------------------------------


def test_gl117_flags_mutation_surfaces_in_library_modules():
  """A data-path module that can reshard the fleet, edit the replica
  set, or fold the chain is an accidental operator — mutations route
  through control/ daemons or operator tools."""
  src = """
from distributed_embeddings_tpu.fleet import reshard

def on_pressure(router, fplan):
  router.apply_fleet(fplan)

def on_idle(compactor):
  compactor.compact_once()
"""
  out = lint_source(src, "distributed_embeddings_tpu/serving/engine.py",
                    CTX, ["GL117"])
  assert _rules(out) == ["GL117", "GL117", "GL117"]
  assert "control" in out[0].message


def test_gl117_home_packages_and_control_are_exempt():
  fleet_src = """
def set_fleet(self, fplan, transport=None):
  self.fplan = fplan

def promote(store, fplan):
  store.set_fleet(fplan)
"""
  # the home package keeps its definitions and internal plumbing
  assert lint_source(fleet_src,
                     "distributed_embeddings_tpu/fleet/router.py",
                     CTX, ["GL117"]) == []
  stream_src = """
def daemon_tick(compactor, k):
  return compactor.compact_once(through_seq=k)
"""
  assert lint_source(stream_src,
                     "distributed_embeddings_tpu/streaming/compact.py",
                     CTX, ["GL117"]) == []
  # control/ is the sanctioned caller of EVERY surface
  control_src = """
def actuate(router, fplan, compactor, k):
  router.apply_fleet(fplan)
  compactor.compact_once(through_seq=k)
  compactor.gc_deltas(k)
"""
  assert lint_source(control_src,
                     "distributed_embeddings_tpu/control/autoscaler.py",
                     CTX, ["GL117"]) == []
  # but fleet/ calling the STREAMING surfaces is still a violation —
  # the exemption is per-surface, not package-wide
  cross = """
def tidy(compactor):
  compactor.compact_once()
"""
  out = lint_source(cross, "distributed_embeddings_tpu/fleet/stream.py",
                    CTX, ["GL117"])
  assert _rules(out) == ["GL117"]


def test_gl117_scope_and_suppression():
  src = """
from distributed_embeddings_tpu.fleet import reshard

def main(path, world):
  reshard(path, world)
"""
  # operator tools and tests live outside the library package
  assert lint_source(src, "tools/fleet_reshard.py", CTX, ["GL117"]) == []
  assert lint_source(src, "tests/test_fleet.py", CTX, ["GL117"]) == []
  sup = """
def drain(router, fplan):
  router.apply_fleet(fplan)  # graftlint: disable=GL117 (drain hook, reviewed)
"""
  assert lint_source(sup, "distributed_embeddings_tpu/serving/engine.py",
                     CTX, ["GL117"]) == []
  # unrelated same-shape names stay legal
  ok = """
def apply_fleet_discount(prices):
  return [p * 0.9 for p in prices]
"""
  assert lint_source(ok, "distributed_embeddings_tpu/serving/engine.py",
                     CTX, ["GL117"]) == []


# GL118: multi-controller refusals must name a reason and be inventoried
def test_gl118_flags_uninventoried_refusal():
  src = """
import jax

def publish(path):
  if jax.process_count() > 1:
    raise NotImplementedError(
        "frobnication is a single-controller operation: run it from a "
        "restored checkpoint.")
"""
  out = lint_source(src, "distributed_embeddings_tpu/streaming/frob.py",
                    CTX, ["GL118"])
  assert _rules(out) == ["GL118"]
  assert "REFUSAL_INVENTORY" in out[0].message
  # the same refusal in an INVENTORIED file+snippet is the sanctioned form
  inv = src.replace(
      "frobnication is a single-controller operation",
      "delta publication is a single-controller operation")
  assert lint_source(inv, "distributed_embeddings_tpu/streaming/publish.py",
                     CTX, ["GL118"]) == []


def test_gl118_requires_literal_reason():
  src = """
import jax

def save(msg):
  if jax.process_count() > 1:
    raise NotImplementedError(msg)
"""
  out = lint_source(src, "distributed_embeddings_tpu/streaming/frob.py",
                    CTX, ["GL118"])
  assert _rules(out) == ["GL118"]
  assert "reason string" in out[0].message


def test_gl118_scope_and_suppression():
  src = """
import jax

def run():
  if jax.process_count() > 1:
    raise NotImplementedError("tools do their own thing")
"""
  # tools and tests live outside the library package
  assert lint_source(src, "tools/chaos_thing.py", CTX, ["GL118"]) == []
  # behavior branches (no raise) and other exception types are not refusals
  ok = """
import jax

def save():
  if jax.process_count() > 1:
    barrier()
  if jax.process_count() > 1:
    raise RuntimeError("a real error, not a refusal")
"""
  assert lint_source(ok, "distributed_embeddings_tpu/streaming/frob.py",
                     CTX, ["GL118"]) == []
  sup = """
import jax

def run():
  if jax.process_count() > 1:  # graftlint: disable=GL118 (migration shim)
    raise NotImplementedError("temporary refusal under review")
"""
  assert lint_source(sup, "distributed_embeddings_tpu/streaming/frob.py",
                     CTX, ["GL118"]) == []


# GL126: Pallas kernel calls and env gates are registered and homed
def test_gl126_kernel_call_outside_home():
  src = """
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def fancy(x):
  return pl.pallas_call(lambda r, o: None, out_shape=x)(x)

def ship(src, dst, sems):
  pltpu.make_async_remote_copy(src, dst, *sems, device_id=(1,)).start()
"""
  out = lint_source(src, "distributed_embeddings_tpu/parallel/fast.py",
                    CTX, ["GL126"])
  assert _rules(out) == ["GL126", "GL126"]
  assert "ops/pallas_" in out[0].message
  # the kernel modules themselves are the sanctioned home
  assert lint_source(src, "distributed_embeddings_tpu/ops/pallas_fast.py",
                     CTX, ["GL126"]) == []
  # tools/tests live outside the library package
  assert lint_source(src, "tools/smoke_thing.py", CTX, ["GL126"]) == []


def test_gl126_unregistered_gate_fires_registered_is_clean():
  src = """
import os

def _use_pallas_frob():
  return os.environ.get("DE_TPU_PALLAS_FROB", "0") == "1"
"""
  out = lint_source(src, "distributed_embeddings_tpu/ops/pallas_frob.py",
                    CTX, ["GL126"])
  assert _rules(out) == ["GL126"]
  assert "PALLAS_GATE_REGISTRY" in out[0].message
  # a docstring MENTIONING a gate is not a read
  doc = '''
def helper():
  """Gated by DE_TPU_PALLAS_FROB on real TPUs."""
  return 0
'''
  assert lint_source(doc, "distributed_embeddings_tpu/ops/pallas_frob.py",
                     CTX, ["GL126"]) == []
  # the registered (file, env, predicate) triple is the sanctioned form
  reg = """
import os
import jax

def _use_pallas_exchange():
  if os.environ.get("DE_TPU_PALLAS_EXCHANGE", "0") != "1":
    return False
  return jax.default_backend() == "tpu"
"""
  assert lint_source(reg, "distributed_embeddings_tpu/ops/pallas_exchange.py",
                     CTX, ["GL126"]) == []


def test_gl126_stale_registry_entry_fails():
  # the registered file without the env read: stale (gate moved/removed)
  out = lint_source("def gather_rows():\n  return 1\n",
                    "distributed_embeddings_tpu/ops/pallas_exchange.py",
                    CTX, ["GL126"])
  assert [f.rule for f in out] == ["GL126", "GL126"]
  assert all("stale" in f.message for f in out)
  # env read present but the registered predicate renamed away: stale
  src = """
import os

def _kernel_enabled():
  return os.environ.get("DE_TPU_PALLAS_EXCHANGE", "0") == "1"
"""
  out = lint_source(src, "distributed_embeddings_tpu/ops/pallas_exchange.py",
                    CTX, ["GL126"])
  assert _rules(out) == ["GL126"]
  assert "_use_pallas_exchange" in out[0].message


def test_gl126_suppression():
  src = """
import os

def probe():
  # transition shim reviewed in round 20
  return os.environ.get("DE_TPU_PALLAS_LEGACY")  # graftlint: disable=GL126
"""
  assert lint_source(src, "distributed_embeddings_tpu/ops/pallas_x.py",
                     CTX, ["GL126"]) == []


def test_gl118_stale_inventory_entry_fails(tmp_path):
  # a file that IS named by an inventory entry but no longer carries the
  # refusal must produce the stale-inventory finding from lint_paths
  pkg = tmp_path / "distributed_embeddings_tpu" / "streaming"
  pkg.mkdir(parents=True)
  (tmp_path / "pyproject.toml").write_text("")
  f = pkg / "publish.py"
  f.write_text("def publish():\n  return 1\n")
  out = [x for x in lint_paths([str(f)], root=str(tmp_path),
                               rules=["GL118"]) if x.rule == "GL118"]
  assert len(out) == 1 and "stale" in out[0].message
  # restore the inventoried refusal: the staleness finding clears
  f.write_text("""
import jax

def publish():
  if jax.process_count() > 1:
    raise NotImplementedError(
        "delta publication is a single-controller operation")
""")
  assert lint_paths([str(f)], root=str(tmp_path), rules=["GL118"]) == []


# ---------------------------------------------------------------------------
# GL119: raw thread/executor construction next to the step loop
# ---------------------------------------------------------------------------


def test_gl119_raw_thread_in_step_adjacent_module():
  """threading.Thread construction in the training packages that sit
  next to the step loop: pipeline.HostWorker is the one sanctioned
  overlap surface (one worker, joined before accounting, failures
  re-raised as step failures, spans on the shared trace)."""
  src = """
import threading

def start(self):
  t = threading.Thread(target=self._loop, daemon=True)
  t.start()
"""
  out = lint_source(src, "distributed_embeddings_tpu/tiering/prefetch.py",
                    CTX, ["GL119"])
  assert _rules(out) == ["GL119"]
  assert "pipeline.HostWorker" in out[0].message
  assert "threading.Thread" in out[0].message


def test_gl119_alias_and_executor_forms():
  """Renames and from-imports are not a bypass, and executors count the
  same as bare threads."""
  src = """
import threading as thr
from threading import Thread as T
from concurrent.futures import ThreadPoolExecutor
from concurrent import futures

def overlap():
  a = thr.Thread(target=work)
  b = T(target=work)
  c = ThreadPoolExecutor(max_workers=2)
  d = futures.ProcessPoolExecutor()
  return a, b, c, d
"""
  out = lint_source(src, "distributed_embeddings_tpu/dynvocab/trainer.py",
                    CTX, ["GL119"])
  assert _rules(out) == ["GL119"] * 4
  assert "concurrent.futures.ThreadPoolExecutor" in out[2].message


def test_gl119_scope_and_suppression():
  src = """
import threading

def start(self):
  return threading.Thread(target=self._poll)
"""
  # pipeline.py IS the sanctioned home of the worker thread
  assert lint_source(src, "distributed_embeddings_tpu/pipeline.py",
                     CTX, ["GL119"]) == []
  # training.py sits next to the step loop: in scope
  assert _rules(lint_source(src, "distributed_embeddings_tpu/training.py",
                            CTX, ["GL119"])) == ["GL119"]
  # serving/fleet run their own audited pools; layers never thread;
  # tools and tests drive their own harnesses
  for path in ("distributed_embeddings_tpu/serving/batcher.py",
               "distributed_embeddings_tpu/fleet/transport.py",
               "distributed_embeddings_tpu/layers/embedding.py",
               "tools/chaos_thing.py", "tests/test_thing.py"):
    assert lint_source(src, path, CTX, ["GL119"]) == [], path
  # a long-lived service thread suppresses with its reason
  sup = """
import threading

def start(self):
  self._writer = threading.Thread(target=self._write,  # graftlint: disable=GL119
                                  daemon=True)
"""
  assert lint_source(sup, "distributed_embeddings_tpu/resilience/trainer.py",
                     CTX, ["GL119"]) == []
  # a Thread ATTRIBUTE access (isinstance checks, current_thread) is use,
  # not construction — only the constructor call is flagged
  ok = """
import threading

def is_worker():
  return threading.current_thread().name == "host-pipeline"
"""
  assert lint_source(ok, "distributed_embeddings_tpu/tiering/prefetch.py",
                     CTX, ["GL119"]) == []


# ---------------------------------------------------------------------------
# threadlint (GL120-GL123, GL125): the concurrency pass
# ---------------------------------------------------------------------------

from distributed_embeddings_tpu.analysis import threadlint as tlint  # noqa: E402
from distributed_embeddings_tpu.telemetry.lockorder import (  # noqa: E402
    LockOrderError,
    LockOrderMonitor,
)


def test_gl120_guarded_attribute_fires_and_locked_access_clean():
  src = """
import threading

class Box:
  def __init__(self):
    self._lock = threading.Lock()
    self._items = []  # guarded-by: _lock

  def good(self):
    with self._lock:
      self._items.append(1)
      return len(self._items)

  def bad_write(self):
    self._items.append(1)

  def bad_read(self):
    return len(self._items)
"""
  out = tlint.lint_source(src, "x.py", rules=["GL120"])
  assert _rules(out) == ["GL120", "GL120"]
  assert "written" in out[0].message and "read" in out[1].message
  assert "'with self._lock:'" in out[0].message


def test_gl120_init_exempt_and_suppression():
  src = """
import threading

class Box:
  def __init__(self):
    self._lock = threading.Lock()
    self._n = 0  # guarded-by: _lock
    self._n = 1  # construction writes need no lock (pre-start)

  def bump(self):
    self._n += 1  # graftlint: disable=GL120 (single-writer by contract)
"""
  assert tlint.lint_source(src, "x.py", rules=["GL120"]) == []


def test_gl120_writes_mode_exempts_reads():
  """[writes]: locked-write/racy-read state (metric values, the
  subscriber's engine binding) needs no read-side suppressions."""
  src = """
import threading

class Metric:
  def __init__(self):
    self._lock = threading.RLock()
    self._value = 0  # guarded-by: _lock [writes]

  def inc(self):
    with self._lock:
      self._value += 1

  @property
  def value(self):
    return self._value

  def reset(self):
    self._value = 0
"""
  out = tlint.lint_source(src, "x.py", rules=["GL120"])
  assert [(f.rule, f.line) for f in out] == [("GL120", 18)]


def test_gl120_requires_lock_contract_and_condition_alias():
  """A requires-lock method is checked as lock-held, and holding a
  Condition built over the lock IS holding the lock (the batcher's
  _nonempty/_lock pair)."""
  src = """
import threading

class Q:
  def __init__(self):
    self._lock = threading.Lock()
    self._nonempty = threading.Condition(self._lock)
    self._pending = []  # guarded-by: _lock

  def _take_locked(self):  # requires-lock: _lock
    return self._pending.pop()

  def submit(self, x):
    with self._nonempty:
      self._pending.append(x)
      self._nonempty.notify()

  def broken_helper(self):
    return self._pending.pop()
"""
  out = tlint.lint_source(src, "x.py", rules=["GL120"])
  assert [(f.rule, f.line) for f in out] == [("GL120", 19)]


def test_gl120_dotted_guard_via_local_alias():
  """guarded-by: engine.lock is satisfied through the racy-read-verify
  idiom: a local bound from self.engine, then `with eng.lock:`."""
  src = """
class Sub:
  def __init__(self, engine):
    self.engine = engine  # guarded-by: engine.lock [writes]

  def rebase(self, new):
    old = self.engine
    with old.lock:
      self.engine = new

  def broken(self, new):
    self.engine = new
"""
  out = tlint.lint_source(src, "x.py", rules=["GL120"])
  assert [(f.rule, f.line) for f in out] == [("GL120", 12)]


def test_gl121_seeded_deadlock_cycle():
  """Two methods nesting the same pair of locks in opposite orders:
  the classic two-lock deadlock, one finding per knot."""
  src = """
import threading

class AB:
  def __init__(self):
    self._a = threading.Lock()
    self._b = threading.Lock()

  def fwd(self):
    with self._a:
      with self._b:
        pass

  def rev(self):
    with self._b:
      with self._a:
        pass
"""
  out = tlint.lint_source(src, "x.py", rules=["GL121"])
  assert _rules(out) == ["GL121"]
  assert "cycle" in out[0].message
  assert "AB._a" in out[0].message and "AB._b" in out[0].message
  # one consistent global order: no cycle, no finding
  ok = src.replace("with self._b:\n      with self._a:",
                   "with self._a:\n      with self._b:")
  assert tlint.lint_source(ok, "x.py", rules=["GL121"]) == []


def test_gl121_plain_lock_reacquire_deadlocks_rlock_does_not():
  src = """
import threading

class R:
  def __init__(self):
    self._lock = threading.{KIND}()

  def outer(self):
    with self._lock:
      self.inner()

  def inner(self):
    with self._lock:  {SUP}
      pass
"""
  bad = src.replace("{KIND}", "Lock").replace("{SUP}", "")
  # lexical nesting of the SAME plain Lock (via a requires-lock-less
  # helper there is none — seed a direct nest)
  direct = """
import threading

class R:
  def __init__(self):
    self._lock = threading.Lock()

  def outer(self):
    with self._lock:
      with self._lock:
        pass
"""
  out = tlint.lint_source(direct, "x.py", rules=["GL121"])
  assert _rules(out) == ["GL121"]
  assert "re-acquired" in out[0].message
  # an RLock is reentrant: same shape, no finding
  assert tlint.lint_source(
      direct.replace("threading.Lock()", "threading.RLock()"),
      "x.py", rules=["GL121"]) == []
  # and the suppression silences the plain-Lock form
  sup = direct.replace("with self._lock:\n        pass",
                       "with self._lock:  # graftlint: disable=GL121\n"
                       "        pass")
  assert tlint.lint_source(sup, "x.py", rules=["GL121"]) == []
  del bad


def test_gl122_multi_root_unsynchronized_mutation():
  src = """
import threading

class W:
  def __init__(self):
    self._lock = threading.Lock()
    self.items = []
    self._t1 = threading.Thread(target=self._produce)
    self._t2 = threading.Thread(target=self._consume)

  def _produce(self):
    self.items.append(1)

  def _consume(self):
    self.items.pop()
"""
  out = tlint.lint_source(src, "x.py", rules=["GL122"])
  assert _rules(out) == ["GL122"]
  assert "_produce" in out[0].message and "_consume" in out[0].message
  # locking every mutation clears it ...
  locked = src.replace(
      "def _produce(self):\n    self.items.append(1)",
      "def _produce(self):\n    with self._lock:\n      self.items.append(1)"
  ).replace(
      "def _consume(self):\n    self.items.pop()",
      "def _consume(self):\n    with self._lock:\n      self.items.pop()")
  assert tlint.lint_source(locked, "x.py", rules=["GL122"]) == []
  # ... and so does annotating (GL120 then owns the discipline)
  annotated = src.replace("self.items = []",
                          "self.items = []  # guarded-by: _lock")
  assert tlint.lint_source(annotated, "x.py", rules=["GL122"]) == []
  # suppression on the first unsynced mutation line silences
  sup = src.replace("self.items.append(1)",
                    "self.items.append(1)  # graftlint: disable=GL122")
  assert tlint.lint_source(sup, "x.py", rules=["GL122"]) == []


def test_gl122_single_root_is_not_a_race():
  """One thread root mutating freely is thread-confined state (the
  subscriber's poll-thread fields), not a race."""
  src = """
import threading

class S:
  def __init__(self):
    self._t = threading.Thread(target=self._loop)
    self.seen = 0

  def _loop(self):
    self.seen += 1
"""
  assert tlint.lint_source(src, "x.py", rules=["GL122"]) == []


def test_gl123_wait_outside_while_and_notify_without_lock():
  src = """
import threading

class C:
  def __init__(self):
    self._lock = threading.Lock()
    self._cv = threading.Condition(self._lock)
    self.ready = False

  def bad_wait(self):
    with self._cv:
      if not self.ready:
        self._cv.wait()

  def bad_notify(self):
    self._cv.notify()

  def good(self):
    with self._cv:
      while not self.ready:
        self._cv.wait()
      self._cv.notify_all()
"""
  out = tlint.lint_source(src, "x.py", rules=["GL123"])
  assert [(f.rule, f.line) for f in out] == [("GL123", 13), ("GL123", 16)]
  assert "while" in out[0].message
  assert "notify" in out[1].message
  # suppressions silence both
  sup = src.replace("self._cv.wait()\n\n",
                    "self._cv.wait()  # graftlint: disable=GL123\n\n", 1
                    ).replace("self._cv.notify()",
                              "self._cv.notify()  # graftlint: disable=GL123")
  assert tlint.lint_source(sup, "x.py", rules=["GL123"]) == []


def test_gl123_wait_for_and_events_exempt():
  """wait_for loops internally; Event.wait has no predicate to re-test
  — neither is condvar misuse."""
  src = """
import threading

class C:
  def __init__(self):
    self._cv = threading.Condition()
    self._stop = threading.Event()

  def ok(self):
    with self._cv:
      self._cv.wait_for(lambda: True, timeout=1.0)
    self._stop.wait(timeout=1.0)

  def notify_under_own_lock(self):
    with self._cv:
      self._cv.notify_all()
"""
  assert tlint.lint_source(src, "x.py", rules=["GL123"]) == []


def test_gl124_stale_and_unknown_suppressions():
  # a live suppression is fine; a stale one (rule never fires on that
  # line) and an unknown id are both GL124
  stale = """
def f():
  x = 1  # graftlint: disable=GL103
  return x
"""
  out = lint_source(stale, "tools/x.py", CTX, ["GL103", "GL124"])
  assert _rules(out) == ["GL124"]
  assert "suppresses nothing" in out[0].message
  live = """
def f():
  try:
    pass
  except:  # graftlint: disable=GL103
    pass
"""
  assert lint_source(live, "tools/x.py", CTX, ["GL103", "GL124"]) == []
  unknown = """
def f():
  return 1  # graftlint: disable=GL999
"""
  out = lint_source(unknown, "tools/x.py", CTX, ["GL124"])
  assert _rules(out) == ["GL124"]
  assert "unknown rule id" in out[0].message


def test_gl124_scope_rules_and_string_literals():
  # ids whose rule did NOT run this lint are not judged (a partial-rules
  # lint must not call other rules' suppressions stale) ...
  partial = """
def f():
  x = 1  # graftlint: disable=GL103
  return x
"""
  assert lint_source(partial, "tools/x.py", CTX, ["GL106", "GL124"]) == []
  # ... threadlint-owned ids are left to the threadlint pass ...
  external = """
def f():
  return 1  # graftlint: disable=GL120
"""
  assert lint_source(external, "tools/x.py", CTX, ["GL124"]) == []
  # ... and disable text inside a STRING (this suite's own fixtures) is
  # not a suppression at all
  fixture = '''
SRC = """
x = 1  # graftlint: disable=GL103
"""
'''
  assert lint_source(fixture, "tests/x.py", CTX, ["GL124"]) == []


def test_gl124_threadlint_judges_its_own_ids():
  src = """
import threading

class B:
  def __init__(self):
    self._lock = threading.Lock()
    self._n = 0  # guarded-by: _lock

  def ok(self):
    with self._lock:
      self._n += 1  # graftlint: disable=GL120
"""
  out = tlint.lint_source(src, "x.py")
  assert _rules(out) == ["GL124"]
  assert "GL120" in out[0].message


def test_gl125_registry_staleness_both_ways(tmp_path):
  (tmp_path / "pkg").mkdir()
  mod = tmp_path / "pkg" / "svc.py"
  mod.write_text("""
import threading

class Svc:
  def start(self):
    self._t = threading.Thread(target=self._loop, daemon=True)
    self._t.start()

  def _loop(self):
    pass
""")
  # discovered but unregistered: flagged at the construction site
  (tmp_path / "pyproject.toml").write_text(
      "[tool.graftlint]\nthread-roots = []\n")
  out = tlint.lint_paths([str(mod)], root=str(tmp_path))
  assert _rules(out) == ["GL125"]
  assert "not registered" in out[0].message and "Svc._loop" in out[0].message
  # registered and discovered: clean
  (tmp_path / "pyproject.toml").write_text(
      '[tool.graftlint]\nthread-roots = [\n    "pkg/svc.py::Svc._loop",\n]\n')
  assert tlint.lint_paths([str(mod)], root=str(tmp_path)) == []
  # registered but no longer discovered (thread removed): the ENTRY is
  # stale, flagged at its pyproject line
  mod.write_text("class Svc:\n  pass\n")
  out = tlint.lint_paths([str(mod)], root=str(tmp_path))
  assert _rules(out) == ["GL125"]
  assert "stale" in out[0].message
  assert out[0].path.endswith("pyproject.toml")
  # an entry for a file OUTSIDE the linted set is not judged
  (tmp_path / "pyproject.toml").write_text(
      '[tool.graftlint]\nthread-roots = [\n'
      '    "other/mod.py::Other._loop",\n]\n')
  assert tlint.lint_paths([str(mod)], root=str(tmp_path)) == []


def test_threadlint_repo_is_clean_at_head():
  """The annotated baseline: every guarded attribute in the batcher /
  engine / subscriber / router / registry / flight recorder is
  annotated, the thread-root registry matches discovery exactly, the
  lock graph is acyclic, and no suppression is stale."""
  pkg = os.path.join(REPO, "distributed_embeddings_tpu")
  findings = tlint.lint_paths([pkg], root=REPO)
  assert findings == [], "\n".join(f.render() for f in findings)


def test_threadlint_discovers_the_registered_concurrency_model():
  """The registry IS the model: parse_thread_roots and discovery agree
  entry-for-entry (the GL125 invariant, asserted directly), and the
  known long-lived service threads are all present."""
  roots = tlint.parse_thread_roots(REPO)
  assert roots is not None and len(roots) >= 10
  names = {e.split("::", 1)[1] for e, _ in roots}
  for expected in ("MicroBatcher._flush_loop", "MicroBatcher._complete_loop",
                   "DeltaSubscriber._poll_loop", "HostWorker._loop",
                   "FleetStore._hedged_call.run", "FlightRecorder._dump"):
    assert expected in names, expected


# ---------------------------------------------------------------------------
# the runtime sanitizer: lockorder agrees with the static graph
# ---------------------------------------------------------------------------


def test_lockorder_inverted_acquisition_trips():
  import threading
  mon = LockOrderMonitor()
  a = mon.wrap(threading.Lock(), "T.a")
  b = mon.wrap(threading.Lock(), "T.b")
  with a:
    with b:
      pass
  with pytest.raises(LockOrderError, match="inversion"):
    with b:
      with a:
        pass


def test_lockorder_reentrant_and_condition_share_name():
  import threading
  mon = LockOrderMonitor()
  lock = threading.RLock()
  wrapped = mon.wrap(lock, "T.lock")
  cv = mon.wrap(threading.Condition(lock), "T.lock")
  with wrapped:
    with cv:  # same name: reentrant, no self-edge
      cv.notify_all()
  assert mon.edges() == set()


def test_lockorder_consistency_with_static_graph():
  import threading
  mon = LockOrderMonitor()
  a = mon.wrap(threading.Lock(), "T.a")
  b = mon.wrap(threading.Lock(), "T.b")
  with a:
    with b:
      pass
  # consistent with an empty static graph and with a same-order edge
  mon.assert_consistent_with(set())
  mon.assert_consistent_with({("T.a", "T.b")})
  # a static edge in the OPPOSITE order closes a cycle: the runtime
  # truth contradicts the checked-in model
  with pytest.raises(LockOrderError, match="cycle"):
    mon.assert_consistent_with({("T.b", "T.a")})


def test_lockorder_static_graph_is_empty_and_acyclic_at_head():
  """The library holds at most one lock at a time lexically (cross-
  object nesting like router-over-store is runtime-only, covered by
  the instrumented tests) — pin that, so the first nested `with`
  must consciously pick an order."""
  assert tlint.static_lock_edges(REPO) == set()
