"""The solar_open2 family (`benchmark/families/solar_open2.py`,
`configs/solar-open2-250b-ep40tp8share.json`, `workloads/solar_packed_8k.json`)
at toy widths through ``run.run_cell`` on the CPU: the sound program is
correct; the rule handed operands rounded to bfloat16, the attention layer's
gate dropped, one decay a head in place of one a channel, a rotary pass on
the attention layer (each on the timed path) and the bfloat16 control each
come out wrong by a comparison of their own. The family was added as files:
every file the benchmark had keeps its bytes. The new metrics' readers read a
hand-built trace, and a program without the scopes gives them nothing to
read; the four counting functions on cases counted by hand."""

import contextlib
import functools
import hashlib
import importlib.util
import io
import json
import os
import subprocess

import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from test_bench_keye_family import _rebuilt   # a changed step
from test_bench_lfm2_family import _patched   # a function replaced while traced
from benchmark import (
    control_sequential,
    reference,
    roofline_hybrid,
    roofline_laguna,
    roofline_solar,
    run,
    scope_children,
    scope_parts,
    scope_reduce,
    specs,
    traffic,
)

CELL = "solar_kda_train_1chip"
NAME = "solar-open2-250b-ep40tp8share"
CONFIG = f"benchmark/configs/{NAME}.json"
MIX = "benchmark/workloads/solar_packed_8k.json"
MS = ("kda_gate_ms", "kda_ms", "kda_rule_ms", "moe_shared_w1280_ms")
SHARES = ("kda_rule_mxu_pct", "kda_gate_hbm_pct",
          "moe_experts_w1280_mxu_pct", "splash_gqa_d128_mxu_pct")
# as BENCHMARK.json lists them: ISSUE 51's five, then the three that read
# under a name of their own what another family's test holds to its one cell
METRICS = ("kda_rule_mxu_pct", "kda_gate_ms", "kda_gate_hbm_pct",
           "moe_experts_w1280_mxu_pct", "splash_gqa_d128_mxu_pct",
           "kda_ms", "kda_rule_ms", "moe_shared_w1280_ms")
NEW = ("benchmark/families/solar_open2.py", CONFIG, MIX,
       "benchmark/roofline_solar.py",
       "tests/benchmark/test_bench_solar_family.py") + tuple(
           f"benchmark/layer_metrics/{m}.{ext}" for ext in ("json", "py")
           for m in METRICS)
PARENT = "fb4926f3754b6d2b61f23585e47c45e500de0939"   # the PR 50 re-anchor
# the general metrics and the scope readers that read no model's sizes
APPENDED_TO = ("host_feed_ms", "step_device_ms", "device_idle_pct",
               "route_ms", "gather_ms", "combine_ms", "onehot_ms",
               "dense_model_ms", "dense_update_ms", "sparse_apply_ms",
               "unscoped_pct", "attn_ms", "moe_ms", "moe_route_ms",
               "moe_experts_ms", "lm_head_ms", "attn_proj_ms", "attn_qk_ms",
               "attn_layout_ms", "moe_router_ms", "moe_sort_ms",
               "moe_dispatch_ms", "moe_return_ms", "remat_forward_ms",
               "linattn_proj_ms", "linattn_conv_ms")
RULE = "recurrent layer (layers/gated_delta.py)"
MODEL = "model (models/solar_open2.py)"
EXPERTS = "expert layer (layers/moe.py)"
KERNELS = "kernels (splash attention, jax.experimental.pallas.ops.tpu)"
LAYERS = {"kda_rule_mxu_pct": RULE, "kda_rule_ms": RULE,
          "kda_gate_ms": MODEL, "kda_gate_hbm_pct": MODEL, "kda_ms": MODEL,
          "moe_experts_w1280_mxu_pct": EXPERTS,
          "moe_shared_w1280_ms": EXPERTS,
          "splash_gqa_d128_mxu_pct": KERNELS}
LIMITS = {"loss_gap": 2e-5, "table_change_gap": 0.03,
          "dense_change_gap": 0.05}


def _shrink(c):
  c.update(hidden_size=32, moe_intermediate_size=12, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, n_routed_experts=16,
           num_experts_per_tok=4, heads_held=[0, 2], experts_held=[4, 8],
           vocab_here=96, seq_len=48, mean_document_length=16, chunk=32,
           init_scale=0.3, attention="xla")
  c["linear_attn_config"] = dict(c["linear_attn_config"], head_dim=8,
                                 num_heads=4)
  c["assumed_sizes"]["expert_bias_spread"] = 0.1
  c["optimizer"]["learning_rate"] = 1e-3
  # CPU, 3 seeds: the sound program reads loss_gap <= 1.2e-7,
  # table_change_gap <= 6e-4 and dense_change_gap <= 9e-3; the bfloat16
  # control and the family's four faults are held below
  c["check_limits"] = dict(LIMITS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("solar_root")))
  bench_toy._edit(os.path.join(root, CONFIG), _shrink)
  bench_toy._edit(os.path.join(root, MIX),
                  lambda c: c.update(global_batch=4, pool_batches=3))
  return root


def _setup(root, seed):
  cell = specs.load_cell(CELL, root)
  family = cell.family()
  spec = family.model_spec(cell.config)
  pool = traffic.make_pool(cell.traffic, spec.inputs, spec.n_numerical, seed,
                           traffic.family_labels(family, cell.config))
  return cell, family, spec, pool


def test_the_family_was_added_as_files():
  """Every file the parent had under ``benchmark/`` and ``tests/benchmark/``
  has the parent's bytes (``git`` is the witness where the checkout has
  one), and the family's files are new."""
  listed = subprocess.run(
      ["git", "ls-tree", "-r", PARENT, "benchmark", "tests/benchmark"],
      cwd=bench_toy.ROOT, capture_output=True, text=True)
  if listed.returncode != 0 or not listed.stdout.strip():
    pytest.skip("no git history here to compare with")
  for line in listed.stdout.splitlines():
    meta, path = line.split("\t")
    with open(os.path.join(bench_toy.ROOT, path), "rb") as f:
      data = f.read()
    blob = hashlib.sha1(b"blob %d\0" % len(data) + data).hexdigest()
    assert blob == meta.split()[2], f"{path} was edited"
  for path in NEW:
    assert "\t" + path + "\n" not in listed.stdout, path
    assert os.path.exists(os.path.join(bench_toy.ROOT, path)), path


def test_the_benchmark_grew_by_entries_alone():
  """Against the parent's ``BENCHMARK.json``; a later PR's entries after
  these change nothing asserted here."""
  shown = subprocess.run(["git", "show", f"{PARENT}:BENCHMARK.json"],
                         cwd=bench_toy.ROOT, capture_output=True, text=True)
  if shown.returncode != 0:
    pytest.skip("no git history here to compare with")
  old = json.loads(shown.stdout)
  with open(os.path.join(bench_toy.ROOT, "BENCHMARK.json")) as f:
    new = json.load(f)
  for key in ("command", "paths", "run_seconds", "end_to_end"):
    assert new[key] == old[key]
  for key in ("configs", "workloads"):
    assert new[key][:len(old[key])] == old[key]
    assert new[key][len(old[key])]["name"] in (CELL, NAME)
  appended = 0
  for was, now in zip(old["per_layer"], new["per_layer"]):
    assert {k: v for k, v in now.items() if k != "workloads"} \
        == {k: v for k, v in was.items() if k != "workloads"}
    n = len(was["workloads"])
    assert now["workloads"][:n] == was["workloads"]
    assert (CELL in now["workloads"][n:]) == (was["name"] in APPENDED_TO)
    appended += CELL in now["workloads"][n:]
  assert appended == len(APPENDED_TO) == 26
  added = new["per_layer"][len(old["per_layer"]):]
  assert [m["name"] for m in added[:8]] == list(METRICS)
  layers = {m["layer"] for m in old["per_layer"]}
  for m in added[:8]:
    assert m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] == "program_span"
    assert (m["unit"], m["better"]) == (
        ("%", "higher") if m["name"].endswith("_pct") else ("ms", "lower"))
    assert m["layer"] == LAYERS[m["name"]]
    with open(os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                           m["name"] + ".json")) as f:
      spec = json.load(f)
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == (
        m["name"], m["layer"], m["unit"], m["moves"])
  # one new layer; the others spelt as the benchmark had them
  assert {m["layer"] for m in added[:8]} - layers == {MODEL}
  # the lists other families' tests hold to their one cell are left alone
  by_name = {m["name"]: m for m in new["per_layer"]}
  for name in ("linattn_ms", "delta_rule_ms", "delta_rule_mxu_pct",
               "moe_shared_ms", "mlp_ms", "moe_experts_w1536_mxu_pct",
               "splash_d64_mxu_pct"):
    assert CELL not in by_name[name]["workloads"], name
  cell = {w["name"]: w for w in new["workloads"]}[CELL]
  assert (cell["config"], cell["traffic"], cell["chips"]) == (
      NAME, "solar_packed_8k", 1)
  assert len(cell["why"]) <= 200 and "1/5" in cell["why"] \
      and "8x" in cell["why"] and "8192" in cell["why"]
  config = {c["name"]: c for c in new["configs"]}[NAME]
  assert config["reduced"] == [
      "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
      "linear_attn_config.num_heads", "n_routed_experts", "vocab_size"]
  assert len(config["why"]) <= 200 and config["file"] == CONFIG
  assert sum(w["chips"] == 4 for w in new["workloads"]) == 1


def _catalog_row():
  path = "/opt/skills/guides/model-configs/architectures.jsonl"
  if not os.path.exists(path):
    return None
  with open(path) as f:
    rows = [json.loads(line) for line in f]
  return {r["name"]: r for r in rows}.get("Solar-Open2-250B")


def test_the_configuration_states_the_published_widths_and_its_cuts():
  cell = specs.load_cell(CELL)
  c = cell.config
  published = dict(
      model_type="solar_open2", partial_rotary_factor=1,
      linear_attn_config=dict(short_conv_kernel_size=4, head_dim=128,
                              num_heads=64, num_kv_heads=None),
      hidden_size=4096, num_hidden_layers=48, num_attention_heads=64,
      head_dim=128, num_key_value_heads=8, vocab_size=196608,
      intermediate_size=10240, moe_intermediate_size=1280, rms_norm_eps=1e-5,
      rope_theta=10000, tie_word_embeddings=False,
      max_position_embeddings=1048576, first_k_dense_replace=0,
      use_rope=False, gqa_interval=3, gqa_layers=list(range(0, 48, 4)),
      use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
      n_routed_experts=320, n_shared_experts=1, norm_topk_prob=True,
      routed_scaling_factor=1, num_experts_per_tok=8)
  assert {k: c[k] for k in published} == published
  row = _catalog_row()
  if row is not None:   # the catalog beside the guide, where it is at hand
    assert c["source"] == row["source_url"]
    assert {k: c[k] for k in row["config"]} == row["config"]
  # the cuts, and the layers they leave: one period, every floor held
  assert (c["layers_here"], c["heads_held"], c["experts_held"],
          c["vocab_here"]) == ([0, 1, 2, 3], [0, 8], [0, 8], 196608 // 8)
  assert c["reduced"] == [
      "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
      "linear_attn_config.num_heads", "n_routed_experts", "vocab_size"]
  assert set(c["reduced_why"]) == set(c["reduced"])
  family = cell.family()
  s = family.sizes(c)
  assert s["kinds"] == ("gqa", "kda", "kda", "kda")
  assert (s["h"], s["held"], s["lin_hd"], s["hd"], s["taps"]) \
      == (8, 8, 128, 128, 4)
  for words in ("40 chips share each layer", "8 of the 320",
                "8-way tensor parallel", "five groups of eight",
                "1 of the 8 key-value heads", "pipeline stages",
                "without its exchange"):
    assert words in c["deployment"], words
  for key in ("published code", "norm placement", "KDA", "GQA gate",
              "no q/k norm", "no positions", "router", "head share",
              "initialisers", "documents as numerical features", "objective",
              "optimizer", "seq_len", "chunk", "attention path"):
    assert key in c["assumed"], key
  assert c["assumed_sizes"] == {"expert_bias_spread": 0.03}
  assert set(c["check_limits"]) == set(LIMITS)
  # under 1: an update that never happened reads 1.0 on its leaf
  assert c["check_limits"]["dense_change_gap"] < 1
  assert "bfloat16 control" in c["check_limits_why"]
  for key in ("equations", "precision", "counted"):
    assert len(c[key]) > 200, key
  spec = family.model_spec(c)
  n = sum(int(np.prod(v[0])) for v in spec.dense_leaves.values())
  # ISSUE 51's table, recounted
  experts, rest = 8 * 3 * 4096 * 1280, 3 * 4096 * 1280 + 4096 * 320 + 2 * 4096
  kda = 4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 1024 + 4096 * 8 \
      + 3 * 4 * 1024 + 8 + 1024 + 128
  gqa = 3 * 4096 * 1024 + 2 * 4096 * 128
  assert (experts, rest, kda, gqa) == (125829120, 17047552, 18135176,
                                       13631488)
  period = 4 * (experts + rest + 320) + gqa + 3 * kda
  assert n == period + 4096 + 4096 * 24576 == 740212376
  assert n + 24576 * 4096 == 840875672
  assert round((n + 24576 * 4096) * 12 / 1e9, 2) == 10.09
  assert f"{n:,}" in c["counted"] and "840,875,672" in c["counted"]
  assert len(spec.dense_leaves) == 2 + (6 + 9) + 3 * (17 + 9)
  leaves = spec.dense_leaves
  assert leaves["layer_0_wq"][0] == (4096, 1024)
  assert leaves["layer_0_wk"][0] == (4096, 128)        # one key-value head
  assert leaves["layer_0_wg"][0] == (4096, 1024)
  assert leaves["layer_1_w_fa"][0] == (4096, 128)
  assert leaves["layer_1_w_fb"][0] == (128, 1024)
  assert leaves["layer_1_w_gb"][0] == (128, 1024)
  assert leaves["layer_1_b_g"][0] == (1024,)
  assert leaves["layer_1_a_log"] == ((8,), 1.0, 1.0)
  assert leaves["layer_1_dt_bias"] == ((1024,), 2.3, -4.6)
  assert leaves["layer_1_o_norm"] == ((128,), 0.0, 1.0)
  assert leaves["layer_2_conv_k"] == ((4, 1024), 0.5)
  assert leaves["layer_3_router"][0] == (4096, 320)
  assert leaves["layer_3_expert_bias"] == ((320,), 0.03)
  assert leaves["layer_3_w_down"][0] == (8, 1280, 4096)
  assert leaves["layer_3_shared_down"][0] == (1280, 4096)
  assert leaves["head"][0] == (4096, 24576)
  assert "layer_0_a_log" not in leaves and "layer_1_wg" not in leaves
  assert spec.n_numerical == c["seq_len"] and spec.summed_tables == {0}
  assert len(spec.inputs) == 1 and len(spec.tables) == 1
  assert (spec.inputs[0].hotness, spec.inputs[0].sequence,
          spec.inputs[0].rows) == (c["seq_len"], True, 24576)
  mix = cell.traffic
  assert (mix["global_batch"], mix["alpha"], mix["pool_batches"],
          mix["steps_in_flight"], mix["numerical_range"]) == (
              1, 1.05, 16, 3, [0, 1])
  assert (c["seq_len"], c["mean_document_length"], c["chunk"]) \
      == (8192, 4096, 64)
  # the mix is glm_packed_8k's at this vocabulary
  with open(os.path.join(bench_toy.ROOT, "benchmark", "workloads",
                         "glm_packed_8k.json")) as f:
    glm = json.load(f)
  assert {k: v for k, v in mix.items() if k != "why"} \
      == {k: v for k, v in glm.items() if k != "why"}


def test_a_program_without_the_model_says_so_at_once(root, monkeypatch):
  """What the parent of this PR does with these files laid over it."""
  real = importlib.util.find_spec
  monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None
                      if name.endswith("models.solar_open2")
                      else real(name, *a))
  cell = specs.load_cell(CELL, root)
  with pytest.raises(specs.SpecError, match="no .*models/solar_open2.py"):
    cell.family().model_spec(cell.config)


def test_the_familys_batch_and_its_documents(root):
  _, _, _, pool = _setup(root, 2**33 + 1)
  b = pool[0]
  assert b.cats.shape == (4, 48) and b.numerical.shape == (4, 48)
  assert set(b.labels) == {"targets"}
  assert np.array_equal(b.labels["targets"][:, :-1], b.cats[:, 1:])
  assert not b.labels["targets"][:, -1].any()
  assert 0 <= b.numerical.min() and b.numerical.max() < 1
  assert b.cats.max() < 96
  starts = np.concatenate([b.numerical for b in pool]) < 1 / 16
  assert 0.02 < starts[:, 1:].mean() < 0.15   # documents do start mid-way


def test_the_references_outputs_and_counters_are_the_programs(root):
  """The weight, the logits, and the reference's own count of the
  assignments on the held experts, a layer, against the model's."""
  cell, family, spec, pool = _setup(root, 7)
  parts = family.build_parts(cell.config, 1, 4)
  model = type(parts.model)(parts.model.config, with_counters=True)
  dense = {n: jnp.asarray(w) for n, w in
           reference.dense_weights(spec, 7).items()}
  rows = jnp.asarray(np.random.default_rng(0).normal(size=(4, 48, 32)) * 0.3,
                     jnp.float32)
  numerical = jnp.asarray(pool[0].numerical)
  ours = model.apply({"params": dense}, numerical, None, emb_acts=[rows])
  theirs = family.reference_logits(cell.config, dense, [rows], numerical,
                                   counters=True)
  assert np.array_equal(ours["weight"], theirs["weight"])
  assert np.array_equal(ours["moe"]["assignments"], theirs["assignments"])
  assert theirs["assignments"].shape == (4,)
  np.testing.assert_allclose(ours["logits"], theirs["logits"], atol=2e-4)
  # the seeded decays are neither 0 nor 1: A in e^0 .. e^2, dt 0.001 .. 0.1
  a_log, dt_bias = dense["layer_1_a_log"], dense["layer_1_dt_bias"]
  assert 0 <= float(a_log.min()) and float(a_log.max()) <= 2
  assert -6.9 <= float(dt_bias.min()) and float(dt_bias.max()) <= -2.3


# ---- broken timed paths, each caught by a named comparison -----------------
def _rule_rounded(parts):
  """The rule handed q, k, v, g and beta rounded to bfloat16."""
  from distributed_embeddings_tpu.models import solar_open2
  low = lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
  return _patched(parts, solar_open2, "chunk_kda_rule", lambda real: (
      lambda q, k, v, g, beta, seg, chunk: real(
          low(q), low(k), low(v), low(g), low(beta), seg, chunk)))


def _gate_dropped(parts):
  """``sigmoid(u W_g)`` replaced by 1: 2 sigmoid(u 0)."""
  from distributed_embeddings_tpu.models import solar_open2
  return _patched(parts, solar_open2, "gqa_mixer", lambda real: (
      lambda cfg, p, u, seg: 2.0 * real(
          cfg, {**p, "wg": jnp.zeros_like(p["wg"])}, u, seg)))


def _decay_a_head(parts):
  """One decay a head: the channels' mean."""
  from distributed_embeddings_tpu.models import solar_open2
  return _patched(parts, solar_open2, "chunk_kda_rule", lambda real: (
      lambda q, k, v, g, beta, seg, chunk: real(
          q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape),
          beta, seg, chunk)))


def _rope_added(parts):
  """A rotary pass on the attention layer's q and k."""
  from distributed_embeddings_tpu.layers.attention import (
      rope,
      rope_frequencies,
  )
  from distributed_embeddings_tpu.models import solar_open2

  def turned(x):   # [B, L, ..., hd]: heads in one axis for the pass
    flat = x.reshape(x.shape[:2] + (-1, x.shape[-1]))
    return rope(flat, jnp.arange(x.shape[1]),
                rope_frequencies(10000.0, x.shape[-1])).reshape(x.shape)
  return _patched(parts, solar_open2, "attention_xla", lambda real: (
      lambda q, k, v, mask, seg: real(turned(q), turned(k), v, mask, seg)))


ALL = ["loss_gap", "table_change_gap", "dense_change_gap"]


@pytest.mark.parametrize("broken,fails", [
    (None, []),
    ("rule_rounded", ["table_change_gap", "dense_change_gap"]),
    ("gate_dropped", ALL),
    ("decay_a_head", ALL),
    ("rope_added", ALL),
    ("control", ALL),
])
def test_a_run_of_the_family(root, capsys, monkeypatch, broken, fails):
  cell = specs.load_cell(CELL, root)
  devices, dev = bench_toy.cpu_devices(1)
  changes = {"rule_rounded": _rule_rounded, "gate_dropped": _gate_dropped,
             "decay_a_head": _decay_a_head, "rope_added": _rope_added}
  if broken in changes:
    bench_toy.break_compile_step(monkeypatch, _rebuilt(changes[broken]))
  if broken == "control":
    monkeypatch.setattr(reference, "one_step", functools.partial(
        reference.one_step, precision="bfloat16"))
  result = run.run_cell(cell, 2**31 + 77, 0.3, False, devices, dev)
  out = capsys.readouterr().out
  lines = [ln.split() for ln in out.splitlines() if ln.startswith("compare")]
  verdict = {ln[1].rstrip(":"): ln[-1] for ln in lines}
  assert set(verdict) == {"fill", "loss_gap", "table_change_gap",
                          "dense_change_gap", "untouched"}
  assert result.correct == (broken is None), out
  for name in fails:
    assert verdict[name] == "OUTSIDE", out
  assert verdict["fill"] == verdict["untouched"] == "ok"
  assert result.attempted > 1 and result.failed == 0
  if broken is None:
    assert "reference batch: 4 sequence(s) of 48 tokens" in out
    assert "2 chunks of 32 tokens a layer in 3 of 4 layers" in out


# ---- the control, one reference after the other ------------------------------
FAULTS = ("bf16_rule", "no_gate", "scalar_decay", "rope")


@pytest.fixture(scope="module")
def control_lines(root):
  """`control_sequential.control` on the toy cell, one seed, the control and
  the family's four faults: -> (seeds the control was inside on, stand-in
  -> its line of JSON)."""
  said = io.StringIO()
  with contextlib.redirect_stdout(said):
    inside = control_sequential.control(
        specs.load_cell(CELL, root), [2**31 + 77], ["bfloat16", *FAULTS])
  text = said.getvalue()
  lines = [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]
  return inside, {ln["stand_in"]: ln for ln in lines}, text


@pytest.mark.parametrize("stand_in", ["bfloat16", *FAULTS])
def test_the_sequential_control_judges_a_stand_in_as_the_check_does(
    control_lines, stand_in):
  """Reference against reference, by the check's own `Compared` under the
  toy configuration's limits: each says ``"correct": false``, by the table
  and by a dense leaf at least; under a dropped gate the gate's own leaf
  never moves, so some leaf reads 1 or more."""
  inside, lines, _ = control_lines
  assert inside == 0 and set(lines) == {"bfloat16", *FAULTS}
  line = lines[stand_in]
  assert line["correct"] is False and line["seed"] == 2**31 + 77
  assert line["outside"] == [k for k in LIMITS if line[k] > LIMITS[k]]
  assert "table_change_gap" in line["outside"] \
      and "dense_change_gap" in line["outside"]
  if stand_in == "no_gate":
    assert line["dense_change_gap"] >= 1.0


# ---- the new metrics' readers, on a hand-built trace ------------------------
STACK = "jit(step_fn)/jit(local_step)/"
FWD = STACK + "jvp(de_model)/SolarOpen2/checkpoint/"
REBUILT = STACK + "transpose(jvp(de_model))/SolarOpen2/checkpoint/" \
    "rematted_computation/"
BWD = STACK + "transpose(jvp(de_model))/SolarOpen2/checkpoint/"
LIN = "de_linear_attention/"
OPS = {  # op -> (name stack, start ns, duration ns)
    "fusion.1": (FWD + LIN + "mul", 0, 40),                      # the norm
    "fusion.2": (FWD + LIN + "de_linattn_proj/dot_general", 40, 300),
    "fusion.3": (FWD + LIN + "de_linattn_conv/mul", 340, 60),
    "fusion.4": (FWD + LIN + "de_linattn_gate/dot_general", 400, 100),
    "fusion.5": (FWD + LIN + "de_delta_rule/triangular_solve", 500, 700),
    "while.6": (FWD + LIN + "de_delta_rule/while", 1200, 300),
    "fusion.7": (REBUILT + LIN + "de_linattn_gate/logistic", 1500, 50),
    "fusion.8": (REBUILT + LIN + "de_delta_rule/checkpoint/"
                 "rematted_computation/exp", 1550, 90),
    "fusion.9": (BWD + LIN + "de_delta_rule/dot_general", 1640, 1200),
    "fusion.10": (BWD + LIN + "de_linattn_gate/dot_general", 2840, 250),
    "fusion.11": (FWD + "de_attention/de_attn_proj/dot_general", 3090, 100),
    "fusion.12": (FWD + "de_attention/de_attn_qk/mul", 3190, 10),
    "fusion.13": (FWD + "de_attention/de_attn_core/transpose", 3200, 30),
    "splash_mqa_fwd.14": (FWD + "de_attention/de_attn_core/pallas_call",
                          3230, 200),
    "splash_mqa_dkv.15": (BWD + "de_attention/de_attn_core/pallas_call",
                          3430, 500),
    "fusion.16": (FWD + "de_moe/de_moe_route/de_moe_router/top_k", 3930, 100),
    "fusion.17": (FWD + "de_moe/de_moe_experts/mul", 4030, 30),
    "ragged-dot.18": ("", 4060, 400),                 # XLA's kernel: no stack
    "fusion.19": (FWD + "de_moe/de_moe_shared/dot_general", 4460, 70),
    "fusion.20": (BWD + "de_moe/de_moe_shared/dot_general", 4530, 130),
    "fusion.21": (STACK + "jvp(de_model)/SolarOpen2/de_lm_head/dot_general",
                  4660, 60),
    "fusion.22": (STACK + "de_dense_update/add", 4720, 150),
}


def _hand_built(ops_table=None):
  ops_table = ops_table or OPS
  names = scope_reduce.OpNames(
      {op: s for op, (s, _, _) in ops_table.items()}, {})
  ops = [(op, start, dur, 0) for op, (_, start, dur) in ops_table.items()]

  class Red:
    steps = [[("jit_step_fn(7)", 0, 5000)]]
  red = Red()
  red.ops = [ops]
  return red, names


def _ctx(cell, red, names):
  return {"cell": cell, "device_kind": "TPU v5 lite",
          "scope_parts": scope_parts.attribute(red, names),
          "scope_children": scope_children.per_step_ns(red, names)}


def test_the_new_readers_on_a_hand_built_trace():
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = _ctx(cell, red, names)
  read = lambda m: cell.layer_reader(m)(red, ctx)
  rule = 700 + 300 + 90 + 1200
  gate = 100 + 50 + 250
  assert read("kda_rule_ms") == pytest.approx(rule * 1e-6)
  assert read("kda_gate_ms") == pytest.approx(gate * 1e-6)
  assert read("kda_ms") == pytest.approx((40 + 300 + 60 + rule + gate) * 1e-6)
  assert read("moe_shared_w1280_ms") == pytest.approx(200e-6)
  c, mix = cell.config, cell.traffic
  mxu = lambda flops, ns: 100 * flops / 197e12 / (ns * 1e-9)
  assert read("kda_rule_mxu_pct") == pytest.approx(
      mxu(roofline_solar.kda_rule_flops(c, mix), rule))
  assert read("kda_gate_hbm_pct") == pytest.approx(
      100 * roofline_solar.kda_gate_bytes(c, mix) / 819e9 / (gate * 1e-9))
  # the experts' own scope and XLA's grouped-matmul kernel, which has none
  assert read("moe_experts_w1280_mxu_pct") == pytest.approx(
      mxu(roofline_solar.moe_experts_flops(c, mix), 430))
  assert read("splash_gqa_d128_mxu_pct") == pytest.approx(
      mxu(roofline_solar.splash_flops(c, mix), 700))
  # the accepted readers this cell joins read a scope and no model
  assert read("linattn_proj_ms") == pytest.approx(300e-6)
  assert read("linattn_conv_ms") == pytest.approx(60e-6)
  assert read("attn_proj_ms") == pytest.approx(100e-6)
  assert read("attn_qk_ms") == pytest.approx(10e-6)
  assert read("attn_layout_ms") == pytest.approx(30e-6)
  assert read("moe_router_ms") == pytest.approx(100e-6)
  assert read("remat_forward_ms") == pytest.approx(140e-6)
  assert read("attn_ms") == pytest.approx(840e-6)
  assert read("moe_ms") == pytest.approx((100 + 30 + 400 + 200) * 1e-6)
  assert read("moe_experts_ms") == pytest.approx(430e-6)
  assert read("lm_head_ms") == pytest.approx(60e-6)
  # a program without the scopes: the ms read 0.0 as a scope of scope_reduce
  # does, the shares have nothing to divide by
  gone = ("de_linear_attention", "de_linattn_gate", "de_delta_rule",
          "de_attention", "de_moe", "de_moe_experts", "de_moe_shared")
  strip = lambda s: "/".join(p for p in s.split("/") if p not in gone)
  red, bare = _hand_built({op.replace("ragged-dot", "fusion"):
                           (strip(s) or STACK + "jvp(de_model)/dot", a, d)
                           for op, (s, a, d) in OPS.items()})
  ctx = _ctx(cell, red, bare)
  for name in MS:
    assert cell.layer_reader(name)(red, ctx) == 0.0, name
  for name in SHARES:
    assert cell.layer_reader(name)(red, ctx) is None, name


def test_the_four_counting_functions_on_hand_counted_cases():
  cell = specs.load_cell(CELL)
  c, mix = cell.config, cell.traffic
  assert roofline_solar.kinds(c) == ["gqa", "kda", "kda", "kda"]
  # the rule: the scalar rule's count at 64 x 128 x 128 and 6 x 64 x 128
  # per-channel multiplies a chunk; 128 chunks, 8 heads, 3 layers, x 3
  scalar = roofline_hybrid.delta_rule_chunk_flops(64, 128, 128)
  below, upto = 64 * 63 // 2, 64 * 65 // 2
  assert scalar == 2 * ((below + upto) * 128 + (below + upto) * 256
                        + 64 * 128 * 128 + 2 * 64 * 128 * 128
                        + 128 * 128 * 128) == 13631488
  assert roofline_solar.kda_rule_chunk_flops(64, 128, 128) \
      == scalar + 6 * 64 * 128 == 13680640
  assert roofline_solar.kda_rule_flops(c, mix) \
      == 3 * 128 * 8 * 3 * 13680640 == pytest.approx(1.261e11, rel=1e-3)
  # the gate part: a token moves 3 x 4096 + 7 x 1024 + 8 x 128 + 3 x 8
  # floats, three layers
  assert roofline_solar.kda_gate_bytes(c, mix) \
      == 4 * (12288 + 7168 + 1024 + 24) * 8192 * 3 \
      == pytest.approx(2.016e9, rel=1e-3)
  # the experts: 1,638.4 expected assignments a layer on the 8 held
  assert 8192 * 8 * 8 / 320 == 1638.4
  assert roofline_solar.moe_experts_flops(c, mix) \
      == pytest.approx(6 * 3 * 4096 * 1280 * 1638.4 * 4)
  assert roofline_solar.moe_experts_flops(c, mix) \
      == pytest.approx(6.185e11, rel=1e-3)
  # attention: 19.05 M expected pairs at 8 query heads of 128, one layer
  pairs = roofline_laguna.expected_pairs(8192, 4096)
  assert pairs == pytest.approx(19.05e6, rel=2e-3)
  assert roofline_solar.splash_flops(c, mix) \
      == pytest.approx(12 * 128 * 8 * pairs)
  assert roofline_solar.splash_flops(c, mix) == pytest.approx(2.34e11,
                                                              rel=0.01)
  # a toy counted by hand: 5 tokens in one document (15 pairs), chunks of 4
  toy = dict(c, seq_len=5, hidden_size=6, mean_document_length=10 ** 9,
             layers_here=[4, 5], heads_held=[0, 3], head_dim=7, chunk=4,
             linear_attn_config=dict(c["linear_attn_config"], head_dim=2),
             moe_intermediate_size=3, n_routed_experts=10,
             num_experts_per_tok=2, experts_held=[0, 5])
  one = dict(mix, global_batch=1)
  assert roofline_solar.kinds(toy) == ["gqa", "kda"]
  chunk = 2 * ((6 + 10) * 2 + (6 + 10) * 4 + 4 * 2 * 2 + 2 * 4 * 2 * 2
               + 2 * 2 * 2) + 6 * 4 * 2
  assert roofline_solar.kda_rule_chunk_flops(4, 2, 2) == chunk == 352
  assert roofline_solar.kda_rule_flops(toy, one) == 3 * 2 * 3 * 1 * 352
  assert roofline_solar.kda_gate_bytes(toy, one) \
      == 4 * (3 * 6 + 7 * 6 + 8 * 2 + 3 * 3) * 5 * 1
  assert roofline_solar.moe_experts_flops(toy, one) \
      == pytest.approx(6 * 3 * 6 * 3 * (5 * 2 * 5 / 10) * 2)
  assert roofline_solar.splash_flops(toy, one) \
      == pytest.approx(12 * 7 * 3 * 15 * 1, rel=1e-6)
  for fn in (roofline_solar.kda_rule_flops, roofline_solar.kda_gate_bytes,
             roofline_solar.moe_experts_flops, roofline_solar.splash_flops):
    assert fn(toy, dict(mix, global_batch=3)) == pytest.approx(
        3 * fn(toy, one))


def test_the_expert_layers_counters_on_the_toy(root, capsys):
  """`tools/moe_load.py` on this cell: every layer's load on the held
  experts and the share of the choices the bias moved."""
  spec = importlib.util.spec_from_file_location(
      "moe_load", os.path.join(bench_toy.ROOT, "tools", "moe_load.py"))
  tool = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(tool)
  report = tool.main([CELL, "--seed", "3", "--root", root])
  assert report["positions_a_layer"] == 4 * 48
  assert report["experts_held"] == [4, 8]
  assert len(report["assignments_on_held_experts"]) == 4
  assert report["dropped"] == [0] * 4
  assert all(0 < m < 0.5 for m in report["moved_share"])
  expected = 4 * 48 * 4 * 8 / 16
  assert report["load_over_expected"] == [
      round(a / expected, 3) for a in report["assignments_on_held_experts"]]
  assert json.loads(capsys.readouterr().out.splitlines()[-1]) == report
  report = tool.main([CELL, "--seed", "3", "--root", root, "--layers", "2"])
  assert len(report["moved_share"]) == 2
