"""The glm_moe_lite family (`benchmark/families/glm_moe_lite.py`,
`configs/glm-4.7-flash-ep8share.json`, `workloads/glm_packed_8k.json`) at toy
widths through ``run.run_cell`` on the CPU: the sound program is correct; RoPE
left off the shared key, the key-value latent's norm left out, the routed
scale dropped, the prediction module held to the wrong shift, its loss left
out (each on the timed path) and the bfloat16 control each come out wrong by a
comparison of their own. The family was added as files: every file the
benchmark had keeps its bytes. The new metrics' readers read a hand-built
trace, and a program without the scopes gives them nothing to read; the two
counting functions on cases counted by hand."""

import contextlib
import dataclasses
import functools
import hashlib
import importlib.util
import io
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from test_bench_keye_family import _rebuilt, _with_config   # a changed step
from test_bench_lfm2_family import _patched   # a function replaced while traced
from benchmark import (
    control_sequential,
    reference,
    roofline_glm,
    roofline_laguna,
    run,
    scope_parts,
    scope_reduce,
    specs,
    traffic,
)

CELL = "glm_mla_train_1chip"
NAME = "glm-4.7-flash-ep8share"
CONFIG = f"benchmark/configs/{NAME}.json"
MIX = "benchmark/workloads/glm_packed_8k.json"
MS = ("mla_down_ms", "mla_up_ms", "mtp_ms", "mtp_head_ms")
SHARES = ("mla_proj_mxu_pct", "splash_d256_mxu_pct")
METRICS = MS + SHARES
NEW = ("benchmark/families/glm_moe_lite.py", CONFIG, MIX,
       "benchmark/roofline_glm.py",
       "tests/benchmark/test_bench_glm_family.py") + tuple(
           f"benchmark/layer_metrics/{m}.{ext}" for ext in ("json", "py")
           for m in METRICS)
PARENT = "b56d8601f8e7e19e03912550c807e15e8760108f"   # PR 46
# the general metrics and the scope readers that read no model's sizes
APPENDED_TO = ("host_feed_ms", "step_device_ms", "device_idle_pct",
               "route_ms", "gather_ms", "combine_ms", "onehot_ms",
               "dense_model_ms", "dense_update_ms", "sparse_apply_ms",
               "unscoped_pct", "attn_ms", "moe_ms", "moe_route_ms",
               "moe_experts_ms", "lm_head_ms", "attn_proj_ms", "attn_qk_ms",
               "attn_layout_ms", "moe_router_ms", "moe_sort_ms",
               "moe_dispatch_ms", "moe_return_ms", "remat_forward_ms")
LATENT = "latent attention (layers/latent_attention.py)"
MODEL = "model (models/glm_moe_lite.py)"
LAYERS = {"mla_down_ms": LATENT, "mla_up_ms": LATENT,
          "mla_proj_mxu_pct": LATENT, "mtp_ms": MODEL, "mtp_head_ms": MODEL,
          "splash_d256_mxu_pct": "kernels (splash attention, "
                                 "jax.experimental.pallas.ops.tpu)"}
LIMITS = {"loss_gap": 2e-5, "table_change_gap": 0.03,
          "dense_change_gap": 0.03}


def _shrink(c):
  c.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=12,
           num_attention_heads=4, q_lora_rank=12, kv_lora_rank=8,
           qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=12,
           n_routed_experts=16, experts_held=[4, 8], layers_here=[0, 1, 2],
           vocab_here=96, seq_len=48, mean_document_length=16,
           init_scale=0.3, attention="xla")
  c["assumed_sizes"]["expert_bias_spread"] = 0.1
  c["optimizer"]["learning_rate"] = 1e-3
  # CPU, 3 seeds: the sound program reads loss_gap <= 1.2e-7,
  # table_change_gap <= 2.3e-5 and dense_change_gap <= 1.5e-3 (an expert
  # matrix); the bfloat16 control and the family's five faults are held below
  c["check_limits"] = dict(LIMITS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("glm_root")))
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
  assert appended == len(APPENDED_TO) == 24
  added = new["per_layer"][len(old["per_layer"]):]
  assert [m["name"] for m in added[:6]] == list(METRICS)
  layers = {m["layer"] for m in old["per_layer"]}
  for m in added[:6]:
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
  # two new layers, the kernels' spelt as the benchmark had it
  assert {m["layer"] for m in added[:6]} - layers == {LATENT, MODEL}
  # the lists other families' tests hold to their one cell are left alone
  by_name = {m["name"]: m for m in new["per_layer"]}
  for name in ("moe_shared_ms", "dense_mlp_ms", "mlp_ms",
               "moe_experts_w1536_mxu_pct"):
    assert CELL not in by_name[name]["workloads"], name
  cell = {w["name"]: w for w in new["workloads"]}[CELL]
  assert (cell["config"], cell["traffic"], cell["chips"]) == (
      NAME, "glm_packed_8k", 1)
  assert len(cell["why"]) <= 200 and "1/8" in cell["why"] \
      and "8x" in cell["why"] and "8192" in cell["why"]
  config = {c["name"]: c for c in new["configs"]}[NAME]
  assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"] and len(config["why"]) <= 200
  assert config["file"] == CONFIG
  assert sum(w["chips"] == 4 for w in new["workloads"]) == 1


def _catalog_row():
  path = "/opt/skills/guides/model-configs/architectures.jsonl"
  if not os.path.exists(path):
    return None
  with open(path) as f:
    rows = [json.loads(line) for line in f]
  return {r["name"]: r for r in rows}.get("GLM-4.7-Flash")


def test_the_configuration_states_the_published_widths_and_its_cuts():
  cell = specs.load_cell(CELL)
  c = cell.config
  published = dict(
      model_type="glm4_moe_lite", attention_bias=False, hidden_act="silu",
      hidden_size=2048, intermediate_size=10240,
      max_position_embeddings=202752, moe_intermediate_size=1536,
      topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
      n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
      routed_scaling_factor=1.8, num_experts_per_tok=4,
      first_k_dense_replace=1, num_hidden_layers=47, num_key_value_heads=20,
      num_nextn_predict_layers=1, partial_rotary_factor=1, rms_norm_eps=1e-5,
      rope_scaling=None, rope_theta=1000000, tie_word_embeddings=False,
      q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
      qk_rope_head_dim=64, v_head_dim=256, vocab_size=154880)
  assert {k: c[k] for k in published} == published
  row = _catalog_row()
  if row is not None:   # the catalog beside the guide, where it is at hand
    assert c["source"] == row["source_url"]
    assert {k: c[k] for k in row["config"]} == row["config"]
  # the three cuts, and the layers they leave: the leading dense layer, then
  # four expert layers, the module beside them
  assert (c["layers_here"], c["experts_held"], c["vocab_here"]) \
      == ([0, 1, 2, 3, 4], [0, 8], 154880 // 8)
  assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                          "vocab_size"]
  assert set(c["reduced_why"]) == set(c["reduced"])
  family = cell.family()
  s = family.sizes(c)
  assert s["dense"] == (True, False, False, False, False)
  assert (s["nope"] + s["rope"], s["v"], s["heads"]) == (256, 256, 20)
  for words in ("eight chips share each layer", "8 of the 64",
                "all 20 heads held", "pipeline stages",
                "the group that holds the head", "without its exchange"):
    assert words in c["deployment"], words
  for key in ("rotary layout", "softmax scale", "the module's wiring",
              "mtp_loss_weight", "expert_bias", "renormalisation",
              "norm placement", "no biases", "router", "initialisers",
              "documents as numerical features", "objective", "optimizer",
              "seq_len", "attention path"):
    assert key in c["assumed"], key
  assert "Adam leaves where it was" in c["assumed"]["expert_bias"]
  assert "BEFORE the trunk's final norm" in c["assumed"]["the module's wiring"]
  assert c["assumed_sizes"] == {"expert_bias_spread": 0.03,
                                "mtp_loss_weight": 0.3}
  assert set(c["check_limits"]) == set(LIMITS)
  # under 1: an update that never happened reads 1.0 on its leaf
  assert c["check_limits"]["dense_change_gap"] < 1
  assert "bfloat16 control" in c["check_limits_why"]
  spec = family.model_spec(c)
  n = sum(int(np.prod(v[0])) for v in spec.dense_leaves.values())
  # ISSUE 47's count
  latent = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
      + 5120 * 2048 + 768 + 512
  mlp, experts = 3 * 2048 * 10240, 8 * 3 * 2048 * 1536
  shared, router = 3 * 2048 * 1536, 2048 * 64 + 64
  assert (latent, mlp, experts, shared, router) == (
      21759232, 62914560, 75497472, 9437184, 131136)
  layer0 = latent + mlp + 2 * 2048
  expert_layer = latent + experts + shared + router + 2 * 2048
  module = expert_layer + 4096 * 2048 + 3 * 2048
  assert (layer0, expert_layer, module) == (84677888, 106829120, 115223872)
  assert n == layer0 + 4 * expert_layer + module + 2048 + 2048 * 19360 \
      == 666869568
  assert round((n + 19360 * 2048) * 12 / 1e9, 2) == 8.48
  assert len(spec.dense_leaves) == 2 + 12 + 4 * 17 + (4 + 17)
  assert spec.dense_leaves["layer_0_w_dq"][0] == (2048, 768)
  assert spec.dense_leaves["layer_0_w_uq"][0] == (768, 5120)
  assert spec.dense_leaves["layer_0_w_dkv"][0] == (2048, 576)
  assert spec.dense_leaves["layer_0_w_ukv"][0] == (512, 8960)
  assert spec.dense_leaves["layer_0_w_o"][0] == (5120, 2048)
  assert spec.dense_leaves["layer_0_q_a_norm"] == ((768,), 0.0, 1.0)
  assert spec.dense_leaves["layer_0_kv_a_norm"] == ((512,), 0.0, 1.0)
  assert spec.dense_leaves["layer_0_w_gate"][0] == (2048, 10240)
  assert spec.dense_leaves["layer_1_expert_bias"] == ((64,), 0.03)
  assert spec.dense_leaves["layer_4_w_down"][0] == (8, 1536, 2048)
  assert spec.dense_leaves["layer_4_shared_down"][0] == (1536, 2048)
  assert spec.dense_leaves["mtp_w_eh"][0] == (4096, 2048)
  assert spec.dense_leaves["mtp_layer_router"][0] == (2048, 64)
  assert spec.dense_leaves["mtp_layer_w_gate"][0] == (8, 2048, 1536)
  assert "layer_0_router" not in spec.dense_leaves
  assert "mtp_head" not in spec.dense_leaves       # the trunk's, one leaf
  assert spec.n_numerical == c["seq_len"] and spec.summed_tables == {0}
  assert len(spec.inputs) == 1 and len(spec.tables) == 1
  assert (spec.inputs[0].hotness, spec.inputs[0].sequence,
          spec.inputs[0].rows) == (c["seq_len"], True, 19360)
  mix = cell.traffic
  assert (mix["global_batch"], mix["alpha"], mix["pool_batches"],
          mix["steps_in_flight"], mix["numerical_range"]) == (
              1, 1.05, 16, 3, [0, 1])
  assert (c["seq_len"], c["mean_document_length"]) == (8192, 4096)


def test_a_program_without_the_model_says_so_at_once(root, monkeypatch):
  """What the parent of this PR does with these files laid over it."""
  real = importlib.util.find_spec
  monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None
                      if name.endswith("models.glm_moe_lite")
                      else real(name, *a))
  cell = specs.load_cell(CELL, root)
  with pytest.raises(specs.SpecError, match="no .*models/glm_moe_lite.py"):
    cell.family().model_spec(cell.config)


def test_the_familys_batch_its_documents_and_its_two_shifts(root):
  _, _, _, pool = _setup(root, 2**33 + 1)
  b = pool[0]
  assert b.cats.shape == (4, 48) and b.numerical.shape == (4, 48)
  assert set(b.labels) == {"targets", "targets_2"}
  assert np.array_equal(b.labels["targets"][:, :-1], b.cats[:, 1:])
  assert np.array_equal(b.labels["targets_2"][:, :-2], b.cats[:, 2:])
  assert not b.labels["targets"][:, -1].any()
  assert not b.labels["targets_2"][:, -2:].any()
  assert 0 <= b.numerical.min() and b.numerical.max() < 1
  assert b.cats.max() < 96
  starts = np.concatenate([b.numerical for b in pool]) < 1 / 16
  assert 0.02 < starts[:, 1:].mean() < 0.15   # documents do start mid-way


def test_the_references_outputs_and_counters_are_the_programs(root):
  """Both weights, and the reference's own count of the assignments on the
  held experts and of the choices the bias moved, an expert layer (the
  module's last), against the model's."""
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
  assert np.array_equal(ours["mtp_weight"], theirs["mtp_weight"])
  weight, twice = np.asarray(ours["weight"]), np.asarray(ours["mtp_weight"])
  # a position the module counts is one the trunk counts, and so is the next
  assert np.array_equal(twice[:, :-1], weight[:, :-1] * weight[:, 1:])
  assert 0 < twice.sum() < weight.sum() and not twice[:, -2:].any()
  assert np.array_equal(ours["moe"]["assignments"], theirs["assignments"])
  assert np.array_equal(ours["moe"]["moved"], theirs["moved"])
  assert theirs["moved"].shape == (3,) and 0 < int(theirs["moved"].min()) \
      and int(theirs["moved"].max()) < 4 * 48 * 4 // 2
  for name in ("logits", "mtp_logits"):
    np.testing.assert_allclose(ours[name], theirs[name], atol=2e-4)


# ---- broken timed paths, each caught by a named comparison -----------------
def _key_rope_dropped(parts):
  """RoPE left off the shared key (the one call on a single head)."""
  from distributed_embeddings_tpu.layers import latent_attention
  return _patched(parts, latent_attention, "rope", lambda real: (
      lambda x, positions, inv_freq: x if x.shape[2] == 1
      else real(x, positions, inv_freq)))


def _kv_norm_dropped(parts):
  """``kv_a_norm`` left out (the norm of the 8-wide latent of the toy)."""
  from distributed_embeddings_tpu.layers import latent_attention
  kv_rank = parts.model.config.kv_lora_rank
  return _patched(parts, latent_attention, "rms_norm", lambda real: (
      lambda x, gain, eps: x if x.shape[-1] == kv_rank
      else real(x, gain, eps)))


def _scale_dropped(parts):
  return _with_config(parts, routed_scaling_factor=1.0)


def _shifted_by_one(parts):
  """The module held to the token one ahead."""
  return dataclasses.replace(parts, loss_fn=lambda outputs, labels: (
      parts.loss_fn(outputs, dict(labels, targets_2=labels["targets"]))))


def _module_loss_dropped(parts):
  from distributed_embeddings_tpu.layers.decoder import next_token_loss
  return dataclasses.replace(parts, loss_fn=next_token_loss)


ALL = ["loss_gap", "table_change_gap", "dense_change_gap"]


@pytest.mark.parametrize("broken,fails", [
    (None, []),
    ("key_rope_dropped", ALL),
    ("kv_norm_dropped", ALL),
    ("scale_dropped", ALL),
    ("shifted_by_one", ALL),
    ("module_loss_dropped", ALL),
    ("control", ALL),
])
def test_a_run_of_the_family(root, capsys, monkeypatch, broken, fails):
  cell = specs.load_cell(CELL, root)
  devices, dev = bench_toy.cpu_devices(1)
  changes = {"key_rope_dropped": _key_rope_dropped,
             "kv_norm_dropped": _kv_norm_dropped,
             "scale_dropped": _scale_dropped,
             "shifted_by_one": _shifted_by_one,
             "module_loss_dropped": _module_loss_dropped}
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
  assert result.correct == (broken is None)
  for name in fails:
    assert verdict[name] == "OUTSIDE", out
  assert verdict["fill"] == verdict["untouched"] == "ok"
  assert result.attempted > 1 and result.failed == 0


# ---- the control, one reference after the other ------------------------------
FAULTS = ("no_key_rope", "no_kv_norm", "no_scale", "mtp_shift_one",
          "no_mtp_loss")


@pytest.fixture(scope="module")
def control_lines(root):
  """`control_sequential.control` on the toy cell, one seed, the control and
  the family's five faults: -> (seeds the control was inside on, stand-in
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
  toy configuration's limits: each says ``"correct": false``, outside all
  three; the module's loss left out reads exactly 1 on a leaf of the
  module."""
  inside, lines, text = control_lines
  assert inside == 0 and set(lines) == {"bfloat16", *FAULTS}
  line = lines[stand_in]
  assert line["correct"] is False and line["seed"] == 2**31 + 77
  assert line["outside"] == list(LIMITS)
  assert line["outside"] == [k for k in LIMITS if line[k] > LIMITS[k]]
  if stand_in == "no_mtp_loss":
    assert line["dense_change_gap"] == 1.0
    (where,) = [ln for ln in text.splitlines()
                if ln.startswith("compare dense_change_gap: 1 at mtp_")]
    assert "OUTSIDE" in where


# ---- the new metrics' readers, on a hand-built trace ------------------------
STACK = "jit(step_fn)/jit(local_step)/"
FWD = STACK + "jvp(de_model)/GlmMoeLite/checkpoint/"
REBUILT = STACK + "transpose(jvp(de_model))/GlmMoeLite/checkpoint/" \
    "rematted_computation/"
BWD = STACK + "transpose(jvp(de_model))/GlmMoeLite/checkpoint/"
OPS = {  # op -> (name stack, start ns, duration ns)
    "fusion.1": (FWD + "de_attention/mul", 0, 40),               # the norm
    "fusion.2": (FWD + "de_attention/de_mla_down/dot_general", 40, 300),
    "fusion.3": (FWD + "de_attention/de_mla_up/dot_general", 340, 500),
    "fusion.4": (REBUILT + "de_attention/de_mla_down/mul", 840, 20),
    "fusion.5": (REBUILT + "de_attention/de_mla_up/dot_general", 860, 500),
    "fusion.6": (BWD + "de_attention/de_mla_up/dot_general", 1360, 1000),
    "fusion.7": (BWD + "de_attention/de_mla_down/dot_general", 2360, 600),
    "fusion.8": (FWD + "de_attention/de_attn_qk/mul", 2960, 60),
    "fusion.9": (FWD + "de_attention/de_attn_proj/dot_general", 3020, 100),
    "fusion.10": (FWD + "de_attention/de_attn_core/transpose", 3120, 30),
    "splash_mha_fwd.11": (FWD + "de_attention/de_attn_core/pallas_call",
                          3150, 200),
    "splash_mha_dkv.12": (BWD + "de_attention/de_attn_core/pallas_call",
                          3350, 500),
    # the module: a layer of its own inside de_mtp
    "fusion.13": (FWD + "de_mtp/dot_general", 3850, 80),         # W_eh
    "fusion.14": (FWD + "de_mtp/de_attention/de_mla_down/dot_general",
                  3930, 300),
    "splash_mha_fwd.15": (
        FWD + "de_mtp/de_attention/de_attn_core/pallas_call", 4230, 200),
    "fusion.16": (FWD + "de_mtp/de_moe/de_moe_route/de_moe_router/top_k",
                  4430, 100),
    "fusion.17": (STACK + "jvp(de_model)/GlmMoeLite/de_mtp/de_lm_head/"
                  "dot_general", 4530, 70),
    "fusion.18": (STACK + "transpose(jvp(de_model))/GlmMoeLite/de_mtp/"
                  "de_lm_head/dot_general", 4600, 140),
    "fusion.19": (STACK + "jvp(de_loss)/de_mtp/reduce", 4740, 10),
    "fusion.20": (STACK + "jvp(de_model)/GlmMoeLite/de_lm_head/dot_general",
                  4750, 60),
    "fusion.21": (STACK + "de_dense_update/add", 4810, 150),
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


def test_the_new_readers_on_a_hand_built_trace():
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_parts": scope_parts.attribute(red, names)}
  read = lambda m: cell.layer_reader(m)(red, ctx)
  # the trunk's and the module's, all three passes
  assert read("mla_down_ms") == pytest.approx((300 + 20 + 600 + 300) * 1e-6)
  assert read("mla_up_ms") == pytest.approx(2000e-6)
  # the module whole: W_eh, its layer, its head and its cross-entropy
  assert read("mtp_ms") == pytest.approx(
      (80 + 300 + 200 + 100 + 70 + 140 + 10) * 1e-6)
  assert read("mtp_head_ms") == pytest.approx(210e-6)
  c, mix = cell.config, cell.traffic
  mxu = lambda flops, ns: 100 * flops / 197e12 / (ns * 1e-9)
  assert read("mla_proj_mxu_pct") == pytest.approx(
      mxu(roofline_glm.mla_proj_flops(c, mix), 3220))
  assert read("splash_d256_mxu_pct") == pytest.approx(
      mxu(roofline_glm.splash_flops(c, mix), 900))
  # the accepted part readers this cell joins read a scope and no model: the
  # module's layer is in them too
  assert read("attn_proj_ms") == pytest.approx(100e-6)
  assert read("attn_qk_ms") == pytest.approx(60e-6)
  assert read("attn_layout_ms") == pytest.approx(30e-6)
  assert read("moe_router_ms") == pytest.approx(100e-6)
  assert read("remat_forward_ms") == pytest.approx(520e-6)
  # a program without the scopes: the ms read 0.0 as a scope of scope_reduce
  # does, the shares have nothing to divide by
  strip = lambda s: "/".join(
      part for part in s.split("/")
      if part not in ("de_mla_down", "de_mla_up", "de_mtp", "de_attention"))
  red, bare = _hand_built({op: (strip(s), a, d)
                           for op, (s, a, d) in OPS.items()})
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_parts": scope_parts.attribute(red, bare)}
  for name in MS:
    assert cell.layer_reader(name)(red, ctx) == 0.0, name
  for name in SHARES:
    assert cell.layer_reader(name)(red, ctx) is None, name


def test_the_child_scope_readers_count_the_modules_layer_too():
  """`scope_children.scope_ms` puts an op under every name its stack holds:
  the module's attention is in ``attn_ms``, its experts in ``moe_ms``, its
  head and its cross-entropy in ``lm_head_ms`` beside the trunk's."""
  from benchmark import scope_children
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_children": scope_children.per_step_ns(red, names)}
  read = lambda m: cell.layer_reader(m)(red, ctx)
  trunk = 40 + 300 + 500 + 20 + 500 + 1000 + 600 + 60 + 100 + 30 + 200 + 500
  assert read("attn_ms") == pytest.approx((trunk + 300 + 200) * 1e-6)
  assert read("moe_ms") == pytest.approx(100e-6)
  assert read("lm_head_ms") == pytest.approx((70 + 140 + 10 + 60) * 1e-6)


def test_the_two_counting_functions_on_hand_counted_cases():
  cell = specs.load_cell(CELL)
  c, mix = cell.config, cell.traffic
  assert roofline_glm.attention_layers(c) == 6     # five and the module's
  # ISSUE 47: 11,272,192 weights in the four latent products of a layer
  assert roofline_glm.latent_weights(c) \
      == 1572864 + 3932160 + 1179648 + 4587520 == 11272192
  assert roofline_glm.mla_proj_flops(c, mix) == 6 * 11272192 * 8192 * 6
  assert roofline_glm.mla_proj_flops(c, mix) == pytest.approx(3.3e12, rel=0.01)
  # attention: 19.05 M expected pairs at 20 heads of 256, six layers
  pairs = roofline_laguna.expected_pairs(8192, 4096)
  assert pairs == pytest.approx(19.05e6, rel=2e-3)
  assert roofline_glm.splash_flops(c, mix) \
      == pytest.approx(12 * 256 * 20 * pairs * 6)
  assert roofline_glm.splash_flops(c, mix) == pytest.approx(7.0e12, rel=0.01)
  # a toy counted by hand: 2 tokens in one document (1 + 2 pairs), hidden 4,
  # one trunk layer and the module
  toy = dict(c, seq_len=2, hidden_size=4, mean_document_length=10 ** 9,
             layers_here=[3], num_attention_heads=5, q_lora_rank=3,
             kv_lora_rank=2, qk_nope_head_dim=7, qk_rope_head_dim=2,
             v_head_dim=11)
  one = dict(mix, global_batch=1)
  weights = 4 * 3 + 3 * 5 * 9 + 4 * 4 + 2 * 5 * 18
  assert roofline_glm.latent_weights(toy) == weights == 343
  assert roofline_glm.mla_proj_flops(toy, one) == 6 * 343 * 2 * 2
  assert roofline_glm.splash_flops(toy, one) \
      == pytest.approx((6 * 9 + 6 * 11) * 5 * 3 * 2, rel=1e-6)
  for fn in (roofline_glm.mla_proj_flops, roofline_glm.splash_flops):
    assert fn(toy, dict(mix, global_batch=3)) == pytest.approx(
        3 * fn(toy, one))


def test_the_expert_layers_counters_on_the_toy(root, capsys):
  """`tools/moe_load.py` on this cell: a layer's load and the share of the
  choices the bias moved, the trunk's expert layers and the module's."""
  spec = importlib.util.spec_from_file_location(
      "moe_load", os.path.join(bench_toy.ROOT, "tools", "moe_load.py"))
  tool = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(tool)
  report = tool.main([CELL, "--seed", "3", "--root", root])
  assert report["positions_a_layer"] == 4 * 48
  assert report["experts_held"] == [4, 8]
  assert len(report["assignments_on_held_experts"]) == 2 + 1
  assert report["dropped"] == [0] * 3
  assert all(0 < m < 0.5 for m in report["moved_share"])
  expected = 4 * 48 * 4 * 8 / 16
  assert report["load_over_expected"] == [
      round(a / expected, 3) for a in report["assignments_on_held_experts"]]
  assert json.loads(capsys.readouterr().out.splitlines()[-1]) == report
  report = tool.main([CELL, "--seed", "3", "--root", root, "--layers", "2"])
  assert len(report["moved_share"]) == 1 + 1
