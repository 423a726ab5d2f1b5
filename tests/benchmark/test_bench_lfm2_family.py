"""The lfm2_moe family (`benchmark/families/lfm2_moe.py`,
`configs/lfm2-24b-a2b-ep8share.json`, `workloads/lfm2_packed_16k.json`) at
toy widths through ``run.run_cell`` on the CPU: the sound program is correct;
the selection bias dropped from the choice, the weights gathered from the
biased scores, the convolution's reset dropped (each on the timed path) and
the bfloat16 control each come out wrong by a comparison of their own. The
family was added as files: every file the benchmark had keeps its bytes. The
new metrics' readers read a hand-built trace, and a program without the
scopes gives them nothing to read; the three counting functions on cases
counted by hand."""

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
from test_bench_keye_family import _rebuilt   # a changed program's step
from benchmark import (
    control_sequential,
    reference,
    roofline_laguna,
    roofline_lfm2,
    run,
    scope_parts,
    scope_reduce,
    specs,
    traffic,
)

CELL = "lfm2_moe_train_1chip"
NAME = "lfm2-24b-a2b-ep8share"
CONFIG = f"benchmark/configs/{NAME}.json"
MIX = "benchmark/workloads/lfm2_packed_16k.json"
MS = ("short_conv_ms", "conv_proj_ms", "conv_gate_ms", "dense_mlp_ms")
SHARES = ("conv_gate_hbm_pct", "moe_experts_w1536_mxu_pct",
          "splash_d64_mxu_pct")
METRICS = MS + SHARES
NEW = ("benchmark/families/lfm2_moe.py", CONFIG, MIX,
       "benchmark/roofline_lfm2.py",
       "tests/benchmark/test_bench_lfm2_family.py") + tuple(
           f"benchmark/layer_metrics/{m}.{ext}" for ext in ("json", "py")
           for m in METRICS)
PARENT = "77113ce48e1d1e94ebf0cb90f0c7e35c3db97da5"   # PR 41
# the general metrics and the scope readers that read no model's sizes
APPENDED_TO = ("host_feed_ms", "step_device_ms", "device_idle_pct",
               "route_ms", "gather_ms", "combine_ms", "onehot_ms",
               "dense_model_ms", "dense_update_ms", "sparse_apply_ms",
               "unscoped_pct", "attn_ms", "moe_ms", "moe_route_ms",
               "moe_experts_ms", "lm_head_ms", "attn_proj_ms", "attn_qk_ms",
               "attn_layout_ms", "moe_router_ms", "moe_sort_ms",
               "moe_dispatch_ms", "moe_return_ms", "remat_forward_ms")
LAYERS = {"short_conv_ms": "short convolution (layers/short_conv.py)",
          "conv_proj_ms": "short convolution (layers/short_conv.py)",
          "conv_gate_ms": "short convolution (layers/short_conv.py)",
          "conv_gate_hbm_pct": "short convolution (layers/short_conv.py)",
          "dense_mlp_ms": "model (models/lfm2_moe.py)",
          "moe_experts_w1536_mxu_pct": "expert layer (layers/moe.py)",
          "splash_d64_mxu_pct": "kernels (splash attention, "
                                "jax.experimental.pallas.ops.tpu)"}
LIMITS = {"loss_gap": 2e-5, "table_change_gap": 0.03,
          "dense_change_gap": 0.03}


def _shrink(c):
  c.update(hidden_size=32, intermediate_size=48, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, moe_intermediate_size=12,
           num_experts=16, experts_held=[4, 8], vocab_here=96, seq_len=48,
           mean_document_length=16, init_scale=0.3, attention="xla")
  c["assumed_sizes"]["expert_bias_spread"] = 0.1
  c["optimizer"]["learning_rate"] = 1e-3
  # CPU, 3 seeds: the sound program reads loss_gap <= 9.9e-8,
  # table_change_gap <= 1.4e-5 and dense_change_gap <= 7.9e-4 (an expert
  # matrix); the bfloat16 control and the family's three faults are held below
  c["check_limits"] = dict(LIMITS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("lfm2_root")))
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
  for was, now in zip(old["per_layer"], new["per_layer"]):
    assert {k: v for k, v in now.items() if k != "workloads"} \
        == {k: v for k, v in was.items() if k != "workloads"}
    n = len(was["workloads"])
    assert now["workloads"][:n] == was["workloads"]
    assert (CELL in now["workloads"][n:]) == (was["name"] in APPENDED_TO)
  added = new["per_layer"][len(old["per_layer"]):]
  assert [m["name"] for m in added[:7]] == list(METRICS)
  layers = {m["layer"] for m in old["per_layer"]}
  for m in added[:7]:
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
  # two new layers, the others spelt as the benchmark had them
  assert {m["layer"] for m in added[:7]} - layers == {
      LAYERS["short_conv_ms"], LAYERS["dense_mlp_ms"]}
  cell = {w["name"]: w for w in new["workloads"]}[CELL]
  assert (cell["config"], cell["traffic"], cell["chips"]) == (
      NAME, "lfm2_packed_16k", 1)
  assert len(cell["why"]) <= 200 and "1/8" in cell["why"] \
      and "8x" in cell["why"] and "16384" in cell["why"]
  config = {c["name"]: c for c in new["configs"]}[NAME]
  assert config["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"] and len(config["why"]) <= 200
  assert config["file"] == CONFIG
  assert sum(w["chips"] == 4 for w in new["workloads"]) == 1


def _catalog_row():
  path = "/opt/skills/guides/model-configs/architectures.jsonl"
  if not os.path.exists(path):
    return None
  with open(path) as f:
    rows = [json.loads(line) for line in f]
  return {r["name"]: r for r in rows}.get("LFM2-24B-A2B")


def test_the_configuration_states_the_published_widths_and_its_cuts():
  cell = specs.load_cell(CELL)
  c = cell.config
  published = dict(
      model_type="lfm2_moe", conv_L_cache=3, conv_bias=False,
      hidden_size=2048, intermediate_size=11776,
      max_position_embeddings=128000, moe_intermediate_size=1536,
      norm_eps=1e-5, norm_topk_prob=True, num_attention_heads=32,
      num_dense_layers=2, num_experts=64, num_experts_per_tok=4,
      num_hidden_layers=40, num_key_value_heads=8,
      rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
      routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)
  assert {k: c[k] for k in published} == published
  assert c["layer_types"] == ["conv", "conv"] \
      + ["full_attention", "conv", "conv", "conv"] * 9 \
      + ["full_attention", "conv"]
  row = _catalog_row()
  if row is not None:   # the catalog beside the guide, where it is at hand
    assert c["source"] == row["source_url"]
    assert {k: c[k] for k in row["config"]} == row["config"]
  # the three cuts, and the layers they leave: one leading dense layer, then
  # a whole period in its published order
  assert (c["layers_here"], c["experts_held"], c["vocab_here"]) \
      == ([0, 2, 3, 4, 5], [0, 8], 65536 // 8)
  assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
  assert set(c["reduced_why"]) == set(c["reduced"])
  family = cell.family()
  assert family.sizes(c)["kinds"] == (
      ("conv", "dense"), ("full_attention", "experts"), ("conv", "experts"),
      ("conv", "experts"), ("conv", "experts"))
  assert c["head_dim"] == c["hidden_size"] // c["num_attention_heads"] == 64
  for words in ("eight chips share each layer", "8 of the 64",
                "all heads held", "pipeline stages", "without its exchange"):
    assert words in c["deployment"], words
  for key in ("head_dim", "untied head", "expert_bias", "renormalisation",
              "norm placement", "convolution",
              "reset at a document's first token", "rotary embedding",
              "router", "initialisers", "documents as numerical features",
              "objective", "optimizer", "seq_len", "attention path"):
    assert key in c["assumed"], key
  assert "Adam leaves where it was" in c["assumed"]["expert_bias"]
  assert set(c["assumed_sizes"]) == {"conv_init_bound", "expert_bias_spread"}
  assert c["assumed_sizes"]["conv_init_bound"] == pytest.approx(3 ** -0.5)
  assert 0 < c["assumed_sizes"]["expert_bias_spread"] < 0.1
  assert set(c["check_limits"]) == set(LIMITS)
  # under 1: an update that never happened reads 1.0 on its leaf
  assert c["check_limits"]["dense_change_gap"] < 1
  assert "bfloat16 control" in c["check_limits_why"]
  spec = family.model_spec(c)
  n = sum(int(np.prod(v[0])) for v in spec.dense_leaves.values())
  # ISSUE 42's count
  conv, attention = 2048 * 6144 + 3 * 2048 + 2048 * 2048, \
      2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
  mlp, experts = 3 * 2048 * 11776, 8 * 3 * 2048 * 1536 + 2048 * 64 + 64
  assert (conv, attention, mlp, experts) == (
      16783360, 10485888, 72351744, 75497472 + 131072 + 64)
  layer0 = conv + mlp + 2 * 2048
  period = attention + 3 * conv + 4 * (experts + 2 * 2048)
  assert round(layer0 / 1e6, 2) == 89.14 and round(period / 1e6, 2) == 363.37
  assert n == layer0 + period + 2048 + 2048 * 8192 == 469285248
  assert round((n + 8192 * 2048) * 12 / 1e9, 2) == 5.83
  assert len(spec.dense_leaves) == 2 + 8 + 13 + 3 * 10
  assert spec.dense_leaves["layer_0_w_in"][0] == (2048, 6144)
  assert spec.dense_leaves["layer_0_conv"] == ((3, 2048), 0.57735027)
  assert spec.dense_leaves["layer_0_w_gate"][0] == (2048, 11776)
  assert spec.dense_leaves["layer_1_wq"][0] == (2048, 2048)
  assert spec.dense_leaves["layer_1_wk"][0] == (2048, 512)
  assert spec.dense_leaves["layer_1_q_norm"] == ((64,), 0.0, 1.0)
  assert spec.dense_leaves["layer_1_expert_bias"] == ((64,), 0.03)
  assert spec.dense_leaves["layer_4_w_down"][0] == (8, 1536, 2048)
  assert spec.dense_leaves["layer_4_router"][0] == (2048, 64)
  assert "layer_0_router" not in spec.dense_leaves
  assert spec.n_numerical == c["seq_len"] and spec.summed_tables == {0}
  assert (spec.inputs[0].hotness, spec.inputs[0].sequence,
          spec.inputs[0].rows) == (c["seq_len"], True, 8192)
  mix = cell.traffic
  assert (mix["global_batch"], mix["alpha"], mix["pool_batches"],
          mix["steps_in_flight"], mix["numerical_range"]) == (
              1, 1.05, 16, 3, [0, 1])
  assert (c["seq_len"], c["mean_document_length"]) == (16384, 4096)


def test_a_program_without_the_model_says_so_at_once(root, monkeypatch):
  """What the parent of this PR does with these files laid over it."""
  real = importlib.util.find_spec
  monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None
                      if name.endswith("models.lfm2_moe")
                      else real(name, *a))
  cell = specs.load_cell(CELL, root)
  with pytest.raises(specs.SpecError, match="no .*models/lfm2_moe.py"):
    cell.family().model_spec(cell.config)


def test_the_familys_batch_and_its_documents(root):
  _, _, _, pool = _setup(root, 2**33 + 1)
  b = pool[0]
  assert b.cats.shape == (4, 48) and b.numerical.shape == (4, 48)
  assert np.array_equal(b.labels["targets"][:, :-1], b.cats[:, 1:])
  assert not b.labels["targets"][:, -1].any()
  assert 0 <= b.numerical.min() and b.numerical.max() < 1
  assert b.cats.max() < 96
  starts = np.concatenate([b.numerical for b in pool]) < 1 / 16
  assert 0.02 < starts[:, 1:].mean() < 0.15   # documents do start mid-way


def test_the_references_counters_are_the_programs(root):
  """The reference's own count of the assignments on the held experts and of
  the choices the bias moved, an expert layer, against `moe_share`'s."""
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
  assert np.array_equal(ours["moe"]["assignments"], theirs["assignments"])
  assert np.array_equal(ours["moe"]["moved"], theirs["moved"])
  assert theirs["moved"].shape == (4,) and 0 < int(theirs["moved"].min()) \
      and int(theirs["moved"].max()) < 4 * 48 * 4 // 2


# ---- broken timed paths, each caught by a named comparison -----------------
def _patched(parts, module, name, stand_in):
  """The model with ``module.name`` replaced while it is traced."""
  class Patched:
    config = parts.model.config

    def apply(self, *args, **kwargs):
      real = getattr(module, name)
      setattr(module, name, stand_in(real))
      try:
        return parts.model.apply(*args, **kwargs)
      finally:
        setattr(module, name, real)
  return dataclasses.replace(parts, model=Patched())


def _bias_dropped(parts):
  """The experts chosen on the unbiased scores."""
  from distributed_embeddings_tpu.layers import moe
  return _patched(parts, moe, "route", lambda real: (
      lambda h, w, k, router=moe.Router(), bias=None: real(h, w, k, router)))


def _weights_biased(parts):
  """The chosen experts weighted by ``s + b``."""
  from distributed_embeddings_tpu.layers import moe

  def stand_in(real):
    def route(h, w, k, router=moe.Router(), bias=None):
      if bias is None:   # the counters' second, unbiased choice
        return real(h, w, k, router)
      _, top_e = real(h, w, k, router, bias)
      scores = jax.nn.sigmoid(jnp.dot(
          h, w, precision=jax.lax.Precision.HIGHEST)) + bias
      top_p = jnp.take_along_axis(scores, top_e, axis=-1)
      return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e
    return route
  return _patched(parts, moe, "route", stand_in)


def _reset_dropped(parts):
  """The convolution reads across a document's first token."""
  from distributed_embeddings_tpu.layers import short_conv
  return _patched(parts, short_conv, "causal_conv", lambda real: (
      lambda x, w, seg: real(x, w, jnp.zeros_like(seg))))


@pytest.mark.parametrize("broken,fails", [
    (None, []),
    ("bias_dropped", ["loss_gap", "table_change_gap", "dense_change_gap"]),
    ("weights_biased", ["loss_gap", "dense_change_gap"]),
    ("reset_dropped", ["loss_gap", "table_change_gap", "dense_change_gap"]),
    ("control", ["loss_gap", "table_change_gap", "dense_change_gap"]),
])
def test_a_run_of_the_family(root, capsys, monkeypatch, broken, fails):
  cell = specs.load_cell(CELL, root)
  devices, dev = bench_toy.cpu_devices(1)
  changes = {"bias_dropped": _bias_dropped, "weights_biased": _weights_biased,
             "reset_dropped": _reset_dropped}
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
FAULTS = ("no_bias", "biased_weights", "no_reset")


@pytest.fixture(scope="module")
def control_lines(root):
  """`control_sequential.control` on the toy cell, one seed, the control and
  the family's three faults: -> (seeds the control was inside on, stand-in
  -> its line of JSON)."""
  said = io.StringIO()
  with contextlib.redirect_stdout(said):
    inside = control_sequential.control(
        specs.load_cell(CELL, root), [2**31 + 77], ["bfloat16", *FAULTS])
  lines = [json.loads(ln) for ln in said.getvalue().splitlines()
           if ln.startswith("{")]
  return inside, {ln["stand_in"]: ln for ln in lines}


@pytest.mark.parametrize("stand_in", ["bfloat16", *FAULTS])
def test_the_sequential_control_judges_a_stand_in_as_the_check_does(
    control_lines, stand_in):
  """Reference against reference, by the check's own `Compared` under the
  toy configuration's limits: each says ``"correct": false``, outside all
  three."""
  inside, lines = control_lines
  assert inside == 0 and set(lines) == {"bfloat16", *FAULTS}
  line = lines[stand_in]
  assert line["correct"] is False and line["seed"] == 2**31 + 77
  assert line["outside"] == list(LIMITS)
  assert line["outside"] == [k for k in LIMITS if line[k] > LIMITS[k]]


# ---- the new metrics' readers, on a hand-built trace ------------------------
STACK = "jit(step_fn)/jit(local_step)/"
FWD = STACK + "jvp(de_model)/Lfm2Moe/checkpoint/"
REBUILT = STACK + "transpose(jvp(de_model))/Lfm2Moe/checkpoint/" \
    "rematted_computation/"
BWD = STACK + "transpose(jvp(de_model))/Lfm2Moe/checkpoint/"
OPS = {  # op -> (name stack, start ns, duration ns)
    "fusion.1": (FWD + "de_short_conv/mul", 0, 40),            # the norm
    "fusion.2": (FWD + "de_short_conv/de_conv_proj/dot_general", 40, 300),
    "fusion.3": (FWD + "de_short_conv/de_conv_gate/mul", 340, 100),
    "fusion.4": (REBUILT + "de_short_conv/de_conv_gate/mul", 440, 100),
    "fusion.5": (BWD + "de_short_conv/de_conv_gate/mul", 540, 200),
    "fusion.6": (BWD + "de_short_conv/de_conv_proj/dot_general", 740, 600),
    "fusion.7": (FWD + "de_mlp/dot_general", 1340, 500),
    "fusion.8": (BWD + "de_mlp/dot_general", 1840, 1000),
    "fusion.9": (FWD + "de_attention/de_attn_proj/dot_general", 2840, 100),
    "fusion.10": (FWD + "de_attention/de_attn_core/transpose", 2940, 30),
    "splash_mqa_fwd.11": (FWD + "de_attention/de_attn_core/pallas_call",
                          2970, 200),
    "splash_mqa_dkv.12": (BWD + "de_attention/de_attn_core/pallas_call",
                          3170, 500),
    "fusion.13": (FWD + "de_moe/de_moe_experts/mul", 3670, 50),
    "ragged-dot-none.14": ("", 3720, 450),    # XLA's kernel: no name stack
    "fusion.15": (FWD + "de_moe/de_moe_route/de_moe_router/top_k", 4170, 100),
    "fusion.16": (STACK + "jvp(de_model)/Lfm2Moe/de_lm_head/dot_general",
                  4270, 60),
    "fusion.17": (STACK + "de_dense_update/add", 4330, 150),
}


def _hand_built(ops_table=None):
  ops_table = ops_table or OPS
  names = scope_reduce.OpNames(
      {op: s for op, (s, _, _) in ops_table.items()}, {})
  ops = [(op, start, dur, 0) for op, (_, start, dur) in ops_table.items()]

  class Red:
    steps = [[("jit_step_fn(7)", 0, 4500)]]
  red = Red()
  red.ops = [ops]
  return red, names


def test_the_new_readers_on_a_hand_built_trace():
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_parts": scope_parts.attribute(red, names)}
  read = lambda m: cell.layer_reader(m)(red, ctx)
  assert read("conv_proj_ms") == pytest.approx(900e-6)
  assert read("conv_gate_ms") == pytest.approx(400e-6)
  # the two parts and the norm are the mixer
  assert read("short_conv_ms") == pytest.approx(1340e-6)
  assert read("dense_mlp_ms") == pytest.approx(1500e-6)
  c, mix = cell.config, cell.traffic
  assert read("conv_gate_hbm_pct") == pytest.approx(
      100 * roofline_lfm2.conv_gate_bytes(c, mix) / 819e9 / 400e-9)
  mxu = lambda flops, ns: 100 * flops / 197e12 / (ns * 1e-9)
  assert read("splash_d64_mxu_pct") == pytest.approx(
      mxu(roofline_lfm2.splash_flops(c, mix), 700))
  # the accepted part readers this cell joins read a scope and no model
  assert read("attn_proj_ms") == pytest.approx(100e-6)
  assert read("attn_layout_ms") == pytest.approx(30e-6)
  assert read("moe_router_ms") == pytest.approx(100e-6)
  assert read("remat_forward_ms") == pytest.approx(100e-6)
  # a program without the scopes (the parent, on any cell): the ms read 0.0
  # as a scope of scope_reduce does, the shares have nothing to divide by
  strip = lambda s: "/".join(
      part for part in s.split("/")
      if part not in ("de_short_conv", "de_conv_proj", "de_conv_gate",
                      "de_mlp", "de_attention"))
  red, bare = _hand_built({op: (strip(s), a, d)
                           for op, (s, a, d) in OPS.items()})
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_parts": scope_parts.attribute(red, bare)}
  for name in MS:
    assert cell.layer_reader(name)(red, ctx) == 0.0, name
  for name in ("conv_gate_hbm_pct", "splash_d64_mxu_pct"):
    assert cell.layer_reader(name)(red, ctx) is None, name


def test_the_experts_share_reads_the_trace_as_moe_experts_ms_does(
    tmp_path, monkeypatch):
  """`scope_children.scope_ms` opens the run's ``.xplane.pb``; here its
  attribution is handed in: the time under ``de_moe_experts`` with XLA's
  ragged-dot kernels."""
  from benchmark import scope_children
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_children": scope_children.per_step_ns(red, names)}
  got = cell.layer_reader("moe_experts_w1536_mxu_pct")(red, ctx)
  assert cell.layer_reader("moe_experts_ms")(red, ctx) \
      == pytest.approx(500e-6)
  assert got == pytest.approx(
      100 * roofline_lfm2.moe_experts_flops(cell.config, cell.traffic)
      / 197e12 / 500e-9)
  red, bare = _hand_built({"fusion.1": OPS["fusion.1"]})
  ctx["scope_children"] = scope_children.per_step_ns(red, bare)
  assert cell.layer_reader("moe_experts_w1536_mxu_pct")(red, ctx) is None


def test_the_three_counting_functions_on_hand_counted_cases():
  cell = specs.load_cell(CELL)
  c, mix = cell.config, cell.traffic
  assert roofline_lfm2.kinds(c) == [("conv", False), ("full_attention", True),
                                    ("conv", True), ("conv", True),
                                    ("conv", True)]
  # the gate chain: 11 arrays of [16384, 2048] float32 a layer, four layers
  assert roofline_lfm2.conv_gate_bytes(c, mix) \
      == 11 * 4 * 16384 * 2048 * 4 == 5905580032
  # ISSUE 42: 290 kB a token a layer is what a chain of separate passes
  # moves; the least is 11 x 8 kB = 90 kB
  assert roofline_lfm2.conv_gate_bytes(c, mix) / (4 * 16384) == 90112
  # the experts: 1,024 expected assignments a held expert, four layers
  assignments = 16384 * 4 * 8 / 64
  assert assignments == 8 * 1024
  assert roofline_lfm2.moe_experts_flops(c, mix) \
      == 6 * 3 * 2048 * 1536 * assignments * 4
  # attention: 50.6 M expected pairs (ISSUE 42) at 32 heads of 64, one layer
  pairs = roofline_laguna.expected_pairs(16384, 4096)
  assert pairs == pytest.approx(50.6e6, rel=2e-3)
  assert roofline_lfm2.splash_flops(c, mix) \
      == pytest.approx(12 * 64 * 32 * pairs)
  # a toy counted by hand: 2 tokens x hidden 4, one conv layer and one
  # attention layer with experts; 2 queries in one document see 1 + 2 keys
  toy = dict(c, seq_len=2, hidden_size=4, mean_document_length=10 ** 9,
             layers_here=[1, 2], head_dim=3, num_attention_heads=5,
             moe_intermediate_size=7, num_experts=4, num_experts_per_tok=2,
             experts_held=[0, 2])
  one = dict(mix, global_batch=1)
  assert roofline_lfm2.kinds(toy) == [("conv", False),
                                      ("full_attention", True)]
  assert roofline_lfm2.conv_gate_bytes(toy, one) == 11 * 4 * 2 * 4
  assert roofline_lfm2.moe_experts_flops(toy, one) \
      == 6 * 3 * 4 * 7 * (2 * 2 * 2 / 4)
  assert roofline_lfm2.splash_flops(toy, one) \
      == pytest.approx(12 * 3 * 5 * 3, rel=1e-6)
  for fn in (roofline_lfm2.conv_gate_bytes, roofline_lfm2.moe_experts_flops,
             roofline_lfm2.splash_flops):
    assert fn(toy, dict(mix, global_batch=3)) == pytest.approx(
        3 * fn(toy, one))
  assert roofline_lfm2.hbm_pct(819e9, 1000.0, "TPU v5 lite") == 100.0
  assert roofline_lfm2.hbm_pct(1.0, 0.0, "TPU v5 lite") is None


def test_the_expert_layers_counters_on_the_toy(root, capsys):
  """`tools/moe_load.py` on this cell: a layer's load and the share of the
  choices the bias moved."""
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
  assert len(report["moved_share"]) == 1
