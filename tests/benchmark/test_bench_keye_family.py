"""The keye_sparse family (`benchmark/families/keye_sparse.py`,
`configs/keye-vl2-30b-a3b-ep8share.json`, `workloads/keye_packed_8k.json`) at
toy widths through ``run.run_cell`` on the CPU: the sound program is correct;
the selection dropped (dense causal attention on the timed path), the
indexers' loss left out, the indexer's input left attached and the bfloat16
control each come out wrong by a comparison of their own (and half the
``topk`` too, on the reference's side). The family was added as files: every file the
benchmark had keeps its bytes. The new metrics' readers read a hand-built
trace, and a program without the scopes gives them nothing to read; the
pairs the mix is expected to leave are counted by drawing its documents."""

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
from benchmark import (
    control_sequential,
    program,
    reference,
    roofline_keye,
    run,
    scope_parts,
    scope_reduce,
    specs,
    traffic,
)

CELL = "keye_dsa_train_1chip"
NAME = "keye-vl2-30b-a3b-ep8share"
CONFIG = f"benchmark/configs/{NAME}.json"
MIX = "benchmark/workloads/keye_packed_8k.json"
MS = ("sparse_index_ms", "index_scores_ms", "index_select_ms",
      "index_loss_ms", "attn_core_ms")
METRICS = MS + ("sparse_attn_mxu_pct", "index_scores_mxu_pct")
NEW = ("benchmark/families/keye_sparse.py", CONFIG, MIX,
       "benchmark/roofline_keye.py",
       "tests/benchmark/test_bench_keye_family.py") + tuple(
           f"benchmark/layer_metrics/{m}.{ext}" for ext in ("json", "py")
           for m in METRICS)
PARENT = "c5587ada5ccb47fa710e74e76b82a1f4677ec8d9"   # PR 39
# the general metrics and the scope readers that read no model's sizes
APPENDED_TO = ("host_feed_ms", "step_device_ms", "device_idle_pct",
               "route_ms", "gather_ms", "combine_ms", "onehot_ms",
               "dense_model_ms", "dense_update_ms", "sparse_apply_ms",
               "unscoped_pct", "attn_ms", "moe_ms", "moe_route_ms",
               "moe_experts_ms", "lm_head_ms", "attn_proj_ms", "attn_qk_ms",
               "attn_layout_ms", "moe_router_ms", "moe_sort_ms",
               "moe_dispatch_ms", "moe_return_ms", "remat_forward_ms")
LIMITS = {"loss_gap": 2e-5, "table_change_gap": 0.03,
          "dense_change_gap": 0.03}


def _shrink(c):
  c.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, moe_intermediate_size=12, num_experts=16,
           num_experts_per_tok=3, experts_held=[4, 8], vocab_here=96,
           seq_len=48, mean_document_length=16, init_scale=0.3,
           num_hidden_layers_here=2)
  c["sa_config"].update(indexer_head_dim=8, indexer_num_heads=3, topk=6,
                        q_chunk_size=8)
  c["assumed_sizes"]["indexer_rotary_dim"] = 4
  c["optimizer"]["learning_rate"] = 1e-3
  # CPU, 3 seeds: the sound program reads loss_gap <= 9.1e-8,
  # table_change_gap <= 1.0e-5 and dense_change_gap <= 1.5e-3 (an indexer
  # matrix, whose gradient is the KL's alone and small); the bfloat16
  # control and the family's four faults are held below
  c["check_limits"] = dict(LIMITS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("keye_root")))
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
    # one new layer, spelt alike in the entry and in the metric's file
    assert m["layer"] == "sparse indexer (layers/sparse_index.py)" \
        and m["layer"] not in layers
    with open(os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                           m["name"] + ".json")) as f:
      spec = json.load(f)
    assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == (
        m["name"], m["layer"], m["unit"], m["moves"])
  cell = {w["name"]: w for w in new["workloads"]}[CELL]
  assert (cell["config"], cell["traffic"], cell["chips"]) == (
      NAME, "keye_packed_8k", 1)
  assert len(cell["why"]) <= 200 and "1/8" in cell["why"] \
      and "8x" in cell["why"]
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
  return {r["name"]: r for r in rows}.get("Keye-VL-2.0-30B-A3B")


def test_the_configuration_states_the_published_widths_and_its_cuts():
  cell = specs.load_cell(CELL)
  c = cell.config
  published = dict(
      model_type="KeyeVL2", attention_bias=False, decoder_sparse_step=1,
      head_dim=128, hidden_act="silu", hidden_size=2048,
      intermediate_size=6144, max_position_embeddings=262144,
      max_window_layers=48, mlp_only_layers=[], moe_intermediate_size=768,
      norm_topk_prob=True, num_attention_heads=32, num_experts=128,
      num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
      num_local_experts=128, rms_norm_eps=1e-6, rope_theta=10000000,
      rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                    "type": "default"},
      sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                 "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                 "q_chunk_size": 512, "topk": 2048},
      sliding_window=None, tie_word_embeddings=False,
      use_sliding_window=False, vocab_size=151936)
  assert {k: c[k] for k in published} == published
  row = _catalog_row()
  if row is not None:   # the catalog beside the guide, where it is at hand
    assert c["source"] == row["source_url"]
    assert {k: c[k] for k in row["config"]} == row["config"]
  # the widths are sdar-30b-a3b-ep8share's to the last key, and so is the cut
  sdar = specs.load_cell("sdar_moe_train_1chip").config
  for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts",
              "num_experts_per_tok", "vocab_size", "rms_norm_eps",
              "num_hidden_layers_here", "experts_held", "vocab_here",
              "init_scale", "optimizer", "reduced"):
    assert c[key] == sdar[key], key
  assert (c["num_hidden_layers_here"], c["experts_held"], c["vocab_here"]) \
      == (4, [0, 16], 151936 // 8)
  assert set(c["reduced_why"]) == set(c["reduced"])
  for words in ("eight chips share each layer", "16 of the 128",
                "all heads held", "pipeline stages", "without the exchange"):
    assert words in c["deployment"], words
  assert "vision tower" in c["not_run"]
  for key in ("q/k norm", "indexer", "indexer precision", "indexer loss",
              "selection's ties", "initialisers",
              "documents as numerical features", "objective", "optimizer",
              "seq_len", "mean_document_length"):
    assert key in c["assumed"], key
  assert c["assumed_sizes"] == {"indexer_rotary_dim": 32,
                                "index_loss_weight": 1.0}
  assert set(c["check_limits"]) == set(LIMITS)
  # under 1: an update that never happened reads 1.0 on its leaf
  assert c["check_limits"]["dense_change_gap"] < 1
  spec = cell.family().model_spec(c)
  n = sum(int(np.prod(v[0])) for v in spec.dense_leaves.values())
  # ISSUE 40's count: a layer is 16 experts of 4.72 M, 18.87 M of attention,
  # 0.26 M of router and 2.26 M of indexer, 96.9 M; the head 38.9 M
  expert, attention = 3 * 2048 * 768, 2048 * 128 * (2 * 32 + 2 * 4)
  indexer = 2048 * (16 * 64 + 64 + 16) + 2 * 64
  layer = 16 * expert + attention + 2048 * 128 + indexer + 2 * 2048 + 2 * 128
  assert indexer == 2261120 and 96.8e6 < layer < 97.0e6
  assert n == 4 * layer + 2048 + 2048 * 18992
  assert len(spec.dense_leaves) == 2 + 4 * 17
  assert spec.dense_leaves["layer_0_index_wq"][0] == (2048, 1024)
  assert spec.dense_leaves["layer_3_index_wk"][0] == (2048, 64)
  assert spec.dense_leaves["layer_3_index_ww"][0] == (2048, 16)
  assert spec.dense_leaves["layer_1_index_norm_bias"] == ((64,), 0.0, 0.0)
  assert spec.dense_leaves["layer_1_index_norm_gain"] == ((64,), 0.0, 1.0)
  assert spec.dense_leaves["layer_2_w_down"][0] == (16, 768, 2048)
  assert spec.dense_leaves["layer_2_router"][0] == (2048, 128)
  assert spec.n_numerical == c["seq_len"] and spec.summed_tables == {0}
  assert (spec.inputs[0].hotness, spec.inputs[0].sequence,
          spec.inputs[0].rows) == (c["seq_len"], True, 18992)
  mix = cell.traffic
  assert (mix["global_batch"], mix["alpha"], mix["pool_batches"],
          mix["steps_in_flight"], mix["numerical_range"]) == (
              1, 1.05, 16, 3, [0, 1])
  # ISSUE 40's second form: a step at 16,384 tokens passed 1,500 ms
  assert (c["seq_len"], c["mean_document_length"]) == (8192, 4096)


def test_a_program_without_the_model_says_so_at_once(root, monkeypatch):
  """What the parent of this PR does with these files laid over it."""
  real = importlib.util.find_spec
  monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None
                      if name.endswith("models.keye_sparse")
                      else real(name, *a))
  cell = specs.load_cell(CELL, root)
  with pytest.raises(specs.SpecError, match="no .*models/keye_sparse.py"):
    cell.family().model_spec(cell.config)


def test_the_familys_batch_and_its_documents_counts(root):
  cell, family, _, pool = _setup(root, 2**33 + 1)
  b = pool[0]
  assert b.cats.shape == (4, 48) and b.numerical.shape == (4, 48)
  assert np.array_equal(b.labels["targets"][:, :-1], b.cats[:, 1:])
  assert not b.labels["targets"][:, -1].any()
  assert 0 <= b.numerical.min() and b.numerical.max() < 1
  assert b.cats.max() < 96
  starts = np.concatenate([b.numerical for b in pool]) < 1 / 16
  assert 0.02 < starts[:, 1:].mean() < 0.15   # documents do start mid-way
  # pair by pair
  got = family.document_counts(cell.config, b.numerical)
  begins = b.numerical < 1 / 16
  begins[:, 0] = True
  doc = np.cumsum(begins, axis=1)
  i, j = np.arange(48)[:, None], np.arange(48)[None, :]
  seen = ((j <= i)[None] & (doc[:, :, None] == doc[:, None, :])).sum(-1)
  assert got == {"visible_pairs": int(seen.sum()),
                 "selected_pairs": int(np.minimum(seen, 6).sum()),
                 "active_queries": int((seen > 6).sum())}
  assert 0 < got["active_queries"] < 4 * 48


# ---- broken timed paths, each caught by a named comparison -----------------
COMPILE_STEP = program.Program.compile_step   # before any test breaks it


def _rebuilt(change):
  """A breaker that swaps the compiled step for that of a changed program
  (``change(parts) -> Parts``; the state keeps its layout), compiled when
  first called."""
  def breaker(prog, step):
    other = program.Program(change(prog.parts), prog.spec, prog.seed,
                            prog.mesh)
    box = {}

    def call(state, numerical, cats, labels):
      if "step" not in box:
        box["step"] = COMPILE_STEP(other, state, traffic.Batch(
            np.asarray(numerical), np.asarray(cats),
            jax.tree_util.tree_map(np.asarray, labels)))
      return box["step"](state, numerical, cats, labels)
    return call
  return breaker


def _with_config(parts, **changes):
  model = parts.model
  return dataclasses.replace(parts, model=type(model)(
      dataclasses.replace(model.config, **changes)))


def _selection_dropped(parts):
  """Dense causal attention inside a document: every visible key kept."""
  return _with_config(parts, topk=10 ** 6)


def _kl_dropped(parts):
  """The language-model loss alone: the indexers' leaves never move."""
  from distributed_embeddings_tpu.models.olmo_hybrid import next_token_loss
  return dataclasses.replace(parts, loss_fn=next_token_loss)


def _input_attached(parts):
  """The indexer reads its layer's input with the gradient left on."""
  class Attached:
    config = parts.model.config

    def apply(self, *args, **kwargs):
      real = jax.lax.stop_gradient
      jax.lax.stop_gradient = lambda x: x
      try:
        return parts.model.apply(*args, **kwargs)
      finally:
        jax.lax.stop_gradient = real
  return dataclasses.replace(parts, model=Attached())


@pytest.mark.parametrize("broken,fails", [
    (None, []),
    ("selection_dropped", ["loss_gap"]),
    ("kl_dropped", ["loss_gap", "dense_change_gap"]),
    ("input_attached", ["dense_change_gap"]),
    ("control", ["loss_gap", "dense_change_gap"]),
])
def test_a_run_of_the_family(root, capsys, monkeypatch, broken, fails):
  cell = specs.load_cell(CELL, root)
  devices, dev = bench_toy.cpu_devices(1)
  changes = {"selection_dropped": _selection_dropped,
             "kl_dropped": _kl_dropped, "input_attached": _input_attached}
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
FAULTS = ("no_selection", "topk_half", "no_kl", "input_attached")


@pytest.fixture(scope="module")
def control_lines(root):
  """`control_sequential.control` on the toy cell, one seed, the control and
  the family's four faults: -> (seeds the control was inside on, stand-in
  -> its line of JSON)."""
  said = io.StringIO()
  with contextlib.redirect_stdout(said):
    inside = control_sequential.control(
        specs.load_cell(CELL, root), [2**31 + 77], ["bfloat16", *FAULTS])
  lines = [json.loads(ln) for ln in said.getvalue().splitlines()
           if ln.startswith("{")]
  return inside, {ln["stand_in"]: ln for ln in lines}


@pytest.mark.parametrize("stand_in,outside", [
    ("bfloat16", ["loss_gap", "table_change_gap", "dense_change_gap"]),
    ("no_selection", ["loss_gap"]),
    ("topk_half", ["loss_gap"]),
    ("no_kl", ["loss_gap", "dense_change_gap"]),
    ("input_attached", ["dense_change_gap"]),
])
def test_the_sequential_control_judges_a_stand_in_as_the_check_does(
    control_lines, stand_in, outside):
  """Reference against reference, by the check's own `Compared` under the
  toy configuration's limits: each says ``"correct": false``."""
  inside, lines = control_lines
  assert inside == 0 and set(lines) == {"bfloat16", *FAULTS}
  line = lines[stand_in]
  assert line["correct"] is False and line["seed"] == 2**31 + 77
  assert set(outside) <= set(line["outside"])
  assert line["outside"] == [k for k in LIMITS if line[k] > LIMITS[k]]
  if stand_in == "no_kl":
    # an indexer leaf that never moved reads exactly its own size
    assert line["dense_change_gap"] >= 1.0


# ---- the new metrics' readers, on a hand-built trace ------------------------
STACK = "jit(step_fn)/jit(local_step)/"
FWD = STACK + "jvp(de_model)/KeyeSparse/checkpoint/"
REBUILT = STACK + "transpose(jvp(de_model))/KeyeSparse/checkpoint/" \
    "rematted_computation/"
BWD = STACK + "transpose(jvp(de_model))/KeyeSparse/checkpoint/"
TILE = "de_attention/vmap(while)/body/"
OPS = {  # op -> (name stack, start ns, duration ns)
    "fusion.1": (FWD + "de_attention/de_attn_proj/dot_general", 0, 100),
    "fusion.2": (FWD + "de_attention/de_sparse_index/de_index_scores/"
                 "dot_general", 100, 50),
    "fusion.3": (FWD + TILE + "de_sparse_index/de_index_scores/dot_general",
                 150, 250),
    "fusion.4": (FWD + TILE + "de_sparse_index/de_index_select/while", 400,
                 120),
    "fusion.5": (FWD + TILE + "de_attn_core/dot_general", 520, 400),
    "fusion.6": (FWD + TILE + "de_sparse_index/de_index_loss/reduce_sum",
                 920, 80),
    "fusion.7": (REBUILT + "de_attention/de_sparse_index/de_index_scores/"
                 "dot_general", 1000, 50),
    "fusion.8": (BWD + TILE + "de_attn_core/dot_general", 1050, 900),
    "fusion.9": (BWD + TILE + "de_sparse_index/de_index_scores/dot_general",
                 1950, 350),
    "fusion.10": (BWD + TILE + "de_sparse_index/de_index_loss/exp", 2300,
                  100),
    "fusion.11": (FWD + "de_moe/de_moe_experts/mul", 2400, 50),
    "ragged-dot-none.12": ("", 2450, 450),    # XLA's kernel: no name stack
    "fusion.13": (FWD + "de_moe/de_moe_route/de_moe_sort/sort", 2900, 100),
    "fusion.14": (STACK + "jvp(de_model)/KeyeSparse/de_lm_head/dot_general",
                  3000, 60),
    "fusion.15": (STACK + "de_loss/reduce_sum", 3060, 40),
    "fusion.16": (STACK + "de_dense_update/add", 3100, 150),
}


def _hand_built(ops_table=None):
  ops_table = ops_table or OPS
  names = scope_reduce.OpNames(
      {op: s for op, (s, _, _) in ops_table.items()}, {})
  ops = [(op, start, dur, 0) for op, (_, start, dur) in ops_table.items()]

  class Red:
    steps = [[("jit_step_fn(7)", 0, 3300)]]
  red = Red()
  red.ops = [ops]
  return red, names


def test_the_new_readers_on_a_hand_built_trace():
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_parts": scope_parts.attribute(red, names)}
  read = lambda m: cell.layer_reader(m)(red, ctx)
  assert read("index_scores_ms") == pytest.approx(700e-6)
  assert read("index_select_ms") == pytest.approx(120e-6)
  assert read("index_loss_ms") == pytest.approx(180e-6)
  # the three parts partition the indexer
  assert read("sparse_index_ms") == pytest.approx(1000e-6)
  assert read("attn_core_ms") == read("attn_layout_ms") \
      == pytest.approx(1300e-6)
  share = lambda flops, ns: 100 * flops / 197e12 / (ns * 1e-9)
  assert read("sparse_attn_mxu_pct") == pytest.approx(share(
      roofline_keye.sparse_attention_flops(cell.config, cell.traffic), 1300))
  assert read("index_scores_mxu_pct") == pytest.approx(share(
      roofline_keye.index_scores_flops(cell.config, cell.traffic), 700))
  # the accepted part readers this cell joins read a scope and no model
  assert read("attn_proj_ms") == pytest.approx(100e-6)
  assert read("moe_sort_ms") == pytest.approx(100e-6)
  assert read("remat_forward_ms") == pytest.approx(50e-6)
  # a program without the scopes (the parent, on any cell): the ms read 0.0
  # as a scope of scope_reduce does, the shares have nothing to divide by
  strip = lambda s: "/".join(
      part for part in s.split("/")
      if part not in ("de_sparse_index", "de_index_scores",
                      "de_index_select", "de_index_loss", "de_attn_core"))
  red, bare = _hand_built({op: (strip(s), a, d)
                           for op, (s, a, d) in OPS.items()})
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_parts": scope_parts.attribute(red, bare)}
  for name in MS:
    assert cell.layer_reader(name)(red, ctx) == 0.0, name
  for name in ("sparse_attn_mxu_pct", "index_scores_mxu_pct"):
    assert cell.layer_reader(name)(red, ctx) is None, name


@pytest.mark.parametrize("length,mean_doc,topk", [
    (48, 16, 6), (48, 16, 100), (40, 3, 7), (64, 1000, 4)])
def test_the_expected_pairs_are_counted_by_drawing_documents(length, mean_doc,
                                                             topk):
  got = roofline_keye.expected_pairs(length, mean_doc, topk)
  rng = np.random.default_rng(0)
  starts = rng.random((6000, length)) < 1.0 / mean_doc
  starts[:, 0] = True
  at = np.arange(length)
  first = np.maximum.accumulate(np.where(starts, at[None], 0), axis=1)
  seen = at[None] - first + 1
  assert got["visible"] == pytest.approx(seen.sum() / 6000, rel=0.03)
  assert got["selected"] == pytest.approx(
      np.minimum(seen, topk).sum() / 6000, rel=0.03)
  assert got["active"] == pytest.approx(
      (seen > topk).sum() / 6000, rel=0.06, abs=0.02)
  assert got["selected"] <= got["visible"]


def test_the_least_work_of_the_cells_step():
  cell = specs.load_cell(CELL)
  c, mix = cell.config, cell.traffic
  pairs = roofline_keye.expected_pairs(8192, 4096, 2048)
  # ISSUE 40's counts for this mix: 11.7 M of 19.1 M pairs kept, 46% of the
  # queries live
  assert pairs["visible"] == pytest.approx(19.05e6, rel=2e-3)
  assert pairs["selected"] == pytest.approx(11.69e6, rel=2e-3)
  assert pairs["active"] / 8192 == pytest.approx(0.455, abs=2e-3)
  assert roofline_keye.sparse_attention_flops(c, mix) \
      == pytest.approx(12 * 128 * 32 * pairs["selected"] * 4)
  assert roofline_keye.index_scores_flops(c, mix) \
      == pytest.approx(6 * 64 * 16 * pairs["visible"] * 4)
  assert roofline_keye.index_scores_flops(c, dict(mix, global_batch=2)) \
      == 2 * roofline_keye.index_scores_flops(c, mix)
  # and the 16,384-token mix ISSUE 40 named first: 27.9 M of 76 M, 68%
  longer = roofline_keye.expected_pairs(16384, 8192, 2048)
  assert longer["selected"] == pytest.approx(27.9e6, rel=2e-3)
  assert longer["active"] / 16384 == pytest.approx(0.68, abs=5e-3)


def test_the_indexers_counters_on_the_toy(root, capsys):
  """`tools/sparse_index_load.py`: the program's counters, the documents'
  own counts and the plain reference's mask agree, layer by layer."""
  spec = importlib.util.spec_from_file_location(
      "sparse_index_load", os.path.join(bench_toy.ROOT, "tools",
                                        "sparse_index_load.py"))
  tool = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(tool)
  report = tool.main([CELL, "--seed", "3", "--root", root, "--reference"])
  assert report["counts_agree"] and report["topk"] == 6
  assert report["positions_a_layer"] == 4 * 48
  want = report["from_the_documents"]
  assert report["selected_pairs"] == report["reference_selected_pairs"] \
      == [want["selected_pairs"]] * 2
  assert report["visible_pairs"] == [want["visible_pairs"]] * 2
  assert 0 < want["selected_pairs"] < want["visible_pairs"]
  assert json.loads(capsys.readouterr().out.splitlines()[-1]) == report
