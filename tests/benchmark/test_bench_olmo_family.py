"""The olmo_hybrid family (`benchmark/families/olmo_hybrid.py`,
`configs/olmo-hybrid-7b-tp2share.json`, `workloads/olmo_packed_8k.json`) at
toy widths through ``run.run_cell`` on the CPU: the sound program is correct;
documents not packed, the loss's weight dropped, ``summed`` switched off and
the bfloat16 control each come out wrong by a comparison of their own. The
family was added as files: every file the benchmark had keeps its bytes.
Every committed mix has a cell; the new metrics' readers read a hand-built
trace; the rule's least work is counted product by product."""

import contextlib
import dataclasses
import functools
import hashlib
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
    in_blocks,
    program,
    reference,
    roofline_hybrid,
    run,
    scope_children,
    scope_children_hybrid,
    scope_reduce,
    specs,
    traffic,
    weights,
)

CELL = "olmo_hybrid_train_1chip"
CONFIG = "benchmark/configs/olmo-hybrid-7b-tp2share.json"
MIX = "benchmark/workloads/olmo_packed_8k.json"
METRICS = ("linattn_ms", "delta_rule_ms", "mlp_ms", "delta_rule_mxu_pct")
NEW = ("benchmark/families/olmo_hybrid.py", CONFIG, MIX,
       "benchmark/roofline_hybrid.py", "benchmark/scope_children_hybrid.py",
       "benchmark/control_sequential.py", "benchmark/in_blocks.py",
       "tests/benchmark/test_bench_olmo_family.py") + tuple(
           f"benchmark/layer_metrics/{m}.{ext}" for ext in ("json", "py")
           for m in METRICS)
PARENT = "8ea9456c6d0499a03ad9050f671e71abf223acce"   # PR 32
# the general metrics and the two scope readers that read no model's sizes
APPENDED_TO = ("host_feed_ms", "step_device_ms", "device_idle_pct",
               "route_ms", "gather_ms", "combine_ms", "onehot_ms",
               "dense_model_ms", "dense_update_ms", "sparse_apply_ms",
               "unscoped_pct", "attn_ms", "lm_head_ms")


def _shrink(c):
  c.update(hidden_size=32, intermediate_size=48, num_attention_heads=4,
           heads_held=[2, 2], head_dim=8, linear_key_head_dim=6,
           linear_value_head_dim=10, vocab_here=96, seq_len=24,
           mean_document_length=6, chunk=8, init_scale=0.3,
           attention="xla")   # the CPU names its own path
  c["optimizer"]["learning_rate"] = 1e-3
  # CPU, 3 seeds: the sound program reads loss_gap 0 to the last digit
  # printed, table_change_gap <= 0.0042 and dense_change_gap <= 0.00054; the
  # bfloat16 control 3.0e-4 .. 2.0e-3, 0.70 .. 0.98 and 1.26 .. 1.37
  c["check_limits"] = {"loss_gap": 2e-5, "table_change_gap": 0.03,
                       "dense_change_gap": 0.03}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("olmo_root")))
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
  logits = functools.partial(family.reference_logits, cell.config)
  return cell, family, spec, pool, logits


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
    assert new[key][len(old[key])]["name"] in (CELL,
                                               "olmo-hybrid-7b-tp2share")
  for was, now in zip(old["per_layer"], new["per_layer"]):
    assert {k: v for k, v in now.items() if k != "workloads"} \
        == {k: v for k, v in was.items() if k != "workloads"}
    n = len(was["workloads"])
    assert now["workloads"][:n] == was["workloads"]
    assert (CELL in now["workloads"][n:]) == (was["name"] in APPENDED_TO)
  added = new["per_layer"][len(old["per_layer"]):]
  assert [m["name"] for m in added[:4]] == list(METRICS)
  for m in added[:4]:
    assert m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s"
  cell = {w["name"]: w for w in new["workloads"]}[CELL]
  assert (cell["config"], cell["traffic"], cell["chips"]) == (
      "olmo-hybrid-7b-tp2share", "olmo_packed_8k", 1)


def test_every_committed_mix_has_a_cell_and_every_cell_its_mix():
  """(What else ``test_the_committed_benchmark_is_consistent`` checks, other
  than its list of families, runs over every cell, this one too, in
  ``test_bench_sdar_family.py``.)"""
  with open(os.path.join(bench_toy.ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  mixes = {w["traffic"] for w in bench["workloads"]}
  on_disk = {f[:-5] for f in os.listdir(os.path.join(
      bench_toy.ROOT, "benchmark", "workloads")) if f.endswith(".json")}
  assert mixes == on_disk


PERIOD = ["linear_attention"] * 3 + ["full_attention"]


def test_the_configuration_states_the_published_widths_and_its_cuts():
  cell = specs.load_cell(CELL)
  c = cell.config
  published = dict(
      model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
      intermediate_size=11008, num_hidden_layers=32, num_attention_heads=30,
      num_key_value_heads=30, hidden_act="silu",
      max_position_embeddings=65536, attention_bias=False, rms_norm_eps=1e-6,
      tie_word_embeddings=False, layer_types=PERIOD * 8,
      linear_num_key_heads=30, linear_num_value_heads=30,
      linear_key_head_dim=96, linear_value_head_dim=192,
      linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
      rope_parameters={"rope_theta": None})
  assert {k: c[k] for k in published} == published
  assert (c["num_hidden_layers_here"], c["heads_held"], c["vocab_here"],
          c["head_dim"]) == (4, [0, 15], 100352 // 8, 3840 // 30)
  assert c["attention"] == "splash"
  with pytest.raises(ValueError, match="is a TPU kernel"):
    parts = cell.family().build_parts(c, 1, 1)
    parts.model.apply(
        {"params": jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), parts.dense_template)},
        jnp.zeros((1, 8192)), None, emb_acts=[jnp.zeros((1, 8192, 3840))])
  assert c["reduced"] == [
      "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
      "linear_num_key_heads", "linear_num_value_heads", "vocab_size"]
  assert set(c["reduced_why"]) == set(c["reduced"])
  for words in ("eight chips", "divided over two", "row-sliced over all "
                "eight", "pipeline stages", "without its exchange"):
    assert words in c["deployment"], words
  for key in ("head_dim", "norm placement", "q/k norm", "rotary embedding",
              "linear-attention mixer", "initialisers",
              "documents as numerical features", "objective", "optimizer",
              "seq_len", "chunk", "attention path"):
    assert key in c["assumed"]
  spec = cell.family().model_spec(c)
  n = sum(int(np.prod(v[0])) for v in spec.dense_leaves.values())
  # per layer the MLP 126.81 M; a linear mixer 44.3 M, the full one 29.5 M
  # at 15 heads; the head 48.2 M: ISSUE 33's table, less the token table
  mlp = 3 * 3840 * 11008 + 2 * 3840
  linear = 3840 * 15 * (2 * 96 + 2 * 192 + 2) + 15 * 192 * 3840 \
      + 4 * 15 * (2 * 96 + 192) + 2 * 15 + 192
  full = 4 * 3840 * 15 * 128 + 2 * 15 * 128
  assert n == 4 * mlp + 3 * linear + full + 3840 + 3840 * 12544 == 718072986
  assert len(spec.dense_leaves) == 2 + 3 * 18 + 11
  assert spec.n_numerical == 8192 and spec.summed_tables == {0}
  assert (spec.inputs[0].hotness, spec.inputs[0].sequence,
          spec.inputs[0].rows) == (8192, True, 12544)
  assert spec.dense_leaves["layer_0_a_log"] == ((15,), 1.0, 1.0)
  assert spec.dense_leaves["layer_2_dt_bias"] == ((15,), 2.3, -4.6)
  assert "layer_3_a_log" not in spec.dense_leaves
  mix = cell.traffic
  assert (mix["global_batch"], mix["alpha"], mix["pool_batches"],
          mix["steps_in_flight"], mix["numerical_range"]) == (
              1, 1.05, 16, 3, [0, 1])


def test_a_program_without_the_model_says_so_at_once(root, monkeypatch):
  """What the parent of this PR does with these files laid over it."""
  import importlib.util
  real = importlib.util.find_spec
  monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None
                      if name.endswith("models.olmo_hybrid")
                      else real(name, *a))
  cell = specs.load_cell(CELL, root)
  with pytest.raises(specs.SpecError, match="no .*models/olmo_hybrid.py"):
    cell.family().model_spec(cell.config)


def test_the_familys_batch(root):
  cell, _, spec, pool, _ = _setup(root, 2**33 + 1)
  b = pool[0]
  assert b.cats.shape == (4, 24) and b.numerical.shape == (4, 24)
  assert np.array_equal(b.labels["targets"][:, :-1], b.cats[:, 1:])
  assert not b.labels["targets"][:, -1].any()
  assert 0 <= b.numerical.min() and b.numerical.max() < 1
  assert b.cats.max() < 96
  starts = np.concatenate([b.numerical for b in pool]) < 1 / 6
  assert 0.05 < starts[:, 1:].mean() < 0.3    # documents do start mid-way


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


def _weight_dropped(parts):
  """The loss counts every position: a document's last token is asked for
  the next document's first."""
  def loss_fn(outputs, labels):
    return parts.loss_fn(dict(outputs, weight=jnp.ones_like(
        outputs["weight"])), labels)
  return dataclasses.replace(parts, loss_fn=loss_fn)


def _not_packed(parts):
  """One document a sequence: no reset of the rule or of the convolution's
  window, attention across documents (the loss keeps its weight)."""
  model = parts.model
  whole = type(model)(dataclasses.replace(model.config,
                                          mean_document_length=10 ** 9))

  class Unpacked:
    def apply(self, variables, numerical, cats, emb_acts=None):
      out = whole.apply(variables, numerical, cats, emb_acts=emb_acts)
      return dict(out, weight=model.apply(
          variables, numerical, cats, emb_acts=emb_acts)["weight"])
  return dataclasses.replace(parts, model=Unpacked())


def _summed_off(parts):
  return dataclasses.replace(
      parts, rule=dataclasses.replace(parts.rule, summed=False))


@pytest.mark.parametrize("broken,fails", [
    (None, []),
    ("weight", ["loss_gap"]),
    ("not_packed", ["loss_gap"]),
    ("summed_off", ["table_change_gap"]),
    ("control", ["loss_gap", "dense_change_gap"]),
])
def test_a_run_of_the_family(root, capsys, monkeypatch, broken, fails):
  cell = specs.load_cell(CELL, root)
  devices, dev = bench_toy.cpu_devices(1)
  changes = {"weight": _weight_dropped, "not_packed": _not_packed,
             "summed_off": _summed_off}
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
    assert verdict[name] == "OUTSIDE"
  assert verdict["fill"] == verdict["untouched"] == "ok"
  assert result.attempted > 1 and result.failed == 0
  # above the result line: the batch's documents and the rule's chunk count
  said = [ln for ln in out.splitlines() if ln.startswith("reference batch:")]
  assert len(said) == 1
  assert "4 sequence(s) of 24 tokens" in said[0]
  assert "3 chunks of 8 tokens a layer in 3 of 4 layers" in said[0]


# ---- the control, one reference after the other ------------------------------
@pytest.fixture(scope="module")
def control_lines(root):
  """`control_sequential.control` on the toy cell, one seed, both faults:
  -> (seeds the control was inside on, stand-in -> its line of JSON)."""
  hashes = reference.dense_weights
  said = io.StringIO()
  with contextlib.redirect_stdout(said):
    inside = control_sequential.control(
        specs.load_cell(CELL, root), [2**31 + 77],
        ["bfloat16", "weight", "not_packed"])
  assert reference.dense_weights is hashes   # the memo of a seed's weights
  lines = [json.loads(ln) for ln in said.getvalue().splitlines()
           if ln.startswith("{")]
  return inside, {ln["stand_in"]: ln for ln in lines}


@pytest.mark.parametrize("stand_in,outside", [
    ("bfloat16", ["loss_gap", "table_change_gap", "dense_change_gap"]),
    ("weight", ["loss_gap"]),
    ("not_packed", ["loss_gap"]),
])
def test_the_sequential_control_judges_a_stand_in_as_the_check_does(
    control_lines, stand_in, outside):
  """Reference against reference, by the check's own `Compared` under the
  toy configuration's limits: each says ``"correct": false``."""
  inside, lines = control_lines
  assert inside == 0 and set(lines) == {"bfloat16", "weight", "not_packed"}
  line = lines[stand_in]
  assert line["correct"] is False and line["seed"] == 2**31 + 77
  assert set(outside) <= set(line["outside"])
  limits = {"loss_gap": 2e-5, "table_change_gap": 0.03,
            "dense_change_gap": 0.03}
  assert line["outside"] == [k for k in limits if line[k] > limits[k]]


# ---- the harness's host arithmetic, a block at a time -----------------------
@pytest.fixture
def blocked(monkeypatch):
  """The wrappers at a block of 64 values; -> the harness's three functions
  as they were."""
  monkeypatch.setattr(in_blocks, "BLOCK", 64)
  in_blocks.install()
  in_blocks.install()   # a second call changes nothing
  return tuple(fn.whole for fn in (weights.rows_np, reference.update,
                                   reference.stored_change))


def test_a_leaf_hashed_in_blocks_has_the_bits_of_the_whole(blocked):
  rows_np = blocked[0]
  assert weights.rows_np is not rows_np
  for shape in [(37, 24), (5, 7), (1, 300), (130, 1)]:
    got = weights.dense_np(77, 0.3, shape, 1.5)
    want = rows_np(77, 0.3, np.arange(shape[0]), shape[1]) + np.float32(1.5)
    assert got.dtype == want.dtype and np.array_equal(got, want), shape
  ids = np.array([90, 3, 3, 41] * 9)
  assert np.array_equal(weights.rows_np(5, 0.1, ids, 48),
                        rows_np(5, 0.1, ids, 48))


@pytest.mark.parametrize("name", ["sgd", "adagrad", "adam"])
def test_an_update_in_blocks_has_the_bits_of_the_whole(blocked, name):
  _, update, stored_change = blocked
  opt = {"name": name, "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
         "eps": 1e-8, "initial_accumulator_value": 0.1}
  rng = np.random.default_rng(3)
  for shape in [(9, 33), (500,), (3, 4)]:   # the last: under a block, whole
    g = rng.standard_normal(shape) * 1e-3
    before = rng.standard_normal(shape).astype(np.float32)
    (got, got_acc), (want, want_acc) = reference.update(opt, g), update(opt, g)
    assert got.shape == shape and np.array_equal(got, want)
    assert isinstance(got_acc, tuple) and len(got_acc) == len(want_acc)
    assert all(np.array_equal(a, b) for a, b in zip(got_acc, want_acc))
    assert np.array_equal(reference.stored_change(before, got),
                          stored_change(before, want))
    # an accumulator's initial value is one number for the whole leaf
    assert np.array_equal(reference.stored_change(0.1, got),
                          stored_change(0.1, want))


# ---- the new metrics' readers, on a hand-built trace ------------------------
STACK = "jit(step_fn)/jit(local_step)/"
RULE = "jvp(de_model)/OlmoHybrid/checkpoint/de_linear_attention/de_delta_rule/"
OPS = {  # op -> (name stack, start ns, duration ns)
    "fusion.1": (STACK + "jvp(de_model)/OlmoHybrid/checkpoint/"
                 "de_linear_attention/dot_general", 0, 100),
    "while.2": (STACK + RULE + "while", 100, 500),
    "fusion.3": (STACK + RULE + "while/body/dot_general", 100, 300),
    "fusion.4": ("", 400, 100),       # nameless, inside the while: its holder's
    "fusion.5": (STACK + RULE + "triangular_solve", 600, 200),
    "fusion.6": (STACK + "transpose(jvp(de_model))/OlmoHybrid/checkpoint/"
                 "de_mlp/dot_general", 800, 700),
    "splash_mha_fwd.7": (
        STACK + "jvp(de_model)/OlmoHybrid/checkpoint/de_attention/vmap("
        "jit(_splash_attention))/splash_mha_fwd/pallas_call", 1500, 250),
    "fusion.8": (STACK + "jvp(de_model)/OlmoHybrid/de_lm_head/dot_general",
                 1750, 60),
    "fusion.9": (STACK + "de_loss/reduce_sum", 1810, 40),
    "fusion.10": (STACK + "de_dense_update/add", 1850, 150),
}


def _hand_built(ops_table=None):
  ops_table = ops_table or OPS
  names = scope_reduce.OpNames(
      {op: s for op, (s, _, _) in ops_table.items()}, {})
  ops = [(op, start, dur, 0) for op, (_, start, dur) in ops_table.items()]

  class Red:
    steps = [[("jit_step_fn(7)", 0, 2000)]]
  red = Red()
  red.ops = [ops]
  return red, names


def test_the_new_readers_on_a_hand_built_trace():
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_children": scope_children.per_step_ns(red, names),
         "scope_children_hybrid": scope_children_hybrid.per_step_ns(
             red, names)}
  read = lambda m: cell.layer_reader(m)(red, ctx)
  assert read("linattn_ms") == pytest.approx(800e-6)   # the rule lies inside
  assert read("delta_rule_ms") == pytest.approx(700e-6)
  assert read("mlp_ms") == pytest.approx(700e-6)
  # the two PR 29 readers this cell joins read a scope and no model's sizes
  assert read("attn_ms") == pytest.approx(250e-6)
  assert read("lm_head_ms") == pytest.approx(100e-6)
  assert read("delta_rule_mxu_pct") == pytest.approx(
      100 * roofline_hybrid.delta_rule_flops(cell.config, cell.traffic)
      / 197e12 / 700e-9)
  # a program without the scopes (the parent): nothing to read, no raise
  red, bare = _hand_built({
      f"fusion.{i}": (STACK + "de_model/dot_general", start, dur)
      for i, (_, start, dur) in enumerate(OPS.values())})
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_children_hybrid": scope_children_hybrid.per_step_ns(
             red, bare)}
  for name in METRICS:
    assert cell.layer_reader(name)(red, ctx) is None, name


@pytest.mark.parametrize("chunk,dk,dv", [(4, 2, 3), (8, 6, 10), (64, 96, 192)])
def test_the_rules_least_work_is_counted_product_by_product(chunk, dk, dv):
  flops = 0
  for i in range(chunk):
    for j in range(chunk):
      if j < i:
        flops += 2 * dk             # K K^T below the diagonal
        flops += 2 * (dv + dk)      # forward substitution
      if j <= i:
        flops += 2 * dk             # Q K^T
        flops += 2 * (dv + dk)      # P U, P Wk
  flops += 2 * chunk * dk * dk      # Kd^T Wk
  flops += 2 * 2 * chunk * dk * dv  # Kd^T U, (e^gamma Q - P Wk) S
  flops += 2 * dk * dk * dv         # M S
  assert roofline_hybrid.delta_rule_chunk_flops(chunk, dk, dv) == flops


def test_the_least_work_of_the_cells_step():
  cell = specs.load_cell(CELL)
  per_chunk = roofline_hybrid.delta_rule_chunk_flops(64, 96, 192)
  assert roofline_hybrid.delta_rule_flops(cell.config, cell.traffic) \
      == 3 * 128 * 15 * 3 * 1 * per_chunk
  # 0.2 TFLOP a step: a thousandth of a second of the MXU
  assert 1.5e11 < 3 * 128 * 15 * 3 * per_chunk < 3e11
  ragged = dict(cell.config, seq_len=8200)
  assert roofline_hybrid.delta_rule_flops(ragged, cell.traffic) \
      == 3 * 129 * 15 * 3 * per_chunk
