"""The sdar_moe family (`benchmark/families/sdar_moe.py`,
`configs/sdar-30b-a3b-ep8share.json`, `workloads/sdar_blockdiff_4k.json`)
at toy widths through ``run.run_cell`` on the CPU: the sound program is
correct; the loss's weight dropped, the absent experts' share added, the
table's moments unwritten, ``summed`` switched off and the bfloat16 control
each come out wrong by a comparison of their own. The family was added as
files: every file the benchmark had keeps its bytes. The checks of
``test_the_committed_benchmark_is_consistent`` other than its list of
families hold for every cell; the new metrics' readers read a hand-built
trace; the attention counts are those of the mask, pair by pair."""

import dataclasses
import functools
import hashlib
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import (
    program,
    reference,
    roofline_lm,
    run,
    scope_children,
    scope_reduce,
    specs,
    traffic,
)

CELL = "sdar_moe_train_1chip"
CONFIG = "benchmark/configs/sdar-30b-a3b-ep8share.json"
MIX = "benchmark/workloads/sdar_blockdiff_4k.json"
NEW = ("benchmark/families/sdar_moe.py", CONFIG, MIX,
       "benchmark/roofline_lm.py", "benchmark/scope_children.py") + tuple(
           f"benchmark/layer_metrics/{m}.{ext}" for ext in ("json", "py")
           for m in ("attn_ms", "moe_ms", "moe_route_ms", "moe_experts_ms",
                     "lm_head_ms", "attn_mxu_pct", "moe_experts_mxu_pct",
                     "splash_attention_roofline"))


def _shrink(c):
  c.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           head_dim=8, moe_intermediate_size=16, num_experts=8,
           num_experts_per_tok=2, num_hidden_layers_here=2,
           experts_held=[2, 4], vocab_here=96, seq_len=16, block_length=4,
           init_scale=0.3, attention="xla")   # the CPU names its own path
  c["optimizer"]["learning_rate"] = 1e-3
  c["check_limits"] = {"loss_gap": 1e-5, "table_change_gap": 0.02,
                       "dense_change_gap": 0.02}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("sdar_root")))
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
  """Every file the parent had under ``benchmark/`` has the parent's bytes
  (``git`` is the witness where the checkout has one), and the new files
  are exactly the family's."""
  base = "022b9bcd2039a481d2f2191274b9cbd7e5a8da83"
  listed = subprocess.run(
      ["git", "ls-tree", "-r", base, "benchmark", "tests/benchmark"],
      cwd=bench_toy.ROOT, capture_output=True, text=True)
  if listed.returncode != 0 or not listed.stdout.strip():
    pytest.skip("no git history here to compare with")
  for line in listed.stdout.splitlines():
    meta, path = line.split("\t")
    with open(os.path.join(bench_toy.ROOT, path), "rb") as f:
      data = f.read()
    blob = hashlib.sha1(b"blob %d\0" % len(data) + data).hexdigest()
    assert blob == meta.split()[2], f"{path} was edited"
  assert "\t" + NEW[0] + "\n" not in listed.stdout
  for path in NEW:
    assert os.path.exists(os.path.join(bench_toy.ROOT, path)), path


def test_the_benchmark_grew_by_entries_alone():
  base = "022b9bcd2039a481d2f2191274b9cbd7e5a8da83"
  shown = subprocess.run(["git", "show", f"{base}:BENCHMARK.json"],
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
  for was, now in zip(old["per_layer"], new["per_layer"]):
    grown = dict(now, workloads=[w for w in now["workloads"] if w != CELL])
    assert grown == was and now["workloads"][:len(was["workloads"])] \
        == was["workloads"]
  assert [m["workloads"] for m in new["per_layer"][len(old["per_layer"]):]
          ] == [[CELL]] * 8


def test_every_cell_of_the_committed_benchmark_is_consistent():
  """``test_the_committed_benchmark_is_consistent`` without its list of
  families (which a PR that may edit no file there cannot extend)."""
  with open(os.path.join(bench_toy.ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  e2e = {m["name"] for m in bench["end_to_end"]}
  assert "setup_s" in e2e
  configs = {c["name"]: c for c in bench["configs"]}
  for w in bench["workloads"]:
    cell = specs.load_cell(w["name"])
    assert os.path.exists(os.path.join(
        bench_toy.ROOT, "benchmark", "families",
        cell.config["family"] + ".py"))
    assert cell.config["reduced"] == configs[w["config"]]["reduced"]
    spec = cell.family().model_spec(cell.config)
    assert len(spec.tables) >= 1 and spec.inputs
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
      assert m["moves"] in e2e
      with open(os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                             m["name"] + ".json")) as f:
        spec_m = json.load(f)
      assert spec_m["layer"] == m["layer"] and spec_m["unit"] == m["unit"]
      assert callable(cell.layer_reader(m["name"]))


def test_the_configuration_states_the_published_widths_and_its_cuts():
  cell = specs.load_cell(CELL)
  c = cell.config
  published = dict(
      hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
      head_dim=128, moe_intermediate_size=768, num_experts=128,
      num_experts_per_tok=8, num_hidden_layers=48, vocab_size=151936,
      norm_topk_prob=True, rope_theta=1000000, rms_norm_eps=1e-6,
      tie_word_embeddings=False, intermediate_size=6144,
      max_position_embeddings=32768, decoder_sparse_step=1,
      mlp_only_layers=[], model_type="sdar_moe", attention_bias=False)
  assert {k: c[k] for k in published} == published
  assert (c["num_hidden_layers_here"], c["experts_held"], c["vocab_here"]
          ) == (4, [0, 16], 151936 // 8)
  # the timed path is named, and it is the TPU's kernel: no backend computes
  # another in its place
  assert c["attention"] == "splash"
  with pytest.raises(ValueError, match="is a TPU kernel"):
    cell.family().build_parts(c, 1, 1)
  assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
  assert set(c["reduced_why"]) == set(c["reduced"])
  assert "eight chips share each layer" in c["deployment"]
  for key in ("block_length", "noise schedule", "auxiliary loss", "q/k norm",
              "mask token", "optimizer", "initialisers", "seq_len",
              "attention path"):
    assert key in c["assumed"]
  spec = cell.family().model_spec(c)
  n = sum(int(np.prod(v[0])) for v in spec.dense_leaves.values())
  assert n == 417453056 and len(spec.dense_leaves) == 3 + 4 * 12
  assert spec.n_numerical == 4096 + 1024 and spec.summed_tables == {0}
  assert (spec.inputs[0].hotness, spec.inputs[0].sequence) == (4096, True)
  mix = cell.traffic
  assert (mix["global_batch"], mix["alpha"], mix["pool_batches"],
          mix["steps_in_flight"], mix["numerical_range"]) == (
              1, 1.05, 16, 3, [0, 1])


def test_a_program_without_the_model_says_so_at_once(root, monkeypatch):
  """What the parent of this PR does with these files laid over it."""
  import importlib.util
  real = importlib.util.find_spec
  monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None
                      if name.endswith("models.sdar_moe") else real(name, *a))
  cell = specs.load_cell(CELL, root)
  with pytest.raises(specs.SpecError, match="no .*models/sdar_moe.py"):
    cell.family().model_spec(cell.config)


def test_the_familys_batch(root):
  cell, _, spec, pool, _ = _setup(root, 2**33 + 1)
  b = pool[0]
  assert b.cats.shape == (4, 16) and b.numerical.shape == (4, 16 + 4)
  assert np.array_equal(b.labels["targets"], b.cats)
  assert 0 <= b.numerical.min() and b.numerical.max() < 1
  assert b.cats.max() < 96


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
  """The loss counts every position once: the mask and 1/t are gone."""
  def loss_fn(outputs, labels):
    return parts.loss_fn(dict(outputs, weight=jnp.ones_like(
        outputs["weight"])), labels)
  return dataclasses.replace(parts, loss_fn=loss_fn)


def _summed_off(parts):
  return dataclasses.replace(
      parts, rule=dataclasses.replace(parts.rule, summed=False))


def _moments_unwritten(prog, step):
  """A step that leaves the rows' moment lanes as it found them."""
  def broken(state, *batch):
    old = {k: jnp.copy(v) for k, v in state["fused"].items()}
    new, loss = step(state, *batch)
    fused = {}
    for name, buf in new["fused"].items():
      lay = prog.layouts[name]
      lane = np.arange(lay.phys_width)
      moments = (lane < lay.rows_per_phys * lay.stride) \
          & (lane % lay.stride >= lay.width)
      fused[name] = jnp.where(moments[None, :], old[name], buf)
    return dict(new, fused=fused), loss
  return broken


@pytest.mark.parametrize("broken,fails", [
    (None, []),
    ("weight", ["loss_gap"]),
    ("summed_off", ["table_change_gap"]),
    ("control", ["loss_gap"]),
])
def test_a_run_of_the_family(root, capsys, monkeypatch, broken, fails):
  cell = specs.load_cell(CELL, root)
  devices, dev = bench_toy.cpu_devices(1)
  if broken == "weight":
    bench_toy.break_compile_step(monkeypatch, _rebuilt(_weight_dropped))
  if broken == "summed_off":
    bench_toy.break_compile_step(monkeypatch, _rebuilt(_summed_off))
  if broken == "control":
    monkeypatch.setattr(reference, "one_step", functools.partial(
        reference.one_step, precision="bfloat16"))
  result = run.run_cell(cell, 2**31 + 77, 0.3, False, devices, dev)
  lines = [ln.split() for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("compare")]
  verdict = {ln[1].rstrip(":"): ln[-1] for ln in lines}
  assert set(verdict) == {"fill", "loss_gap", "table_change_gap",
                          "dense_change_gap", "untouched"}
  assert result.correct == (broken is None)
  for name in fails:
    assert verdict[name] == "OUTSIDE"
  assert verdict["fill"] == verdict["untouched"] == "ok"
  assert result.attempted > 1 and result.failed == 0


def test_summed_off_diverges_on_power_law_ids(root):
  """Per occurrence, Adam on a token table overshoots on every hot row (its
  first moment is counted once per read): over 30 steps on power-law ids the
  hottest token's row runs away, by tens of times what Adam can move a
  weight in 30 steps, and the loss ends higher."""
  cell, family, spec, _, _ = _setup(root, 9)
  lr, batch = 0.02, 16
  mix = dict(cell.traffic, global_batch=batch)
  pool = traffic.make_pool(mix, spec.inputs, spec.n_numerical, 9,
                           traffic.family_labels(family, cell.config))
  reads = np.bincount(np.concatenate([b.cats.reshape(-1) for b in pool]))
  hot = int(np.argmax(reads))
  assert reads[hot] / len(pool) > 20          # reads of it a step
  config = dict(cell.config, optimizer=dict(cell.config["optimizer"],
                                            learning_rate=lr))
  moved, final = {}, {}
  for summed in (True, False):
    parts = family.build_parts(config, 1, batch)
    if not summed:
      parts = _summed_off(parts)
    prog = program.Program(parts, spec, 9, None)
    state = prog.fill()
    step = prog.compile_step(state, pool[0])
    losses = []
    for i in range(30):
      state, loss = step(state, *prog.put(pool[i % len(pool)]))
      losses.append(float(loss))
    change, _ = prog.table_changes(state, {0: np.array([hot])})
    moved[summed], final[summed] = float(np.abs(change[0]).max()), \
        np.mean(losses[-3:])
    assert np.all(np.isfinite(losses)) and final[summed] < losses[0]
  assert moved[True] <= 30 * lr * 1.2         # |Adam's step| <= about the rate
  assert moved[False] > 10 * moved[True]
  assert final[True] < final[False]


def test_the_tables_moments_are_adams_of_the_summed_gradient(root):
  """The harness compares no accumulator of a summed table (the reference
  records none for them), so this does: after one step the moment lanes of
  the rows read hold (1 - b1) g and (1 - b2) g^2 of the row's summed
  gradient, which the table's own change, -lr sign(g), agrees with; and a
  step that leaves them unwritten is seen."""
  cell, family, spec, pool, logits = _setup(root, 5)
  ref = reference.one_step(spec, logits, pool[0], 5)
  prog = program.Program(family.build_parts(cell.config, 1, 4), spec, 5, None)
  state = prog.fill()
  step = prog.compile_step(state, pool[0])
  for breaker, written in ((None, True), (_moments_unwritten, False)):
    call = step if breaker is None else breaker(prog, step)
    after, _ = call(jax.tree_util.tree_map(jnp.copy, state),
                    *prog.put(pool[0]))
    change, moments = prog.table_changes(after, ref.table_rows)
    w = spec.tables[0].width
    m, v = moments[0][:, :w], moments[0][:, w:]
    assert np.allclose(change[0], ref.table_delta[0], atol=2e-5)
    if not written:
      assert not m.any() and not v.any()
      continue
    opt = cell.config["optimizer"]
    g = m / (1 - opt["b1"])
    np.testing.assert_allclose(v, (1 - opt["b2"]) * g * g, rtol=2e-3,
                               atol=1e-12)
    big = np.abs(g) > 1e-4
    assert big.mean() > 0.5
    assert np.array_equal(np.sign(change[0][big]), -np.sign(g[big]))


# ---- the new metrics' readers, on a hand-built trace ------------------------
STACK = "jit(step_fn)/jit(local_step)/"
OPS = {  # op -> (name stack, start ns, duration ns)
    "fusion.1": (STACK + "jvp(de_model)/SDARMoE/de_attention/dot_general",
                 0, 100),
    "splash_mqa_fwd_residuals.2": (
        STACK + "jvp(de_model)/SDARMoE/de_attention/vmap(jit(_splash_attention))"
        "/splash_mqa_fwd_residuals/pallas_call", 100, 400),
    "while.3": (STACK + "jvp(de_model)/SDARMoE/de_moe/while", 500, 1000),
    "fusion.4": (STACK + "jvp(de_model)/SDARMoE/de_moe/while/body/closed_call"
                 "/checkpoint/cond/branch_1_fun/de_moe_route/gather", 500, 200),
    "ragged-dot-none.5": (STACK + "jvp(de_model)/SDARMoE/de_moe/while/body/"
                          "closed_call/ragged-dot-none", 700, 600),
    "fusion.6": ("", 1300, 100),     # nameless, inside the while: its holder's
    "fusion.7": (STACK + "transpose(jvp(de_model))/SDARMoE/de_lm_head/"
                 "dot_general", 1500, 300),
    "fusion.8": (STACK + "de_loss/reduce_sum", 1800, 50),
    "fusion.9": (STACK + "de_dense_update/add", 1850, 150),
}


def _hand_built(ops_table=None):
  ops_table = ops_table or OPS
  names = scope_reduce.OpNames(
      {op: s for op, (s, _, _) in ops_table.items()}, {})
  ops = [(op, start, dur, 0) for op, (_, start, dur) in ops_table.items()]

  class Red:
    steps = [[("jit_step_fn(7)", 0, 2000)]]

    def per_step_ms(self, select):
      hit = [d for n, _, d, _ in ops if select(n)]
      return sum(hit) * 1e-6 if hit else None
  red = Red()
  red.ops = [ops]
  return red, names


def test_the_new_readers_on_a_hand_built_trace():
  red, names = _hand_built()
  cell = specs.load_cell(CELL)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_children": scope_children.per_step_ns(red, names)}
  read = lambda m: cell.layer_reader(m)(red, ctx)
  assert read("attn_ms") == pytest.approx(500e-6)
  assert read("moe_ms") == pytest.approx(1000e-6)
  assert read("moe_route_ms") == pytest.approx(200e-6)
  assert read("moe_experts_ms") == pytest.approx(600e-6)  # the kernel's name
  assert read("lm_head_ms") == pytest.approx(350e-6)
  peak = 197e12
  assert read("attn_mxu_pct") == pytest.approx(
      100 * roofline_lm.attention_flops(cell.config, cell.traffic)
      / peak / 500e-9)
  assert read("moe_experts_mxu_pct") == pytest.approx(
      100 * roofline_lm.moe_experts_flops(cell.config, cell.traffic)
      / peak / 600e-9)
  assert read("splash_attention_roofline") == pytest.approx(
      100 * roofline_lm.attention_core_flops(cell.config, cell.traffic)
      / peak / 400e-9)
  # XLA's ragged-dot kernel made at the step's top level carries no name
  # stack at all: its name places it
  assert scope_children.op_scopes(scope_reduce.OpNames({}, {}),
                                  "ragged-dot-none.60") == {
                                      "de_moe", "de_moe_experts"}
  # a program without the scopes (the parent): nothing to read, no raise
  red, bare = _hand_built({
      f"fusion.{i}": (STACK + "de_model/dot_general", start, dur)
      for i, (_, start, dur) in enumerate(OPS.values())})
  ctx = {"cell": cell, "device_kind": "TPU v5 lite",
         "scope_children": scope_children.per_step_ns(red, bare)}
  for m in NEW[5:13]:
    name = os.path.basename(m)[:-5]
    assert cell.layer_reader(name)(red, ctx) is None, name


@pytest.mark.parametrize("length,block", [(16, 4), (8, 2), (12, 3), (64, 4)])
def test_the_attention_counts_are_the_masks(length, block):
  pairs = 0
  for i in range(2 * length):          # pair by pair, over [xt ; x0]
    for j in range(2 * length):
      bi, bj = (i % length) // block, (j % length) // block
      if i < length:
        pairs += (bj == bi) if j < length else (bj < bi)
      else:
        pairs += j >= length and bj <= bi
  assert roofline_lm.block_diffusion_pairs(length, block) == pairs \
      == length * (length + block)


def test_the_least_work_of_the_cells_step():
  cell = specs.load_cell(CELL)
  c, mix = cell.config, cell.traffic
  core = roofline_lm.attention_core_flops(c, mix)
  assert core == 12 * 128 * 32 * 4096 * 4100 * 1 * 4
  assert roofline_lm.attention_flops(c, mix) - core == pytest.approx(
      6 * 2048 * 128 * 72 * 8192 * 4)
  assert roofline_lm.moe_experts_flops(c, mix) == pytest.approx(
      6 * 4718592 * 8192 * 4)
  assert roofline_lm.mxu_pct(197e12, 1000.0, "TPU v5 lite") == \
      pytest.approx(100.0)
  assert roofline_lm.mxu_pct(1.0, 0.0, "TPU v5 lite") is None


def test_moe_load_counts_on_the_toy(root, capsys):
  import importlib.util
  spec = importlib.util.spec_from_file_location(
      "moe_load", os.path.join(bench_toy.ROOT, "tools", "moe_load.py"))
  tool = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(tool)
  report = tool.main([CELL, "--seed", "3", "--root", root])
  assert report["dropped"] == [0, 0]
  assert report["positions_a_layer"] == 2 * 4 * 16
  # 4 of 8 experts held, 2 of 8 chosen: about one assignment a position
  for n in report["assignments_on_held_experts"]:
    assert 0.5 * 128 < n < 1.5 * 128
  assert all(1 <= x < 4 for x in report["largest_load_over_mean"])
  assert 0.3 < report["masked_share"] < 0.8
  assert json.loads(capsys.readouterr().out.splitlines()[-1]) == report
