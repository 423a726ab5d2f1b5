"""The family interface has room for a sequence model, shown by a toy
family (``data/toyseq/``) added to a copy of the benchmark as files alone:
an input kept as a sequence, labels that are a tree, a vocabulary loss, a
leaf that starts at 1 and a leaf of rank 3, Adam on the dense leaves and on
the table's rows. ``run.run_cell`` drives it on the CPU: the sound program
is correct, and a dropped mask, unwritten second-moment lanes and the
bfloat16 control each come out as not correct by a comparison of their
own. The toy is the harness's test and no model of anything."""

import functools
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import check, program, reference, run, specs, traffic, weights

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "toyseq")
CELL = "toyseq_train_1chip"


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
  """The benchmark's copy with the toy family added: new files and new
  ``BENCHMARK.json`` entries, nothing that was there rewritten."""
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("seq_root")))
  before = bench_toy.digests(root)
  with open(os.path.join(root, "BENCHMARK.json")) as f:
    bench = json.load(f)
  old = json.loads(json.dumps(bench))
  for name, sub in (("toyseq.py", "families"), ("toyseq.json", "configs"),
                    ("toyseq_tokens.json", "workloads")):
    dst = os.path.join(root, "benchmark", sub, name)
    assert not os.path.exists(dst)
    shutil.copy(os.path.join(TOY, name), dst)
  bench["configs"].append({
      "name": "toyseq", "source": "a test",
      "file": "benchmark/configs/toyseq.json", "reduced": [],
      "why": "added as files"})
  bench["workloads"].append({
      "name": CELL, "config": "toyseq", "traffic": "toyseq_tokens",
      "chips": 1, "why": "added as files"})
  with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
    json.dump(bench, f)
  after = bench_toy.digests(root)
  assert {p: d for p, d in after.items() if p in before} == before
  assert len(after) == len(before) + 3
  for key, entries in old.items():  # what was there is still there, first
    assert bench[key][:len(entries)] == entries if isinstance(entries, list) \
        else bench[key] == entries
  return root


def _setup(grown, seed):
  cell = specs.load_cell(CELL, grown)
  family = cell.family()
  spec = family.model_spec(cell.config)
  pool = traffic.make_pool(cell.traffic, spec.inputs, spec.n_numerical, seed,
                           traffic.family_labels(family, cell.config))
  logits = functools.partial(family.reference_logits, cell.config)
  return cell, family, spec, pool, logits


def test_the_familys_batch_is_its_own(grown):
  cell, _, spec, pool, _ = _setup(grown, 11)
  b = pool[0]
  assert b.numerical.shape == (64, 0) and b.cats.shape == (64, 8)
  assert set(b.labels) == {"targets", "mask", "weight"}
  assert b.labels["targets"].shape == (64, 8)
  assert b.labels["targets"].dtype == np.int32
  assert b.labels["mask"].shape == (64, 8) and b.labels["weight"].shape == (64,)
  assert 0.3 < b.labels["mask"].mean() < 0.7
  # the labels are drawn after the ids and the numerical features: the ids
  # are the ones the same inputs get under the default coin
  coin = traffic.make_batch(cell.traffic, spec.inputs, 0, 11, 0)
  assert np.array_equal(coin.cats, b.cats) and coin.labels.shape == (64,)
  assert "numerical_range" not in cell.traffic


def test_leaves_take_an_offset_and_any_rank(grown):
  _, _, spec, _, _ = _setup(grown, 3)
  w0 = reference.dense_weights(spec, 3)
  assert np.array_equal(w0["gain"], np.ones((16,), np.float32))
  assert w0["up"].shape == (2, 16, 32) and w0["up"].dtype == np.float32
  # a leaf of rank 3 is hashed as rows x its last dimension
  flat = weights.dense_np(weights.leaf_key(3, "up"), spec.dense_leaves["up"][1],
                          (32, 32))
  assert np.array_equal(w0["up"].reshape(32, 32), flat)
  assert not np.array_equal(w0["up"][0], w0["up"][1])
  shifted = weights.dense_np(7, 0.25, (4, 8), 2.0)
  assert np.array_equal(shifted, weights.dense_np(7, 0.25, (4, 8))
                        + np.float32(2.0))
  assert np.all(np.abs(shifted - 2.0) <= 0.25)


@pytest.mark.parametrize("g", [
    np.array([[0.3, -1e-4, 2.0], [1e-7, -5.0, 0.02]]),
    np.linspace(-1.0, 1.0, 7)[:, None] * np.array([[1.0, 1e-3, 1e-6]]),
])
def test_adam_is_optax_adams_first_step(g):
  import optax
  opt = {"name": "adam", "learning_rate": 0.01, "b1": 0.9, "b2": 0.999,
         "eps": 1e-8}
  with jax.enable_x64(True):
    tx = optax.adam(0.01, b1=0.9, b2=0.999, eps=1e-8)
    g64 = jnp.asarray(g, jnp.float64)
    upd, state = tx.update(g64, tx.init(jnp.zeros_like(g64)))
    want, mu, nu = np.asarray(upd), np.asarray(state[0].mu), \
        np.asarray(state[0].nu)
  assert want.dtype == np.float64
  change, (m, v) = reference.update(opt, g)
  np.testing.assert_allclose(change, want, rtol=1e-12, atol=0)
  np.testing.assert_allclose(m, mu, rtol=1e-12, atol=0)
  np.testing.assert_allclose(v, nu, rtol=1e-12, atol=0)
  assert reference.initial_accumulators(opt) == (0.0, 0.0)
  assert reference.initial_accumulators({"name": "sgd"}) == ()


def test_the_reference_keeps_the_sequence_and_both_moments(grown):
  cell, _, spec, pool, logits = _setup(grown, 5)
  ref = reference.one_step(spec, logits, pool[0], 5)
  n = len(ref.table_rows[0])
  assert ref.table_delta[0].shape == (n, 16)
  assert ref.acc_delta[0].shape == (n, 32)   # first moment | second moment
  m, v = ref.acc_delta[0][:, :16], ref.acc_delta[0][:, 16:]
  assert np.all(v >= 0) and np.any(m < 0) and np.any(v > 0)
  assert set(ref.dense_delta) == {"gain", "router", "up", "down", "head"}
  assert ref.dense_delta["up"].shape == (2, 16, 32)
  # a row read only at positions the mask leaves out does not move
  mask = pool[0].labels["mask"].astype(bool)
  counted = np.unique(pool[0].cats[mask])
  idle = ~np.isin(ref.table_rows[0], counted)
  assert idle.any() and not np.any(ref.table_delta[0][idle])
  assert np.all(np.any(ref.table_delta[0][~idle] != 0, axis=1))
  # Adam's first step moves every counted element by about the rate
  lr = cell.config["optimizer"]["learning_rate"]
  assert np.max(np.abs(ref.dense_delta["head"])) <= lr * 1.001


def _mask_dropped(prog, step):
  """A step whose loss counts every position."""
  def broken(state, numerical, cats, labels):
    return step(state, numerical, cats,
                dict(labels, mask=jnp.ones_like(labels["mask"])))
  return broken


def _second_moment_unwritten(prog, step):
  """A step that leaves the rows' second-moment lanes as it found them."""
  def broken(state, *batch):
    old = {k: jnp.copy(v) for k, v in state["fused"].items()}
    new, loss = step(state, *batch)
    fused = {}
    for name, buf in new["fused"].items():
      lay = prog.layouts[name]
      lane = np.arange(lay.phys_width)
      second = (lane < lay.rows_per_phys * lay.stride) \
          & (lane % lay.stride >= 2 * lay.width)
      fused[name] = jnp.where(second[None, :], old[name], buf)
    return dict(new, fused=fused), loss
  return broken


def _bfloat16_control(monkeypatch):
  """The reference at the precision below the configuration's, in the
  reference's place: the program is as far from it as it is from the
  program."""
  monkeypatch.setattr(reference, "one_step", functools.partial(
      reference.one_step, precision="bfloat16"))


@pytest.mark.parametrize("broken,fails,passes", [
    (None, [], ["fill", "loss_gap", "table_change_gap", "accumulator_stray",
                "accumulator_2_stray", "dense_change_gap", "untouched"]),
    ("mask", ["loss_gap"], ["fill", "untouched"]),
    ("second_moment", ["accumulator_2_stray"],
     ["fill", "loss_gap", "table_change_gap", "accumulator_stray",
      "dense_change_gap", "untouched"]),
    ("control", ["dense_change_gap"], ["fill", "untouched"]),
])
def test_a_run_of_the_toy_family(grown, capsys, monkeypatch, broken, fails,
                                 passes):
  cell = specs.load_cell(CELL, grown)
  devices, dev = bench_toy.cpu_devices(1)
  breaker = {"mask": _mask_dropped,
             "second_moment": _second_moment_unwritten}.get(broken)
  if breaker is not None:
    bench_toy.break_compile_step(monkeypatch, breaker)
  if broken == "control":
    _bfloat16_control(monkeypatch)
  result = run.run_cell(cell, 2**31 + 77, 0.3, False, devices, dev)
  lines = [ln.split() for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("compare")]
  verdict = {ln[1].rstrip(":"): ln[-1] for ln in lines}
  assert result.correct == (broken is None)
  for name in fails:
    assert verdict[name] == "OUTSIDE"
  for name in passes:
    assert verdict[name] == "ok"
  assert result.attempted > 1 and result.failed == 0
  assert result.values["train_samples_per_s"] > 0


def test_the_toy_familys_program_agrees_on_more_seeds(grown):
  for seed in (0, 2**40 + 1):
    cell, family, spec, pool, logits = _setup(grown, seed)
    ref = reference.one_step(spec, logits, pool[0], seed)
    low = reference.one_step(spec, logits, pool[0], seed,
                             precision="bfloat16")
    parts = family.build_parts(cell.config, 1, 64)
    prog = program.Program(parts, spec, seed, None)
    state = prog.fill()
    step = prog.compile_step(state, pool[0])
    _, compared, _ = check.one_step(prog, state, step, pool[0], ref,
                                    cell.config["check_limits"])
    assert [c.line() for c in compared if not c.ok] == []
    assert len(compared) == 7
    limits = cell.config["check_limits"]
    assert check.worst_gap(low.dense_delta, ref.dense_delta, str)[0] \
        > 3 * limits["dense_change_gap"]
