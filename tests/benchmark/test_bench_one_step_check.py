"""The one-step check at toy size on the CPU: the program's step agrees with
the plain reference on each family, the lower-precision control does not,
and a run whose timed path is broken comes out as not correct."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import check, program, reference, run, specs, traffic

SEEDS = [0, 7, 2**31 + 9001]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  return bench_toy.make_root(str(tmp_path_factory.mktemp("toy_root")))


def _setup(root, key, seed, table_dtype=jnp.float32):
  cell = specs.load_cell(bench_toy.CELLS[key], root)
  family = cell.family()
  spec = family.model_spec(cell.config)
  pool = traffic.make_pool(cell.traffic, spec.inputs, spec.n_numerical, seed)
  logits = functools.partial(family.reference_logits, cell.config)
  devices, _ = bench_toy.cpu_devices(cell.chips)
  mesh = None
  if cell.chips > 1:
    from distributed_embeddings_tpu.parallel import create_mesh
    mesh = create_mesh(cell.chips, devices=devices)
  parts = family.build_parts(cell.config, cell.chips,
                             int(cell.traffic["global_batch"]))
  prog = program.Program(parts, spec, seed, mesh, table_dtype=table_dtype)
  return cell, spec, logits, pool, prog


def _compare(root, key, seed, table_dtype=jnp.float32):
  cell, spec, logits, pool, prog = _setup(root, key, seed, table_dtype)
  ref = reference.one_step(spec, logits, pool[0], seed)
  state = prog.fill()
  step = prog.compile_step(state, pool[0])
  _, compared, _ = check.one_step(prog, state, step, pool[0], ref,
                                  cell.config["check_limits"])
  return {c.name: c for c in compared}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("key", ["dlrm", "zoo", "dlrm4"])
def test_program_agrees_with_the_reference(root, key, seed):
  got = _compare(root, key, seed)
  assert [c.line() for c in got.values() if not c.ok] == []
  # on the CPU both sides are true float32: far inside the chip's limits
  assert got["table_change_gap"].value < 2e-3
  assert got["dense_change_gap"].value < 2e-3
  assert got["untouched"].where.split()[-2] != "0"  # it looked at rows
  # only a rule that keeps an accumulator in the row has one to compare
  assert ("accumulator_stray" in got) == (key == "zoo")


@pytest.mark.parametrize("key", ["dlrm", "zoo"])
def test_bfloat16_tables_fail_the_check(root, key):
  """The program with its own lower-precision path switched on (tables held
  in bfloat16): the fill still reads back exactly what a bfloat16 table
  holds, and the one-step change is outside its limit."""
  got = _compare(root, key, 7, table_dtype=jnp.bfloat16)
  assert got["fill"].ok
  assert not got["table_change_gap"].ok
  # most of a one-step change is below bfloat16's step of the weight it is
  # added to: the change read back is mostly gone
  assert got["table_change_gap"].value > 0.5


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("key", ["dlrm", "zoo"])
def test_bfloat16_reference_is_outside_the_limits(root, key, seed):
  """The contract's control: the reference itself with its step's arithmetic
  in bfloat16 (weights kept and updated in float32), put in the program's
  place, must fail a limit the float32 program passes."""
  cell, spec, logits, pool, _ = _setup(root, key, seed)
  limits = cell.config["check_limits"]
  ref = reference.one_step(spec, logits, pool[0], seed)
  low = reference.one_step(spec, logits, pool[0], seed, precision="bfloat16")
  tables, _ = check.worst_gap(low.table_delta, ref.table_delta,
                              reference.table_name)
  dense, _ = check.worst_gap(low.dense_delta, ref.dense_delta, str)
  assert tables > limits["table_change_gap"] \
      or dense > limits["dense_change_gap"]


def test_accumulator_stray_allows_what_float32_accumulation_explains():
  step = float(np.spacing(np.float32(0.1)))
  ref = {0: np.array([[8 * step, 0.0]]), 1: np.array([[2000 * step, step]])}
  # one increment at a time, float32 loses the small ones and rounds the rest
  lost = {0: np.zeros((1, 2), np.float32),
          1: np.array([[1990 * step, 0.0]], np.float32)}
  stray, where = check.accumulator_stray(lost, ref, 0.1)
  assert stray == 0 and "4 of the rows read" in where and "2000" in where
  # an accumulator that never moved, where the change is visible
  stray, _ = check.accumulator_stray({t: 0 * v for t, v in lost.items()},
                                     ref, 0.1)
  assert stray == 1
  # garbage, and a value that is not a number
  lost[0][0, 1] = 1e-3
  lost[1][0, 1] = np.nan
  assert check.accumulator_stray(lost, ref, 0.1)[0] == 2


def _unchanged_state(prog, step):
  """A step that computes its loss and hands its state back as it was."""
  def broken(state, *batch):
    _, loss = step(jax.tree_util.tree_map(jnp.copy, state), *batch)
    return state, loss
  return broken


def _half_batch(prog, step):
  """A step that trains on the first half of the batch twice over."""
  def broken(state, numerical, cats, labels):
    def twice(x):
      half = x.shape[0] // 2
      return jnp.concatenate([x[:half], x[:half]])
    return step(state, twice(numerical), twice(cats), twice(labels))
  return broken


def _dense_update_dropped(prog, step):
  """A step whose update of the dense leaves is thrown away."""
  def broken(state, *batch):
    dense = jax.tree_util.tree_map(jnp.copy, state["dense"])
    new, loss = step(state, *batch)
    return dict(new, dense=dense), loss
  return broken


def _accumulator_update_dropped(prog, step):
  """A step whose scatter into the rows' accumulator lanes is thrown away."""
  def broken(state, *batch):
    old = {k: jnp.copy(v) for k, v in state["fused"].items()}
    new, loss = step(state, *batch)
    fused = {}
    for name, buf in new["fused"].items():
      lay = prog.layouts[name]
      lane = np.arange(lay.phys_width)
      acc = (lane < lay.rows_per_phys * lay.stride) \
          & (lane % lay.stride >= lay.width)
      fused[name] = jnp.where(acc[None, :], old[name], buf)
    return dict(new, fused=fused), loss
  return broken


@pytest.mark.parametrize("key,breaker,fails", [
    ("dlrm", None, []),
    ("zoo", None, []),
    ("dlrm", _unchanged_state, ["table_change_gap", "dense_change_gap"]),
    ("dlrm", _half_batch, ["loss_gap", "table_change_gap"]),
    ("dlrm", _dense_update_dropped, ["dense_change_gap"]),
    ("zoo", _accumulator_update_dropped, ["accumulator_stray"]),
])
def test_a_run_with_a_broken_timed_path_is_not_correct(
    root, capsys, monkeypatch, key, breaker, fails):
  """Everything of a run but the look for a chip, at toy size. The timed
  path is broken underneath the harness: where it compiles its step."""
  cell = specs.load_cell(bench_toy.CELLS[key], root)
  devices, dev = bench_toy.cpu_devices(1)
  if breaker is not None:
    bench_toy.break_compile_step(monkeypatch, breaker)
  result = run.run_cell(cell, 2**31 + 5, 0.3, False, devices, dev)
  out = capsys.readouterr().out
  outside = [ln.split()[1].rstrip(":") for ln in out.splitlines()
             if ln.startswith("compare") and ln.endswith("OUTSIDE")]
  assert result.correct == (breaker is None)
  assert (outside == []) == (breaker is None)
  for name in fails:
    assert name in outside
  assert result.attempted > 1 and result.failed == 0
  assert set(result.values) == {m["name"] for m in cell.end_to_end}
