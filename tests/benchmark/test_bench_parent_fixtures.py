"""The repaired harness computes, for the families that say nothing new,
what the parent (03d7441, PR 27) computed: the pools' bits, every field of
the reference's one step at float32 and under the bfloat16 control, and
every ``compare`` line of a toy run (``fixtures.py`` says how they were
recorded). On the machine that recorded them the comparison is bit for bit;
elsewhere (another CPU or numpy rounds the reference's matmuls otherwise)
the float fields are held by their norms."""

import json

import pytest

import bench_toy
import fixtures

KEYS = sorted(bench_toy.CELLS)
MIXES = ["criteo_powerlaw", "zoo_powerlaw"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
  return bench_toy.make_root(str(tmp_path_factory.mktemp("fixture_root")))


@pytest.fixture(scope="module")
def parent():
  with open(fixtures.PATH) as f:
    return json.load(f)


@pytest.fixture(scope="module")
def same_machine(parent):
  return parent["machine"] == fixtures.machine()


def test_the_fixtures_cover_every_committed_mix(root, parent):
  assert fixtures.mixes(root) == MIXES
  assert sorted(parent["pools"]) == sorted(
      f"{m}/{k}" for m in MIXES for k in KEYS)
  assert (parent["seed"], parent["run_seed"]) == (fixtures.SEED,
                                                  fixtures.RUN_SEED)


@pytest.mark.parametrize("key", KEYS)
@pytest.mark.parametrize("mix", MIXES)
def test_pools_are_the_parents_bits(root, parent, mix, key):
  """numpy's generators and the integer hash: the same on every machine."""
  assert fixtures.pool_digest(root, mix, key) == parent["pools"][f"{mix}/{key}"]


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
@pytest.mark.parametrize("key", KEYS)
def test_the_references_one_step_is_the_parents(root, parent, same_machine,
                                                key, precision):
  want = parent["step_changes"][f"{key}/{precision}"]
  got = fixtures.step_change_digest(root, key, precision)
  assert sorted(got) == sorted(want)
  # ids and the seed's weights involve no float arithmetic of the device
  assert got["table_rows"] == want["table_rows"]
  assert got["dense_before"] == want["dense_before"]
  if same_machine:
    assert got == want
    return
  assert got["loss_value"] == pytest.approx(want["loss_value"], rel=1e-5)
  for field in ("table_delta", "acc_delta", "dense_delta"):
    assert sorted(got[field]) == sorted(want[field])
    for leaf, w in want[field].items():
      assert got[field][leaf]["norm"] == pytest.approx(
          w["norm"], rel=1e-3, abs=1e-12), (field, leaf)


@pytest.mark.parametrize("key", KEYS)
def test_a_toy_runs_compare_lines_are_the_parents(root, parent, same_machine,
                                                  key):
  correct, lines = fixtures.compare_lines(root, key)
  want = parent["compare_lines"][key]
  assert correct
  if same_machine:
    assert lines == want
  else:  # the same comparisons against the same limits, all inside
    cut = lambda ln: (ln.split(":")[0], ln.split("(limit")[1])
    assert [cut(ln) for ln in lines] == [cut(ln) for ln in want]
