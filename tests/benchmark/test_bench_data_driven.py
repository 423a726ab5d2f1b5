"""A later PR adds files and entries and edits no file that exists: a
configuration, a cell, a traffic mix and per-layer metrics (one as data, one
as a reader of a new kind) added to a copy of the benchmark are found by
name, with every committed file left as it was."""

import json
import os

import pytest

import bench_toy
from benchmark import specs, trace_reduce, traffic


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("grown_root")))
  before = bench_toy.digests(root)
  bdir = os.path.join(root, "benchmark")
  with open(os.path.join(bdir, "configs", "dlrm-criteo1tb.json")) as f:
    config = json.load(f)
  config.update(top_mlp=[64, 1], bottom_mlp=[32, 16], embedding_width=16,
                vocab_scale=0.0001)
  with open(os.path.join(bdir, "configs", "dlrm-small-top.json"), "w") as f:
    json.dump(config, f)
  with open(os.path.join(bdir, "workloads", "uniform_ids.json"), "w") as f:
    json.dump({"generator": "power_law", "alpha": 0, "global_batch": 128,
               "pool_batches": 2, "numerical_range": [0, 1],
               "steps_in_flight": 1}, f)
  with open(os.path.join(bdir, "layer_metrics", "fusion_ms.json"), "w") as f:
    json.dump({"reduction": "per_step_sum_ms",
               "selector": {"op_pattern": "^fusion"}}, f)
  with open(os.path.join(bdir, "layer_metrics", "steps_traced.py"), "w") as f:
    f.write("def read(red, ctx):\n  return float(red.n_steps())\n")
  with open(os.path.join(root, "BENCHMARK.json")) as f:
    bench = json.load(f)
  bench["configs"].append({
      "name": "dlrm-small-top", "source": "a test",
      "file": "benchmark/configs/dlrm-small-top.json", "reduced": [],
      "why": "added as files"})
  bench["workloads"].append({
      "name": "small_top_uniform", "config": "dlrm-small-top",
      "traffic": "uniform_ids", "chips": 1, "why": "added as files"})
  for name, unit in (("fusion_ms", "ms"), ("steps_traced", "steps")):
    bench["per_layer"].append({
        "name": name, "unit": unit, "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "train_samples_per_s", "workloads": ["small_top_uniform"]})
  with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
    json.dump(bench, f)
  after = bench_toy.digests(root)
  assert {p: d for p, d in after.items() if p in before} == before
  return root


def test_the_new_cell_loads_with_its_own_files(grown):
  cell = specs.load_cell("small_top_uniform", grown)
  assert cell.config["top_mlp"] == [64, 1]
  assert cell.traffic["alpha"] == 0
  assert [m["name"] for m in cell.end_to_end] == [
      "train_samples_per_s", "step_ms_p95", "hbm_peak_gib", "setup_s"]
  # per-layer metrics that list cells are owed only there
  assert [m["name"] for m in cell.per_layer] == ["fusion_ms", "steps_traced"]
  old = specs.load_cell("dlrm_train_1chip", grown)
  assert "fusion_ms" not in [m["name"] for m in old.per_layer]
  spec = cell.family().model_spec(cell.config)
  assert spec.tables[0].width == 16
  pool = traffic.make_pool(cell.traffic, spec.inputs, spec.n_numerical, 5)
  assert len(pool) == 2 and pool[0].cats.shape == (128, 26)


def test_the_new_metrics_read_a_trace(grown):
  cell = specs.load_cell("small_top_uniform", grown)
  fixture = os.path.join(os.path.dirname(__file__), "data",
                         "trace_fixture.json")
  with open(fixture) as f:
    red = trace_reduce.Reduced(json.load(f), r"^jit_step_fn\(")
  assert cell.layer_reader("fusion_ms")(red, {}) == pytest.approx(300e-6)
  assert cell.layer_reader("steps_traced")(red, {}) == 2.0


def test_the_new_cell_runs(grown):
  from benchmark import run
  cell = specs.load_cell("small_top_uniform", grown)
  devices, dev = bench_toy.cpu_devices(1)
  result = run.run_cell(cell, 2**40 + 3, 0.2, False, devices, dev)
  assert result.correct and result.attempted > 1


@pytest.mark.parametrize("missing,match", [
    ("configs/dlrm-small-top.json", "missing"),
    ("workloads/uniform_ids.json", "missing"),
])
def test_a_missing_file_is_named(grown, tmp_path, missing, match):
  path = os.path.join(grown, "benchmark", missing)
  hidden = str(tmp_path / "hidden.json")
  os.rename(path, hidden)
  try:
    with pytest.raises(specs.SpecError, match=match):
      specs.load_cell("small_top_uniform", grown)
  finally:
    os.rename(hidden, path)


def test_the_committed_benchmark_is_consistent():
  """Every cell's files exist, every per-layer metric has its file and its
  layer, and every metric a cell is owed is one its files can produce."""
  with open(os.path.join(bench_toy.ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  e2e = {m["name"] for m in bench["end_to_end"]}
  assert "setup_s" in e2e
  for w in bench["workloads"]:
    cell = specs.load_cell(w["name"])
    assert cell.config["family"] in ("dlrm", "zoo")
    cell.family().model_spec(cell.config)
    for m in cell.per_layer:
      assert m["moves"] in e2e
      with open(os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                             m["name"] + ".json")) as f:
        spec = json.load(f)
      assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
      assert callable(cell.layer_reader(m["name"]))
