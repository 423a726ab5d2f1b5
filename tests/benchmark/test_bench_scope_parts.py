"""Parts and passes from a trace (`benchmark/scope_parts.py`): the reader
that takes what it reads from the metric's own ``.json``, on hand-built
traces (ops under two parts in three passes, a fusion whose instructions
straddle two parts, a splash kernel under ``de_attn_core``, a ``while`` with a
body), through the metric files as the harness finds them, and on a trace on
disk of a program without the parts, which reads 0.0 and does not raise.
"""

import glob
import json
import os

import pytest

import bench_toy
import test_bench_scope_reduce as on_disk
from benchmark import scope_parts, scope_reduce, specs, trace_reduce

STEP = r"^jit_step_fn\("
TOP = "jit(step_fn)/jit(local_step)/"
FWD = TOP + "jvp(de_model)/M/"
REBUILT = TOP + "transpose(jvp(de_model))/jvp(de_model)/M/checkpoint/" \
    "rematted_computation/"
BWD = TOP + "transpose(jvp(de_model))/jvp(de_model)/M/checkpoint/"
PROJ, QK, CORE = ("de_attention/de_attn_proj/", "de_attention/de_attn_qk/",
                  "de_attention/de_attn_core/")
KERNEL = "vmap(vmap(jit(_splash_attention)))/splash_mqa_{}/pallas_call"
# op -> (its own name stack, offset in a step of 1000 ns, ns)
OPS = {
    "fusion.1": (FWD + PROJ + "dot_general", 0, 100),
    "fusion.2": (REBUILT + PROJ + "dot_general", 100, 50),
    "fusion.3": (BWD + PROJ + "dot_general", 150, 150),
    "fusion.4": (FWD + QK + "mul", 300, 40),
    "fusion.5": (REBUILT + QK + "mul", 340, 20),
    "fusion.6": (BWD + QK + "mul", 360, 40),
    "splash_mqa_fwd.7": (FWD + CORE + KERNEL.format("fwd"), 400, 100),
    "fusion.8": (FWD + CORE + "transpose", 500, 30),
    "fusion.9": ("", 530, 50),     # no name of its own: its instructions vote
    "fusion.10": (FWD + "de_attention/rsqrt", 580, 20),
    # a while of the backward's core with a body: an op the compiler left
    # without any name, and a kernel
    "while.11": (BWD + CORE + "while", 600, 200),
    "body.12": ("", 620, 80),
    "splash_mqa_dkv.13": (BWD + CORE + "while/body/" + KERNEL.format("dkv"),
                          700, 80),
    "fusion.14": (FWD + "de_moe/de_moe_route/de_moe_sort/cumsum", 800, 100),
    "copy.15": ("", 900, 50),      # no name anywhere: forward, no scope
}
INSIDE = {"fusion.9": [BWD + PROJ + "dot_general"] * 3 + [BWD + QK + "mul"] * 2
          + [""]}
# what the sdar cell's metric files should read of a step, ns
WANT = {"attn_proj_ms": 100 + 50 + 150 + 50, "attn_qk_ms": 40 + 20 + 40,
        "attn_layout_ms": 30 + (200 - 80 - 80) + 80, "moe_sort_ms": 100,
        "remat_forward_ms": 50 + 20}
KERNELS, OWN, ATTENTION, BUSY = 100 + 80, 20, 800, 950
CELL = "sdar_moe_train_1chip"
METRICS = ("attn_proj_ms", "attn_qk_ms", "attn_layout_ms", "moe_router_ms",
           "moe_sort_ms", "moe_dispatch_ms", "moe_return_ms",
           "linattn_proj_ms", "linattn_conv_ms", "remat_forward_ms")


def _reduced(table, n_devices=2, starts=(0, 1000, 2000)):
  """A ``trace_reduce.Reduced`` of steps of 1000 ns on ``n_devices``; device
  d is shifted by 7 d ns."""
  planes = []
  for d in range(n_devices):
    mods = [["jit_step_fn(7)", s + 7 * d, 1000] for s in starts]
    ops = [[f"%{op} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", s + 7 * d + off, ns]
           for s in starts for op, (_, off, ns) in table.items()]
    planes.append({"name": f"/device:TPU:{d}", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": ops}]})
  return trace_reduce.Reduced({"planes": planes}, STEP)


def _names(table, inside=None):
  return scope_reduce.OpNames(
      {op: s for op, (s, _, _) in table.items() if s}, dict(inside or {}))


def _ctx(cell, red, names):
  got = scope_parts.attribute(red, names)
  print(scope_parts.table(got, scope_parts.layers_of(
      scope_parts.metric_specs(cell))))
  return {"cell": cell, "device_kind": "TPU v5 lite", "scope_parts": got}


@pytest.fixture(scope="module")
def cell():
  return specs.load_cell(CELL)


def test_the_three_passes_of_a_name_stack():
  assert scope_parts.pass_of(FWD + PROJ + "dot_general") == "forward"
  assert scope_parts.pass_of(REBUILT + PROJ + "dot_general") == "remat"
  assert scope_parts.pass_of(BWD + PROJ + "dot_general") == "backward"
  assert scope_parts.pass_of(TOP + "de_apply/scatter-add") == "forward"
  assert scope_parts.pass_of("") == "forward"
  # whole components only
  assert scope_parts.pass_of(FWD + "my_rematted_computation_x/add") \
      == "forward"
  assert scope_parts.chain_of(BWD + CORE + "while") == (
      "de_model", "de_attention", "de_attn_core")
  assert scope_parts.chain_of(FWD + "not_de_attention/x_de_attn_qk/add") \
      == ("de_model",)


def test_parts_kernels_and_the_rest_add_up_to_the_layer(cell, capsys):
  red = _reduced(OPS)
  ctx = _ctx(cell, red, _names(OPS, INSIDE))
  printed = capsys.readouterr().out
  read = lambda m: cell.layer_reader(m)(red, ctx)
  for name, ns in WANT.items():
    assert read(name) == pytest.approx(ns * 1e-6), name
  got = ctx["scope_parts"]
  under = lambda *scopes_: got.ms(lambda k: all(s in k[0] for s in scopes_))
  assert under("de_attention") == pytest.approx(ATTENTION * 1e-6)
  # `less_kernels` takes the kernels out and nothing else
  core = under("de_attention", "de_attn_core")
  assert core - read("attn_layout_ms") == pytest.approx(KERNELS * 1e-6)
  parts = read("attn_proj_ms") + read("attn_qk_ms") + core
  assert under("de_attention") - parts == pytest.approx(OWN * 1e-6)
  # the passes of every op add up to the busy time a step
  by_pass = [got.ms(lambda k, p=p: k[1] == p) for p in scope_parts.PASSES]
  assert sum(by_pass) == pytest.approx(BUSY * 1e-6)
  assert sum(by_pass) == pytest.approx(red.busy_s() / red.n_steps() * 1e3)
  assert by_pass[1] == read("remat_forward_ms")
  # a part with no op reads 0.0 (a traced line may leave no metric out)
  for name in ("moe_router_ms", "moe_dispatch_ms", "linattn_proj_ms"):
    assert read(name) == 0.0, name
  # for people: one table a layer, the straddling fusion named under it
  assert printed.count("parts by pass") == 1
  layer = printed[printed.index("  de_attention"):printed.index(
      "  de_moe_route")]
  for row in ("de_attn_proj", "de_attn_qk", "de_attn_core: splash_*",
              "(the layer's own)", "all"):
    assert f"    {row} " in layer, row
  assert f"straddle {100 * 50 / ATTENTION:.2f}%" in layer
  assert "fusion.9 0.000 de_attention/de_attn_proj|" \
      "de_attention/de_attn_qk 60%" in layer
  assert "every op of the step" in printed


def test_a_fusion_goes_where_most_of_its_instructions_lie():
  names = _names(OPS, INSIDE)
  place = scope_parts.place_of(names, "fusion.9")
  assert place.chain == ("de_model", "de_attention", "de_attn_proj")
  assert place.which == "backward"
  # three of its five scoped instructions lie there: it straddles; two lie in qk
  assert place.agree == pytest.approx(3 / 5)
  assert place.beside == ("de_model", "de_attention", "de_attn_qk")
  # scope by scope from the outermost: two parts of one layer outvote a
  # third scope that is the commonest chain
  mixed = scope_reduce.OpNames({}, {"fusion.1": (
      [FWD + PROJ + "a"] * 2 + [FWD + QK + "b"] * 2
      + [FWD + "de_moe/de_moe_route/c"] * 3)})
  assert scope_parts.place_of(mixed, "fusion.1").chain[:2] == (
      "de_model", "de_attention")
  # its own name stack wins where it names a top-level scope
  own = scope_reduce.OpNames({"fusion.1": FWD + QK + "mul"},
                             {"fusion.1": [FWD + PROJ + "dot_general"] * 3})
  place = scope_parts.place_of(own, "fusion.1")
  assert place.chain[-1] == "de_attn_qk" and place.agree == 0.0
  assert scope_parts.place_of(names, "copy.15") is None


def test_an_op_inside_a_while_goes_where_its_holder_went():
  red = _reduced(OPS, n_devices=1)
  got = scope_parts.attribute(red, _names(OPS, INSIDE))
  keys = {op: (chain, which) for chain, which, op in got.per_step[0]}
  assert keys["body.12"] == keys["while.11"] == (
      ("de_model", "de_attention", "de_attn_core"), "backward")
  assert got.per_step[0][keys["while.11"] + ("while.11",)] == [40.0] * 3
  assert keys["copy.15"] == ((), "forward")


def test_a_program_without_the_parts_reads_zero_and_its_passes(cell, capsys):
  """The parent of the PR that added the parts: its stacks hold the layers'
  scopes and ``rematted_computation``. Every part reads 0.0 (a number: the
  harness refuses a traced line that leaves a declared metric out), the
  rebuilt forward reads as it is, and the layer's table is its own row."""
  bare = {op: (s.replace("de_attn_proj/", "").replace("de_attn_qk/", "")
               .replace("de_attn_core/", "").replace("de_moe_sort/", ""), a, d)
          for op, (s, a, d) in OPS.items()}
  red = _reduced(bare)
  ctx = _ctx(cell, red, _names(bare))
  printed = capsys.readouterr().out
  for name in METRICS:
    got = specs.load_cell("laguna_moe_train_1chip").layer_reader(name)(
        red, ctx)
    assert got == pytest.approx(
        WANT["remat_forward_ms"] * 1e-6 if name == "remat_forward_ms"
        else 0.0), name
  layer = printed[printed.index("  de_attention"):printed.index(
      "  de_moe_route")]
  assert "(the layer's own)" in layer and "de_attn" not in layer


def test_a_trace_on_disk_without_the_names_reads_zero(tmp_path, capsys):
  """Through ``parts()``: the ``.xplane.pb`` of the cell, found and opened
  once as a traced run leaves it; a program with none of the names."""
  root = bench_toy.make_root(str(tmp_path))
  cell = specs.load_cell(CELL, root)
  path = on_disk._write_trace(root, cell.name, on_disk._xspace())
  red = trace_reduce.Reduced(trace_reduce.load_xplane(path), STEP)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite"}
  read = [m["name"] for m in cell.per_layer if m["name"] in METRICS]
  assert len(read) == 8
  for name in read:
    assert cell.layer_reader(name)(red, ctx) == 0.0, name
  printed = capsys.readouterr().out
  assert printed.count("parts by pass") == 1      # one reduction, one table
  got = ctx["scope_parts"]
  by_pass = [got.ms(lambda k, p=p: k[1] == p) for p in scope_parts.PASSES]
  assert by_pass == pytest.approx([500e-6, 0.0, 500e-6])   # that trace's


def test_a_pass_the_reader_does_not_know_is_an_error_in_words(tmp_path):
  spec = {"name": "x_ms", "scopes": [], "pass": "sideways"}
  with open(tmp_path / "x_ms.json", "w") as f:
    json.dump(spec, f)
  with pytest.raises(ValueError, match="sideways"):
    scope_parts.reader(str(tmp_path / "x_ms.py"))


def test_the_ten_metric_files_name_the_programs_scopes_and_their_entries():
  from distributed_embeddings_tpu.telemetry import scopes
  vocabulary = set(scopes.TOP_LEVEL + scopes.CHILDREN + scopes.LM_CHILDREN
                   + scopes.PARTS)
  with open(os.path.join(bench_toy.ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  declared = {m["name"]: m for m in bench["per_layer"]}
  assert [m["name"] for m in bench["per_layer"]][-10:] == list(METRICS)
  layers = {m["layer"] for m in bench["per_layer"][:-10]}
  cells = {w["name"] for w in bench["workloads"]}
  files = glob.glob(os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                                 "*.json"))
  mine = {}
  for path in files:
    with open(path) as f:
      spec = json.load(f)
    if "scopes" in spec:
      mine[spec["name"]] = spec
  assert sorted(mine) == sorted(METRICS)
  named = set()
  for name, spec in mine.items():
    entry = declared[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"]) \
        == (entry["layer"], "ms", "train_samples_per_s")
    assert entry["layer"] in layers       # a layer the benchmark had
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    assert set(entry["workloads"]) <= cells
    assert set(spec) - {"less_kernels", "pass"} == {
        "name", "layer", "unit", "moves", "what", "reader", "scopes"}
    assert set(spec["scopes"]) <= vocabulary, name
    named |= set(spec["scopes"])
    assert spec.get("pass") in (None,) + scope_parts.PASSES
    module = specs.load_module(
        os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                     f"{name}.py"), "metric_under_test")
    assert callable(module.read)
  # every part is read by a metric
  assert set(scopes.PARTS) <= named
