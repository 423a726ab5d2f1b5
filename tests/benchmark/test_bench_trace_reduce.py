"""The reducer from a trace to per-layer metrics, on a small hand-built
trace (`data/trace_fixture.json`): two devices (the second shifted by 7 ns),
a step program run twice beside another program, ops that overlap, a
collective, idle gaps under different host spans."""

import json
import os

import pytest

import bench_toy
from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "trace_fixture.json")


@pytest.fixture(scope="module")
def red():
  with open(FIXTURE) as f:
    return trace_reduce.Reduced(json.load(f), r"^jit_step_fn\(")


def test_names_and_opcodes():
  name = ("%all_to_all.5 = s32[4,1,16]{2,1,0:T(1,128)S(1)} "
          "all-to-all(s32[4,1,16]{2,1,0} %x)")
  assert trace_reduce.op_name(name) == "all_to_all.5"
  assert trace_reduce.opcode(name) == "all-to-all"
  assert trace_reduce.is_collective(name)
  tup = "%k.3 = (f32[64,128]{1,0:T(8,128)}, f32[8]{0}) custom-call(f32[8] %c)"
  assert trace_reduce.opcode(tup) == "custom-call"
  assert not trace_reduce.is_collective(tup)
  assert trace_reduce.opcode("bench_feed") == ""


def test_window_and_busy(red):
  # window: first step's start to last step's end = 100 .. 2200
  assert red.window_s() == pytest.approx(2100e-9)
  # per step: [s, s+500) u [s+500, s+600) u [s+700, s+900) = 800 busy of 1000
  assert red.busy_s() == pytest.approx(1600e-9)
  assert 0 < red.busy_s() <= red.window_s()
  assert red.n_steps() == 2
  assert red.idle_pct() == pytest.approx(100 * (1 - 1600 / 2100))


def test_reductions(red):
  assert red.module_ms() == pytest.approx(1000e-6)
  apply_ms = red.per_step_ms(
      lambda n: trace_reduce.op_name(n).startswith("de_apply_rows_cached"))
  assert apply_ms == pytest.approx(500e-6)
  assert red.per_step_ms(trace_reduce.is_collective) == pytest.approx(100e-6)
  assert red.per_step_ms(lambda n: "no_such_op" in n) is None
  # the main thread's spans only: the same name on another line is not read
  assert red.span_ms("bench_feed") == pytest.approx(90e-6)
  assert red.span_ms("bench_nothing") is None


def test_breakdown(red):
  ops = dict(red.top_ops())
  # self time: the 100 ns in which fusion.1 and the kernel that starts
  # before it ends both run go to the kernel, as scope_reduce charges them,
  # so the rows add up to the busy time
  assert ops["fusion.1"] == pytest.approx(400e-9)
  assert ops["de_apply_rows_cached.2"] == pytest.approx(600e-9)
  assert sum(ops.values()) == pytest.approx(red.busy_s())
  assert "copy.9" not in ops  # ran outside the steps' window
  gaps = dict(red.idle_gaps())
  # device 0 is busy 100-700, 800-1000, 1200-1800, 1900-2100 of the window
  # 100-2200. It idles 700-800 (middle 750: bench_wait ended at 730, no
  # span), 1000-1200 (middle 1100: the second bench_feed, 1000-1150),
  # 1800-1900 and 2100-2200 (middles inside bench_wait spans)
  assert gaps == {
      "host: bench_wait": pytest.approx(200e-9),
      "host: bench_feed": pytest.approx(200e-9),
      f"host: {trace_reduce.OUTSIDE}": pytest.approx(100e-9)}
  assert sum(gaps.values()) == pytest.approx(
      red.window_s() - sum(b - a for a, b in red.busy[0]) * 1e-9)


def test_top_ops_counts_a_while_once():
  """A ``while`` holds its body's ops on the same line (the four-chip
  cell's ``while.51`` and the ``fusion.295`` inside it): the breakdown
  gives each its own time, and ``per_step_ms`` is not touched."""
  op = lambda name, code: f"%{name} = f32[8]{{0}} {code}(f32[8]{{0}} %p)"
  ops = [[op("while.51", "while"), 100, 600],
         [op("fusion.295", "fusion"), 120, 200],   # two trips of the body
         [op("fusion.295", "fusion"), 400, 250],
         [op("copy.1", "copy"), 350, 20],
         [op("fusion.7", "fusion"), 750, 100]]     # after the loop
  trace = {"planes": [{"name": "/device:TPU:0", "lines": [
      {"name": "XLA Modules", "events": [["jit_step_fn(1)", 100, 800]]},
      {"name": "XLA Ops", "events": ops}]}]}
  red = trace_reduce.Reduced(trace, r"^jit_step_fn\(")
  got = dict(red.top_ops())
  assert got == {"fusion.295": pytest.approx(450e-9),
                 "while.51": pytest.approx(130e-9),  # 600 - 200 - 250 - 20
                 "fusion.7": pytest.approx(100e-9),
                 "copy.1": pytest.approx(20e-9)}
  assert sum(got.values()) == pytest.approx(red.busy_s())
  assert [k for k, _ in red.top_ops(2)] == ["fusion.295", "while.51"]
  # the declared metrics' reduction still sums what it is asked for
  assert red.per_step_ms(lambda n: "while" in n) == pytest.approx(600e-6)


@pytest.mark.parametrize("metric,want", [
    ("host_feed_ms", 90e-6), ("step_device_ms", 1000e-6),
    ("exchange_ms", 100e-6), ("apply_kernel_ms", 500e-6),
    ("device_idle_pct", 100 * (1 - 1600 / 2100)),
    # 512 B * (2 * 10 + 30) = 25,600 B at 819 GB/s = 3.1258e-5 ms of 5e-4 ms
    ("apply_roofline", 100 * (25600 / 819e9 * 1e3) / 500e-6),
])
def test_the_committed_metric_files_read_the_fixture(red, metric, want):
  spec_path = os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")
  with open(spec_path) as f:
    reader = trace_reduce.reader_from_spec(json.load(f))
  ctx = {"device_kind": "TPU v5 lite", "shapes": {"ranks": 1, "apply_classes": [
      {"occurrences": 30, "unique_rows": 10, "row_bytes": 512}]}}
  assert reader(red, ctx) == pytest.approx(want)


def test_a_trace_without_the_step_program_is_an_error():
  with open(FIXTURE) as f:
    trace = json.load(f)
  with pytest.raises(ValueError, match="no module matches"):
    trace_reduce.Reduced(trace, r"^jit_absent\(")


def test_load_xplane_finds_the_benchmarks_spans(tmp_path):
  """The adapter from the profiler's file, on a trace taken here: the CPU
  has no device plane, so only the host side can be read."""
  import glob
  import jax
  import jax.numpy as jnp
  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
  for _ in range(2):
    with jax.profiler.TraceAnnotation("bench_feed"):
      jax.device_put(jnp.ones((8,))).block_until_ready()
  jax.profiler.stop_trace()
  (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
  trace = trace_reduce.load_xplane(path)
  (host,) = [p for p in trace["planes"] if p["name"] == "/host:CPU"]
  names = [e[0] for line in host["lines"] for e in line["events"]]
  assert names == ["bench_feed", "bench_feed"]
  # whatever the interpreter was started as (`python`, `python3`), its
  # main thread's line is the one without a /<tid> suffix
  assert [trace_reduce.is_main_thread(line["name"])
          for line in host["lines"]] == [True]
  with pytest.raises(ValueError, match="no /device:TPU"):
    trace_reduce.Reduced(trace, "x")
