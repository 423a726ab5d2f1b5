"""From scopes to per-layer device time (`benchmark/scope_reduce.py`): the
wire-format decoder on a hand-written ``.xplane.pb``, cross-checked against
``jax.profiler.ProfileData``; the attribution on hand-built traces; and the
``layer_metrics/*.py`` readers, on a program with scopes and on one without.
"""

import glob
import json
import os

import jax
import pytest

import bench_toy
from benchmark import scope_reduce, specs, trace_reduce

STEP = r"^jit_step_fn\("
STEP_ID, OTHER_ID = 111, 222

# ---- a hand-written trace ----------------------------------------------------
# Per step (1000 ns): fusion.1 200 | while.1 500 {fusion.2 100, fusion.2 100}
# | fusion.3 200 | copy.1 100. Another program runs between the steps and has
# an op of the same name, fusion.1, under another scope.
OPS = [  # metadata id, name, display name, program, tf_op as (kind, text)
    (1, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", "fusion.1",
     STEP_ID, ("str", "jit(step_fn)/jit(local_step)/de_gather/gather:Gather")),
    (2, "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
     "while.1", STEP_ID,
     ("ref", "jit(step_fn)/transpose(jvp(de_combine))/de_onehot/while:")),
    (3, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %y), kind=kOutput", "fusion.2",
     STEP_ID,
     ("str", "jit(step_fn)/transpose(jvp(de_combine))/de_onehot/dot_general:")),
    (4, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", "fusion.3",
     STEP_ID, None),   # no name of its own: the HloProto names its inside
    (5, "%copy.1 = f32[8]{0} copy(f32[8]{0} %z)", "copy.1", STEP_ID, None),
    (6, "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %q), kind=kLoop", "fusion.1",
     OTHER_ID, ("str", "jit(other)/de_model/tanh:")),
]
STEP_EVENTS = [(1, 0, 200), (2, 200, 500), (3, 250, 100), (3, 400, 100),
               (4, 700, 200), (5, 900, 100)]  # metadata id, offset, ns
STEP_STARTS = (1000, 3000)
HLO_TEXT = """HloModule jit_step_fn

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %a = f32[8]{0} negate(%p), metadata={op_name="jit(step_fn)/de_apply/de_apply/neg"}
  %b = f32[8]{0} sine(%a), metadata={op_name="jit(step_fn)/de_apply/sin"}
  ROOT %c = f32[8]{0} cosine(%b), metadata={op_name="jit(step_fn)/de_gather/cos"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
}
"""


def _quoted(s):
  return json.dumps(s)  # a text-proto string literal


def _device_plane(ops, with_names=True):
  stat_ids = {"program_id": 1, "tf_op": 2}
  refs = [t[1] for *_, t in ops if t and t[0] == "ref"]
  stat_meta = [(i, n) for n, i in stat_ids.items()] + [
      (10 + j, text) for j, text in enumerate(refs)]
  out = ['planes { id: 1 name: "/device:TPU:0"']
  for mid, name, display, program, tf_op in ops:
    stats = [f"stats {{ metadata_id: 1 uint64_value: {program} }}"]
    if tf_op and with_names:
      kind, text = tf_op
      value = (f"str_value: {_quoted(text)}" if kind == "str"
               else f"ref_value: {10 + refs.index(text)}")
      stats.append(f"stats {{ metadata_id: 2 {value} }}")
    out.append(f"event_metadata {{ key: {mid} value {{ id: {mid} name: "
               f"{_quoted(name)} display_name: {_quoted(display)} "
               f"{' '.join(stats)} }} }}")
  out.append('event_metadata { key: 20 value { id: 20 name: '
             f'"jit_step_fn({STEP_ID})" }} }}')
  out.append('event_metadata { key: 21 value { id: 21 name: '
             f'"jit_other({OTHER_ID})" }} }}')
  for sid, name in stat_meta:
    out.append(f"stat_metadata {{ key: {sid} value {{ id: {sid} name: "
               f"{_quoted(name)} }} }}")
  modules = [f"events {{ metadata_id: 20 offset_ps: {s * 1000} "
             f"duration_ps: 1000000 }}" for s in STEP_STARTS]
  modules.append("events { metadata_id: 21 offset_ps: 2200000 "
                 "duration_ps: 100000 }")
  out.append('lines { id: 1 name: "XLA Modules" timestamp_ns: 0 '
             + " ".join(modules) + " }")
  events = [f"events {{ metadata_id: {mid} offset_ps: {(s + off) * 1000} "
            f"duration_ps: {dur * 1000} }}"
            for s in STEP_STARTS for mid, off, dur in STEP_EVENTS]
  events.append("events { metadata_id: 6 offset_ps: 2200000 "
                "duration_ps: 100000 }")
  out.append('lines { id: 2 name: "XLA Ops" timestamp_ns: 0 '
             + " ".join(events) + " }")
  out.append("}")
  return "\n".join(out)


def _varint(n):
  out = bytearray()
  while True:
    out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
    n >>= 7
    if not n:
      return bytes(out)


def _field(number, payload):
  """One field of wire type 2 (bytes), or 0 for an int payload."""
  if isinstance(payload, int):
    return _varint(number << 3) + _varint(payload)
  return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _metadata_plane(hlo_text):
  """``/host:metadata`` with the step's ``HloProto``, encoded by hand: the
  text-proto route cannot carry raw bytes comfortably."""
  from jax._src.lib import xla_client
  module = xla_client._xla.hlo_module_from_text(hlo_text)
  proto = _field(1, module.as_serialized_hlo_module_proto())  # HloProto
  stat = _field(1, 1) + _field(6, proto)                      # XStat
  meta = (_field(1, STEP_ID) + _field(2, f"jit_step_fn({STEP_ID})".encode())
          + _field(5, stat))                                  # XEventMetadata
  plane = (_field(1, 9) + _field(2, b"/host:metadata")
           + _field(4, _field(1, STEP_ID) + _field(2, meta))
           + _field(5, _field(1, 1) + _field(
               2, _field(1, 1) + _field(2, b"Hlo Proto"))))
  return _field(1, plane)                                     # XSpace.planes


def _xspace(ops=OPS, with_names=True, hlo_text=HLO_TEXT) -> bytes:
  data = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
      _device_plane(ops, with_names))
  return data + (_metadata_plane(hlo_text) if hlo_text else b"")


def _write_trace(root, cell_name, data: bytes) -> str:
  d = os.path.join(root, ".bench_trace", cell_name, "plugins", "profile", "t")
  os.makedirs(d, exist_ok=True)
  path = os.path.join(d, "host.xplane.pb")
  with open(path, "wb") as f:
    f.write(data)
  return path


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
  return _write_trace(str(tmp_path_factory.mktemp("trace")), "cell", _xspace())


# ---- the decoder -------------------------------------------------------------
def test_decoder_agrees_with_profile_data_on_names_and_times():
  data = _xspace()
  planes = scope_reduce.read_planes(data)
  theirs = jax.profiler.ProfileData.from_serialized_xspace(data)
  assert [p["name"] for p in planes] == [p.name for p in theirs.planes] \
      == ["/device:TPU:0", "/host:metadata"]
  device = next(iter(theirs.planes))
  assert planes[0]["lines"] == [ln.name for ln in device.lines] \
      == ["XLA Modules", "XLA Ops"]
  by_name = {m["name"]: m for m in planes[0]["events"].values()}
  ops_line = [ln for ln in device.lines if ln.name == "XLA Ops"][0]
  events = list(ops_line.events)
  assert {e.name for e in events} <= set(by_name)
  # the fixture's times, as the library reads them
  first = [(e.name, e.start_ns, e.duration_ns) for e in events[:3]]
  assert first == [(OPS[0][1], 1000.0, 200.0), (OPS[1][1], 1200.0, 500.0),
                   (OPS[2][1], 1250.0, 100.0)]
  # what ProfileData does not yield: the stats of the event's metadata
  assert all("tf_op" not in dict(e.stats) for e in events)
  assert by_name[OPS[0][1]]["stats"]["tf_op"] == OPS[0][4][1]
  assert by_name[OPS[0][1]]["stats"]["program_id"] == STEP_ID
  assert by_name[OPS[0][1]]["display_name"] == "fusion.1"


@pytest.mark.parametrize("case", ["str_value", "ref_value", "hlo_proto",
                                  "two_programs_share_a_name"])
def test_where_an_ops_name_stack_comes_from(trace_path, case):
  names = scope_reduce.read_op_names(trace_path, f"jit_step_fn({STEP_ID})")
  if case == "str_value":
    assert names.name_stack("fusion.2") == OPS[2][4][1][:-1]
    assert scope_reduce.layer_of(names.name_stack("fusion.2")) == (
        "de_combine", True)
  elif case == "ref_value":
    assert names.own["while.1"] == OPS[1][4][1][:-1]
  elif case == "hlo_proto":
    # no name of its own: two of its three instructions lie under de_apply
    assert "fusion.3" not in names.own
    assert scope_reduce.layer_of(names.name_stack("fusion.3")) == (
        "de_apply", False)
    assert names.name_stack("copy.1") == ""
  else:
    # the op type after the last colon is dropped; the other program's
    # fusion.1 (de_model) does not replace the step's (de_gather)
    assert names.own["fusion.1"] == \
        "jit(step_fn)/jit(local_step)/de_gather/gather"


def test_the_hlo_proto_alone_is_enough(tmp_path):
  text = HLO_TEXT.replace(
      "fusion(%x), kind=kLoop, calls=%fused_computation",
      "fusion(%x), kind=kLoop, calls=%fused_computation, "
      'metadata={op_name="jit(step_fn)/de_route/iota"}')
  path = _write_trace(str(tmp_path), "cell",
                      _xspace(with_names=False, hlo_text=text))
  names = scope_reduce.read_op_names(path, f"jit_step_fn({STEP_ID})")
  assert scope_reduce.layer_of(names.name_stack("fusion.3")) == (
      "de_route", False)
  assert names.name_stack("fusion.1") == ""


def test_a_trace_with_neither_source_is_an_error_in_words(tmp_path):
  path = _write_trace(str(tmp_path), "cell",
                      _xspace(with_names=False, hlo_text=None))
  with pytest.raises(scope_reduce.NoNameStacks, match="tf_op.*Hlo Proto"):
    scope_reduce.read_op_names(path, f"jit_step_fn({STEP_ID})")


def test_decoder_refuses_what_is_not_a_protobuf():
  with pytest.raises((ValueError, IndexError)):
    list(scope_reduce.fields(b"\x0a\x7fshort"))


# ---- name stacks -------------------------------------------------------------
@pytest.mark.parametrize("stack,layer,chain", [
    ("jit(step_fn)/jit(local_step)/de_apply/de_apply/scatter-add",
     ("de_apply", False), ("de_apply", "de_apply")),
    ("jit(step_fn)/jit(local_step)/transpose(jvp(de_combine))/de_onehot/dot",
     ("de_combine", True), ("de_combine", "de_onehot")),
    ("jit(step_fn)/jvp(de_model)/bottom_mlp/dense_0/dot_general",
     ("de_model", False), ("de_model",)),
    # the outermost wins: the exact apply gathers rows inside de_apply
    ("jit(step_fn)/de_apply/de_gather/gather",
     ("de_apply", False), ("de_apply", "de_gather")),
    # whole components only, and the first of ';'-joined names
    ("jit(step_fn)/my_de_apply_thing/add;jit(step_fn)/de_apply/add",
     (None, False), ()),
    ("jit(step_fn)/jit(local_step)/add", (None, False), ()),
    ("", (None, False), ()),
])
def test_layer_of_a_name_stack(stack, layer, chain):
  assert scope_reduce.layer_of(stack) == layer
  assert scope_reduce.scope_chain(stack) == chain


# ---- attribution -------------------------------------------------------------
def _reduced(events_per_step, n_devices=1, starts=(0, 1000)):
  """A ``trace_reduce.Reduced`` of steps of 1000 ns; ``events_per_step``:
  (op, offset, ns). Device d is shifted by 7 d ns."""
  planes = []
  for d in range(n_devices):
    mods = [[f"jit_step_fn({STEP_ID})", s + 7 * d, 1000] for s in starts]
    ops = [[f"%{op} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", s + 7 * d + off, ns]
           for s in starts for op, off, ns in events_per_step]
    planes.append({"name": f"/device:TPU:{d}", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": ops}]})
  return trace_reduce.Reduced({"planes": planes}, STEP)


NESTED = [("gather.1", 0, 200), ("while.1", 200, 500), ("body.1", 250, 100),
          ("body.1", 400, 100), ("anon.1", 520, 50), ("apply.1", 700, 200),
          ("copy.1", 900, 50)]
NAMES = scope_reduce.OpNames(own={
    "gather.1": "jit(step_fn)/de_gather/de_gather/gather",
    "while.1": "jit(step_fn)/jvp(de_combine)/de_onehot/while",
    "body.1": "jit(step_fn)/transpose(jvp(de_combine))/de_onehot/dot_general",
    "apply.1": "jit(step_fn)/de_apply/de_gather/gather",
}, inside={})


def test_a_while_is_charged_its_self_time_and_the_scopes_add_up_to_busy():
  red = _reduced(NESTED, n_devices=2)
  got = scope_reduce.attribute(red, NAMES)
  assert got.scope_ms("de_gather") == pytest.approx(200e-6)
  # forward: the while less its body (500 - 250) and anon.1 (50), which the
  # compiler left without a name and which runs inside the while: an op with
  # no scope of its own is its holder's. Backward: the body's named ops
  assert got.scope_ms("de_combine", backward=False) == pytest.approx(300e-6)
  assert got.op_ms["de_combine"]["anon.1"] == pytest.approx(50e-6)
  assert got.op_ms["de_combine"]["while.1"] == pytest.approx(250e-6)
  assert got.scope_ms("de_combine", backward=True) == pytest.approx(200e-6)
  assert got.scope_ms("de_combine") == pytest.approx(500e-6)
  # a child scope counts where it is, and its parent still owns the time
  assert got.child_ms("de_onehot") == pytest.approx(500e-6)
  # the outermost scope wins: a gather inside the apply is the apply's
  assert got.scope_ms("de_apply") == pytest.approx(200e-6)
  assert got.scope_ms("de_model", "de_loss") == 0.0   # absent: 0.0, not None
  assert got.child_ms("de_interact") == 0.0
  assert got.unscoped_pct() == pytest.approx(100 * 50 / 950)
  parts = sum(got.scope_ms(s) for s in scope_reduce.TOP_LEVEL) \
      + got.unscoped_pct() / 100 * 950e-6
  assert parts == pytest.approx(red.busy_s() / red.n_steps() * 1e3)
  # the sum of durations counts the body twice; the self times do not
  assert red.per_step_ms(lambda n: True) == pytest.approx(1200e-6)
  table = got.table()
  assert "de_apply" in table and "unscoped_pct" in table
  assert [ln for ln in table.splitlines() if "gather.1" in ln][0] \
      .lstrip().startswith("de_gather")


def test_self_time_of_nested_and_adjacent_events():
  ops = [("a", 0.0, 100.0, 0), ("b", 10.0, 20.0, 0), ("c", 30.0, 70.0, 0),
         ("d", 40.0, 10.0, 0), ("e", 100.0, 5.0, 0)]
  self_ns, parent, order = scope_reduce.nesting(ops)
  assert self_ns == [10.0, 20.0, 60.0, 10.0, 5.0]
  assert parent == [-1, 0, 0, 2, -1] and order == [0, 1, 2, 3, 4]


def test_a_program_without_scopes_reads_all_unscoped_and_does_not_raise():
  red = _reduced(NESTED)
  bare = scope_reduce.OpNames(own={
      "gather.1": "jit(step_fn)/jit(local_step)/gather",
      "body.1": "jit(step_fn)/transpose(jvp(mlp))/dense_0/dot_general"},
      inside={})
  got = scope_reduce.attribute(red, bare)
  assert got.unscoped_pct() == pytest.approx(100.0)
  assert all(got.scope_ms(s) == 0.0 for s in scope_reduce.TOP_LEVEL)
  assert all(got.child_ms(c) == 0.0 for c in scope_reduce.CHILDREN)
  assert "(no scope)" in got.table()


# ---- the readers, as the harness finds them ----------------------------------
NEW_METRICS = ("route_ms", "gather_ms", "combine_ms", "onehot_ms",
               "dense_model_ms", "interact_ms", "dense_update_ms",
               "sparse_apply_ms", "unscoped_pct")


def _read_all(cell, path, capsys):
  red = trace_reduce.Reduced(trace_reduce.load_xplane(path), STEP)
  ctx = {"cell": cell, "device_kind": "TPU v5 lite", "shapes": {}}
  values = {}
  for m in cell.per_layer:
    if m["name"] in NEW_METRICS:
      values[m["name"]] = cell.layer_reader(m["name"])(red, ctx)
  return values, capsys.readouterr().out


@pytest.mark.parametrize("with_names", [True, False],
                         ids=["program-with-scopes", "program-without"])
def test_every_new_metric_reads_a_number_from_the_trace_on_disk(
    tmp_path, capsys, with_names):
  cell = specs.load_cell("dlrm_train_1chip",
                         bench_toy.make_root(str(tmp_path)))
  if with_names:
    data = _xspace()
  else:  # as the parent commit's step: names, but no scope of the registry
    ops = [(i, n, d, p, t and ("str", "jit(step_fn)/jit(local_step)/add:"))
           for i, n, d, p, t in OPS]
    data = _xspace(ops, hlo_text=None)
  path = _write_trace(str(tmp_path), cell.name, data)
  values, printed = _read_all(cell, path, capsys)
  assert set(values) == set(NEW_METRICS)
  assert all(isinstance(v, float) for v in values.values())
  assert printed.count("scopes (self time") == 1   # one reduction, one table
  assert printed.count("longest ops -> scope:") == 1
  if with_names:
    assert values["gather_ms"] == pytest.approx(200e-6)
    assert values["combine_ms"] == values["onehot_ms"] == pytest.approx(500e-6)
    assert values["sparse_apply_ms"] == pytest.approx(200e-6)
    assert values["unscoped_pct"] == pytest.approx(10.0)
    assert values["route_ms"] == values["interact_ms"] == 0.0
    assert "fusion.1->de_gather" in printed and "copy.1->(no scope)" in printed
    assert "while.1->de_combine" in printed and "fusion.3->de_apply" in printed
  else:
    assert values["unscoped_pct"] == pytest.approx(100.0)
    assert all(v == 0.0 for k, v in values.items() if k != "unscoped_pct")


def test_every_scope_a_reader_names_is_in_the_programs_vocabulary():
  from distributed_embeddings_tpu.telemetry import scopes
  assert scope_reduce.TOP_LEVEL == scopes.TOP_LEVEL
  assert scope_reduce.CHILDREN == scopes.CHILDREN
  files = glob.glob(os.path.join(bench_toy.ROOT, "benchmark", "layer_metrics",
                                 "*.py"))
  assert sorted(os.path.basename(f)[:-3] for f in files) == sorted(NEW_METRICS)
  named = set()
  for f in files:
    module = specs.load_module(f, "metric_under_test")
    assert callable(module.read)
    named |= set(module.SCOPES)
  assert named <= set(scopes.TOP_LEVEL + scopes.CHILDREN)
  # every top-level scope is read by some metric: nothing of a step is lost
  assert set(scopes.TOP_LEVEL) <= named


def test_benchmark_json_declares_the_new_metrics_with_their_cells():
  with open(os.path.join(bench_toy.ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  declared = {m["name"]: m for m in bench["per_layer"]}
  cells = [w["name"] for w in bench["workloads"]]
  for name in NEW_METRICS:
    m = declared[name]
    assert m["source"] == "program_span" and m["better"] == "lower"
    assert m["moves"] == "train_samples_per_s"
    assert m["unit"] == ("%" if name == "unscoped_pct" else "ms")
    want = [c for c in cells if "dlrm" in c] if name == "interact_ms" \
        else cells
    assert m["workloads"] == want
  assert [m["name"] for m in bench["per_layer"]][-9:] == list(NEW_METRICS)
