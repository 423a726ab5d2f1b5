"""The bytes the apply kernel's roofline counts, on hand-computed shapes,
and the table of peaks."""

import pytest

import bench_toy  # noqa: F401
from benchmark import roofline


def test_apply_bytes_hand_computed():
  # one class on one chip: 1,000 delta rows of 512 B onto 400 distinct rows:
  # 400 rows read + 400 written + 1,000 delta rows read = 1,800 * 512
  shapes = {"ranks": 1, "apply_classes": [
      {"occurrences": 1000, "unique_rows": 400, "row_bytes": 512}]}
  assert roofline.apply_rows_hbm_bytes(shapes) == 1800 * 512


def test_apply_bytes_is_the_mean_over_chips():
  shapes = {"ranks": 4, "apply_classes": [
      {"occurrences": 100, "unique_rows": 100, "row_bytes": 512},
      {"occurrences": 300, "unique_rows": 50, "row_bytes": 512}]}
  assert roofline.apply_rows_hbm_bytes(shapes) == \
      (300 * 512 + 400 * 512) / 4


def test_no_kernel_class_gives_no_number():
  assert roofline.apply_rows_hbm_bytes({"ranks": 1, "apply_classes": []}) \
      is None
  assert roofline.least_ms("apply_rows_hbm_bytes", {
      "device_kind": "TPU v5 lite",
      "shapes": {"ranks": 1, "apply_classes": []}}) is None


def test_least_ms_uses_the_devices_hbm_peak():
  ctx = {"device_kind": "TPU v5 lite", "shapes": {"ranks": 1, "apply_classes": [
      {"occurrences": 0, "unique_rows": 819e9 / 2 / 512 / 1e3,
       "row_bytes": 512}]}}
  assert roofline.least_ms("apply_rows_hbm_bytes", ctx) == pytest.approx(1.0)


def test_unknown_device_is_an_error_not_a_default():
  with pytest.raises(KeyError, match="no peaks"):
    roofline.peaks("cpu")


# two lines of the one-chip DLRM step as the v5e's compiler printed it (PR 25),
# cut to what `program.kernel_calls` reads, with the buffers' shapes left open
_HLO = """
  %de_interact_parts_fwd.1 = f32[65536,351]{1,0:T(8,128)} custom-call(%a, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[27,27,351]{2,1,0}, f32[65536,128]{1,0}}, metadata={op_name="jit(step_fn)/jit(local_step)/de_interact_parts_fwd/pallas_call" stack_frame_id=90},
  %fusion.4 = f32[65536,128]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.4
  %de_apply_rows_cached.5 = f32[ROWS,LANES]{1,0:T(8,128)} custom-call(%copy-done.43, %carry__fused__.1, %custom-call.21, %constant.358), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[65536]{0}, f32[ROWS,LANES]{1,0}, f32[65536,LANES]{1,0}, f32[1]{0}}, custom_call_has_side_effect=true, output_to_operand_aliasing={{}: (1, {})}, metadata={op_name="jit(step_fn)/jit(local_step)/de_apply_rows_cached/pallas_call" stack_frame_id=94},
"""


def _hlo(rows, lanes):
  return _HLO.replace("ROWS", str(rows)).replace("LANES", str(lanes))


def test_kernel_calls_reads_names_and_operand_shapes():
  from benchmark import program
  calls = program.kernel_calls(_hlo(1602580, 128))
  assert calls == [
      ("de_interact_parts_fwd", [(27, 27, 351), (65536, 128)]),
      ("de_apply_rows_cached",
       [(65536,), (1602580, 128), (65536, 128), (1,)])]
  assert program.mosaic_kernels(_hlo(8, 128)) == [
      "de_apply_rows_cached", "de_interact_parts_fwd"]


@pytest.fixture(scope="module")
def toy_program(tmp_path_factory):
  from benchmark import program, specs, traffic
  root = bench_toy.make_root(str(tmp_path_factory.mktemp("toy_root")))
  cell = specs.load_cell(bench_toy.CELLS["dlrm"], root)
  family = cell.family()
  spec = family.model_spec(cell.config)
  pool = traffic.make_pool(cell.traffic, spec.inputs, spec.n_numerical, 3)
  parts = family.build_parts(cell.config, 1,
                             int(cell.traffic["global_batch"]))
  return program.Program(parts, spec, 3, None), pool


def test_apply_shapes_counts_the_classes_the_compiled_step_serves(
    toy_program):
  """Which classes the kernel serves is read from the compiled step: the
  class whose buffer a call names is counted at the call's own stream
  length, the others are not."""
  prog, pool = toy_program
  name, lay = sorted(prog.layouts.items(),
                     key=lambda kv: -kv[1].phys_rows)[0]
  shapes = prog.apply_shapes(pool, _hlo(lay.phys_rows, lay.phys_width))
  assert shapes["ranks"] == 1
  (only,) = shapes["apply_classes"]
  # every occurrence of the toy batch that reads one of the class's tables;
  # the call's padded stream (65536) only has to hold them
  tables = [t for _, _, t in prog.class_spans[name][0]]
  assert only["class"] == name
  assert only["occurrences"] == len(tables) * pool[0].cats.shape[0] > 0
  assert only["row_bytes"] == lay.phys_width * 4
  assert 0 < only["unique_rows"] <= lay.phys_rows
  # a step with no such call (the zoo's): nothing to count, no number
  assert prog.apply_shapes(pool, "")["apply_classes"] == []


def test_apply_shapes_refuses_a_call_it_cannot_place(toy_program):
  prog, pool = toy_program
  with pytest.raises(ValueError, match="cannot be counted"):
    prog.apply_shapes(pool, _hlo(12345, 128))
  name, lay = sorted(prog.layouts.items(),
                     key=lambda kv: -kv[1].phys_rows)[0]
  short = _hlo(lay.phys_rows, lay.phys_width).replace("65536", "8")
  with pytest.raises(ValueError, match="delta stream holds 8"):
    prog.apply_shapes(pool, short)
