"""The result line's validator: what the driver reads, checked before it is
printed (PR 22 was refused over a traced line without window_s/busy_s)."""

import copy
import json

import pytest

import bench_toy  # noqa: F401  (puts the repo root on sys.path)
from benchmark import result_line

E2E = [{"name": "train_samples_per_s", "unit": "samples/s"},
       {"name": "setup_s", "unit": "s"}]
LAYER = [{"name": "step_device_ms", "unit": "ms"},
         {"name": "apply_roofline", "unit": "%"}]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 12330631680}
UNTRACED = {
    "correct": True, "attempted": 400, "failed": 0,
    "metrics": {"train_samples_per_s": {"value": 1.4e6, "unit": "samples/s"},
                "setup_s": {"value": 15.3, "unit": "s"}},
    "device": DEVICE}
TRACED = {
    "correct": True, "attempted": 87, "failed": 0,
    "metrics": {"step_device_ms": {"value": 46.5, "unit": "ms"},
                "apply_roofline": {"value": 3.1, "unit": "%"}},
    "device": dict(DEVICE, window_s=4.05, busy_s=4.04),
    "breakdown": {"device_ops": [["fusion.1", 0.5]],
                  "idle_gaps": [["host: bench_wait", 0.001]]}}


def _edited(line, path, value=None, drop=False):
  line = copy.deepcopy(line)
  node = line
  for key in path[:-1]:
    node = node[key]
  if drop:
    del node[path[-1]]
  else:
    node[path[-1]] = value
  return line


@pytest.mark.parametrize("line,declared,traced", [
    (UNTRACED, E2E, False),
    (TRACED, LAYER, True),
    (_edited(TRACED, ["breakdown"], drop=True), LAYER, True),
])
def test_accepts_the_forms_the_driver_reads(line, declared, traced):
  result_line.validate(line, declared, traced)


@pytest.mark.parametrize("line,declared,traced,why", [
    (_edited(TRACED, ["device", "window_s"], drop=True), LAYER, True,
     "device keys"),
    (_edited(TRACED, ["device", "busy_s"], drop=True), LAYER, True,
     "device keys"),
    (_edited(TRACED, ["device", "busy_s"], 4.06), LAYER, True, "busy_s"),
    (_edited(TRACED, ["device", "busy_s"], 0.0), LAYER, True, "busy_s"),
    (_edited(UNTRACED, ["metrics", "extra"], {"value": 1.0, "unit": "s"}),
     E2E, False, "undeclared"),
    (_edited(UNTRACED, ["metrics", "setup_s"], drop=True), E2E, False,
     "missing"),
    (_edited(TRACED, ["metrics", "step_device_ms"], drop=True), LAYER, True,
     "missing"),
    (_edited(UNTRACED, ["metrics", "setup_s", "unit"], "ms"), E2E, False,
     "unit"),
    (_edited(UNTRACED, ["metrics", "setup_s", "value"], float("nan")), E2E,
     False, "finite"),
    (_edited(TRACED, ["metrics", "apply_roofline", "value"], 104.0), LAYER,
     True, "share"),
    (_edited(UNTRACED, ["device", "window_s"], 4.0), E2E, False,
     "device keys"),
    (_edited(UNTRACED, ["breakdown"], {"device_ops": [], "idle_gaps": []}),
     E2E, False, "keys"),
    (_edited(UNTRACED, ["failed"], 401), E2E, False, "exceeds"),
    (_edited(UNTRACED, ["correct"], 1), E2E, False, "boolean"),
    (_edited(TRACED, ["breakdown", "device_ops"],
             [[f"op{i}", 0.1] for i in range(11)]), LAYER, True, "entries"),
])
def test_rejects(line, declared, traced, why):
  with pytest.raises(result_line.InvalidResult, match=why):
    result_line.validate(line, declared, traced)


def test_build_prints_exactly_the_keys_the_driver_reads():
  text = result_line.build(
      correct=True, attempted=87, failed=0,
      values={"step_device_ms": 46.5, "apply_roofline": 3.1},
      declared=LAYER, device=TRACED["device"], traced=True,
      breakdown=TRACED["breakdown"])
  assert "\n" not in text
  assert json.loads(text) == TRACED
  with pytest.raises(result_line.InvalidResult, match="missing"):
    result_line.build(correct=True, attempted=1, failed=0,
                      values={"setup_s": 1.0}, declared=E2E, device=DEVICE,
                      traced=False)
