"""Everything the harness knows about a cell comes from data files found by
name: ``BENCHMARK.json`` at the root, then under ``benchmark/`` the
configuration (``configs/<config>.json``), the traffic mix
(``workloads/<traffic>.json``), the family module the configuration names
(``families/<family>.py``) and one file per per-layer metric
(``layer_metrics/<metric>.json``, or ``<metric>.py`` for a reader of a new
kind). A later PR adds files and entries; nothing here names a cell, a
configuration or a metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(ValueError):
  """A data file is missing, or does not say what the harness needs."""


def _read_json(path: str) -> Any:
  try:
    with open(path) as f:
      return json.load(f)
  except FileNotFoundError:
    raise SpecError(f"missing {path}") from None
  except json.JSONDecodeError as e:
    raise SpecError(f"{path} is not JSON: {e}") from None


def load_module(path: str, name: str):
  """A family or a metric reader, loaded from its file under ``root``."""
  if not os.path.exists(path):
    raise SpecError(f"missing {path}")
  spec = importlib.util.spec_from_file_location(name, path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


@dataclasses.dataclass(frozen=True)
class Cell:
  """One entry of ``workloads`` with everything it points to, loaded."""
  name: str
  chips: int
  config_name: str
  config: Dict[str, Any]
  traffic_name: str
  traffic: Dict[str, Any]
  end_to_end: List[Dict[str, Any]]   # the metrics this cell reports,
  per_layer: List[Dict[str, Any]]    # as BENCHMARK.json declares them
  root: str

  def family(self):
    fam = self.config["family"]
    return load_module(
        os.path.join(self.root, "benchmark", "families", f"{fam}.py"),
        f"benchmark_family_{fam}")

  def layer_reader(self, metric: str):
    """``reader(trace, cell) -> float | None`` of one per-layer metric."""
    from benchmark import trace_reduce
    base = os.path.join(self.root, "benchmark", "layer_metrics", metric)
    if os.path.exists(base + ".py"):
      return load_module(base + ".py", f"benchmark_metric_{metric}").read
    return trace_reduce.reader_from_spec(_read_json(base + ".json"))


def _reported_in(metric: Dict[str, Any], cell: str) -> bool:
  return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
  bench = _read_json(os.path.join(root, "BENCHMARK.json"))
  cells = {w["name"]: w for w in bench["workloads"]}
  if name not in cells:
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                    f"{sorted(cells)}")
  w = cells[name]
  configs = {c["name"]: c for c in bench["configs"]}
  if w["config"] not in configs:
    raise SpecError(f"workload {name!r} names config {w['config']!r}, "
                    "which BENCHMARK.json does not list")
  config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
  traffic = _read_json(os.path.join(root, "benchmark", "workloads",
                                    f"{w['traffic']}.json"))
  return Cell(
      name=name, chips=int(w["chips"]), config_name=w["config"],
      config=config, traffic_name=w["traffic"], traffic=traffic,
      end_to_end=[m for m in bench["end_to_end"] if _reported_in(m, name)],
      per_layer=[m for m in bench["per_layer"] if _reported_in(m, name)],
      root=root)
