"""The last line of a run: built in one place and checked against what the
cell declares before it is printed. A run that cannot build a valid line
exits non-zero with the reason and prints no result.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, List, Optional


class InvalidResult(ValueError):
  pass


def _number(x: Any, what: str) -> float:
  if isinstance(x, bool) or not isinstance(x, (int, float)) \
      or not math.isfinite(x):
    raise InvalidResult(f"{what} is not a finite number: {x!r}")
  return x


def validate(line: Dict[str, Any], declared: List[Dict[str, Any]],
             traced: bool) -> None:
  """``declared``: the metrics this cell owes in this kind of run (name and
  unit): its end-to-end metrics untraced, its per-layer metrics traced."""
  keys = {"correct", "attempted", "failed", "metrics", "device"}
  allowed = keys | ({"breakdown"} if traced else set())
  if set(line) - allowed or keys - set(line):
    raise InvalidResult(f"keys {sorted(line)}; owed {sorted(keys)}"
                        + (" and optionally breakdown" if traced else ""))
  if not isinstance(line["correct"], bool):
    raise InvalidResult("correct is not a boolean")
  for k in ("attempted", "failed"):
    if isinstance(line[k], bool) or not isinstance(line[k], int) \
        or line[k] < 0:
      raise InvalidResult(f"{k} is not a count: {line[k]!r}")
  if line["failed"] > line["attempted"]:
    raise InvalidResult("failed exceeds attempted")
  units = {m["name"]: m["unit"] for m in declared}
  got = line["metrics"]
  extra = set(got) - set(units)
  if extra:
    raise InvalidResult(f"undeclared metrics {sorted(extra)}")
  missing = set(units) - set(got)
  if missing:
    raise InvalidResult(f"missing metrics {sorted(missing)}")
  for name, m in got.items():
    if set(m) != {"value", "unit"}:
      raise InvalidResult(f"metric {name} has keys {sorted(m)}")
    _number(m["value"], f"metric {name}")
    if m["unit"] != units[name]:
      raise InvalidResult(f"metric {name} unit {m['unit']!r}, declared "
                          f"{units[name]!r}")
    if units[name] == "%" and ("roofline" in name or "mfu" in name) \
        and not 0 <= m["value"] <= 100:
      raise InvalidResult(f"{name} = {m['value']} is not a share")
  dev = line["device"]
  owed = {"platform", "kind", "count", "memory_peak_bytes"}
  if traced:
    owed |= {"window_s", "busy_s"}
  if set(dev) != owed:
    raise InvalidResult(f"device keys {sorted(dev)}; owed {sorted(owed)}")
  if _number(dev["memory_peak_bytes"], "memory_peak_bytes") <= 0:
    raise InvalidResult("memory_peak_bytes is not above 0")
  if traced:
    busy = _number(dev["busy_s"], "busy_s")
    window = _number(dev["window_s"], "window_s")
    if not 0 < busy <= window:
      raise InvalidResult(f"busy_s {busy} is not in (0, window_s {window}]")
    bd = line.get("breakdown")
    if bd is not None:
      if set(bd) != {"device_ops", "idle_gaps"}:
        raise InvalidResult(f"breakdown keys {sorted(bd)}")
      for k, rows in bd.items():
        if len(rows) > 10:
          raise InvalidResult(f"breakdown.{k} has {len(rows)} entries")
        for row in rows:
          if len(row) != 2 or not isinstance(row[0], str):
            raise InvalidResult(f"breakdown.{k} entry {row!r}")
          _number(row[1], f"breakdown.{k} {row[0]}")


def build(*, correct: bool, attempted: int, failed: int,
          values: Dict[str, float], declared: List[Dict[str, Any]],
          device: Dict[str, Any], traced: bool,
          breakdown: Optional[Dict[str, Any]] = None) -> str:
  """The line as text, or :class:`InvalidResult`."""
  units = {m["name"]: m["unit"] for m in declared}
  line = {
      "correct": bool(correct), "attempted": int(attempted),
      "failed": int(failed),
      "metrics": {n: {"value": v, "unit": units.get(n, "?")}
                  for n, v in values.items()},
      "device": device,
  }
  if traced and breakdown is not None:
    line["breakdown"] = breakdown
  validate(line, declared, traced)
  return json.dumps(line)
